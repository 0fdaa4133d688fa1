"""Telemetry leak audit (obs/registry.py): the allowlist has teeth, the
shipped registry is batch-level only, and the CI policy checker agrees.

The telemetry counterpart of test_leak_canary.py: those tests prove the
transcript detectors catch deliberately-leaky engines; these prove the
registry rejects deliberately-leaky *metrics* — per-client / per-op
label keys, undeclared label values, mutable bucket boundaries.
"""

import importlib.util
import os

import pytest

from grapevine_tpu.engine.metrics import EngineMetrics
from grapevine_tpu.obs import (
    ALLOWED_LABEL_KEYS,
    FORBIDDEN_LABEL_KEYS,
    TelemetryLeakError,
    TelemetryRegistry,
    render_prometheus,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- registration-time rejection ---------------------------------------


@pytest.mark.parametrize("key", ["op_type", "client_id", "msg_id", "recipient"])
def test_forbidden_label_key_raises_at_registration(key):
    reg = TelemetryRegistry()
    with pytest.raises(TelemetryLeakError, match="side channel|allowlist"):
        reg.counter("grapevine_bad_total", "nope", labels={key: ("x",)})


def test_unallowlisted_key_raises_even_if_not_explicitly_forbidden():
    reg = TelemetryRegistry()
    with pytest.raises(TelemetryLeakError, match="allowlist"):
        reg.gauge("grapevine_bad", "nope", labels={"color": ("red",)})


def test_label_values_must_be_declared():
    reg = TelemetryRegistry()
    with pytest.raises(TelemetryLeakError, match="no values"):
        reg.counter("grapevine_bad_total", "nope", labels={"phase": ()})


def test_undeclared_label_value_raises_at_sample_time():
    reg = TelemetryRegistry()
    h = reg.histogram(
        "grapevine_x_seconds", "x", buckets=(0.1, 1.0),
        labels={"phase": ("verify",)},
    )
    h.observe(0.5, phase="verify")  # declared: fine
    with pytest.raises(TelemetryLeakError, match="not.*declared|dynamic"):
        # a session token smuggled through a *safe* key is still a leak
        h.observe(0.5, phase="deadbeef")


def test_histogram_buckets_fixed_and_sorted():
    reg = TelemetryRegistry()
    with pytest.raises(ValueError, match="increasing"):
        reg.histogram("grapevine_h_seconds", "h", buckets=(1.0, 0.5))
    with pytest.raises(ValueError, match="increasing"):
        reg.histogram("grapevine_h2_seconds", "h", buckets=())


def test_duplicate_metric_name_raises():
    reg = TelemetryRegistry()
    reg.counter("grapevine_a_total", "a")
    with pytest.raises(ValueError, match="duplicate"):
        reg.counter("grapevine_a_total", "again")


def test_forbidden_and_allowed_sets_disjoint():
    assert not (ALLOWED_LABEL_KEYS & FORBIDDEN_LABEL_KEYS)


# -- the audit over the shipped registry -------------------------------


def test_shipped_registry_passes_audit():
    report = EngineMetrics().registry.audit()
    assert report["ok"] and report["metrics"] >= 10


def test_audit_catches_smuggled_series():
    """A series injected past the public API (simulating a bug) fails
    the audit even though registration-time checks never saw it."""
    m = EngineMetrics()
    counter = m.registry.get("grapevine_rounds_total")
    from grapevine_tpu.obs.registry import _CounterChild

    counter._children[("deadbeef",)] = _CounterChild()
    with pytest.raises(TelemetryLeakError, match="undeclared series"):
        m.registry.audit()


def test_telemetry_policy_checker_clean():
    """The CI gate (tools/check_telemetry_policy.py) passes on the tree
    as shipped: no forbidden label keys at any instrumentation call
    site, and the shipped registry audits clean. Unmarked on purpose —
    it rides the tier-1 ``-m 'not slow'`` run, so a policy regression
    fails CI fast."""
    path = os.path.join(REPO, "tools", "check_telemetry_policy.py")
    spec = importlib.util.spec_from_file_location("check_telemetry_policy", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.scan_call_sites() == []
    assert mod.audit_shipped_registry()["ok"]


def test_host_namespace_audit_and_teeth():
    """The host serving pipeline's namespace audit (ISSUE-20 satellite):
    ``audit_host_registry`` builds the real HostPipeline + adaptive
    policy against one registry and passes —
    and the teeth it relies on bite here directly: a channel-id-valued
    ``worker`` label (the exact identity the sticky channel→worker
    routing could be tempted to export) raises TelemetryLeakError at
    registration, as does a ``channel_id`` label key."""
    path = os.path.join(REPO, "tools", "check_telemetry_policy.py")
    spec = importlib.util.spec_from_file_location(
        "check_telemetry_policy_host", path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    report = mod.audit_host_registry()
    assert report["ok"] and report["host_families"] >= 8

    reg = TelemetryRegistry()
    with pytest.raises(TelemetryLeakError):
        reg.counter(
            "grapevine_host_tasks_total", "t",
            labels={"worker": ("deadbeef" * 4,)},
        )
    with pytest.raises(TelemetryLeakError):
        reg.counter(
            "grapevine_host_tasks_total", "t",
            labels={"channel_id": ("0",)},
        )


# -- exposition format -------------------------------------------------


def test_prometheus_render_format():
    reg = TelemetryRegistry()
    c = reg.counter("grapevine_ops_total", "ops")
    c.inc(3)
    h = reg.histogram(
        "grapevine_t_seconds", "t", buckets=(0.1, 1.0),
        labels={"phase": ("verify", "dispatch")},
    )
    h.observe(0.05, phase="verify")
    h.observe(0.5, phase="verify")
    h.observe(2.0, phase="verify")
    text = render_prometheus(reg)
    assert "# TYPE grapevine_ops_total counter" in text
    assert "grapevine_ops_total 3" in text
    # cumulative buckets: le="0.1" 1, le="1" 2, +Inf == count == 3
    assert 'grapevine_t_seconds_bucket{phase="verify",le="0.1"} 1' in text
    assert 'grapevine_t_seconds_bucket{phase="verify",le="1"} 2' in text
    assert 'grapevine_t_seconds_bucket{phase="verify",le="+Inf"} 3' in text
    assert 'grapevine_t_seconds_count{phase="verify"} 3' in text
    # the undriven series exists with zero samples (stable scrape schema)
    assert 'grapevine_t_seconds_count{phase="dispatch"} 0' in text


def test_prometheus_escaping_per_0_0_4():
    """ISSUE 2 satellite: HELP text escapes ``\\`` and newlines; label
    values escape ``\\``, ``"``, and newlines — a declared value with a
    quote must not corrupt the series name for everything after it."""
    reg = TelemetryRegistry()
    g = reg.gauge(
        "grapevine_esc_test",
        'help with \\ backslash\nand "newline" line',
        labels={"phase": ('va"l\\ue\nx', "plain")},
    )
    g.set(1.0, phase='va"l\\ue\nx')
    text = render_prometheus(reg)
    assert (
        "# HELP grapevine_esc_test "
        'help with \\\\ backslash\\nand "newline" line'
    ) in text
    assert 'grapevine_esc_test{phase="va\\"l\\\\ue\\nx"} 1' in text
    # every line still parses as comment-or-sample (no raw newlines
    # smuggled mid-line)
    for line in text.splitlines():
        assert line.startswith("#") or " " in line


def test_leakmon_gauges_under_registry_policy():
    """The leakmon namespace registers through the same audited
    registry: tree-labeled aggregates only, audit() clean."""
    from grapevine_tpu.obs.flightrec import FlightRecorder
    from grapevine_tpu.obs.leakmon import EngineLeakMonitor

    em = EngineMetrics()
    mon = EngineLeakMonitor(
        mb_leaves=16, rec_leaves=128, mb_choices=2,
        registry=em.registry, recorder=FlightRecorder(capacity=8),
    )
    try:
        report = em.registry.audit()
        assert report["ok"]
        fams = [m.name for m in em.registry.collect()
                if m.name.startswith("grapevine_leakmon_")]
        assert "grapevine_leakmon_samekey_collision_rate" in fams
        assert "grapevine_leakmon_cross_round_repeat_rate" in fams
        assert "grapevine_leakmon_uniformity_z" in fams
        assert "grapevine_leakmon_suspect" in fams
        for m in em.registry.collect():
            if m.name.startswith("grapevine_leakmon_"):
                assert set(m.label_keys) <= {"tree"}
    finally:
        mon.close()
