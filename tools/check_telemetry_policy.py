#!/usr/bin/env python
"""Telemetry leak-policy checker (CI gate; invoked by a tier-1 test).

Two passes, mirroring how testing/leakcheck.py checks the transcript:

1. **Static scan** — grep every instrumentation call site under
   ``grapevine_tpu/`` for forbidden label keys (per-client / per-op
   dimensions). A kwarg like ``op_type=`` on a ``labels()``/``inc()``/
   ``observe()`` call, or a forbidden key inside a ``labels={...}``
   registration, fails the check with file:line — before the code ever
   runs.
2. **Registry audit** — instantiate the shipped registry (the one
   ``EngineMetrics`` builds, i.e. exactly what /metrics exports) and run
   ``TelemetryRegistry.audit()``: every label key must be allowlisted,
   every series declared, every histogram's buckets fixed.

Exit 0 = policy holds; exit 1 = a violation, printed with its location.

Run directly::

    python tools/check_telemetry_policy.py
"""

from __future__ import annotations

import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "grapevine_tpu")

#: must match obs.registry.FORBIDDEN_LABEL_KEYS (imported below for the
#: audit pass; duplicated here only to build the static regex without
#: importing before the scan)
_FORBIDDEN = (
    "client", "client_id", "session", "session_id", "channel",
    "channel_id", "user", "user_id", "identity", "auth", "auth_identity",
    "msg_id", "message_id", "sender", "recipient", "key", "block",
    "leaf", "path", "op", "op_type", "operation", "request_type",
)

#: telemetry call sites: sample calls with label kwargs, and
#: registration calls with a labels= declaration
_CALL_RE = re.compile(
    r"\.(?:labels|inc|observe|set|set_max|counter|gauge|histogram)\("
)
_KWARG_RES = [
    (k, re.compile(rf"[(,]\s*{k}\s*=")) for k in _FORBIDDEN
]
_DECL_RES = [
    (k, re.compile(rf"""labels\s*=\s*\{{[^}}]*['"]{k}['"]""")) for k in _FORBIDDEN
]


def _call_site_spans(text: str):
    """Yield (lineno, span_text) for each telemetry call, where span_text
    covers the call through its closing paren (label kwargs may sit on
    continuation lines)."""
    for m in _CALL_RE.finditer(text):
        start = m.end() - 1  # the opening paren
        depth = 0
        end = start
        for i in range(start, min(len(text), start + 2000)):
            c = text[i]
            if c == "(":
                depth += 1
            elif c == ")":
                depth -= 1
                if depth == 0:
                    end = i + 1
                    break
        yield text.count("\n", 0, m.start()) + 1, text[m.start():end]


def scan_call_sites() -> list[str]:
    """Static pass: forbidden label keys at instrumentation call sites."""
    violations = []
    for dirpath, _, names in os.walk(PKG):
        for name in sorted(names):
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            rel = os.path.relpath(path, REPO)
            with open(path, encoding="utf-8") as fh:
                text = fh.read()
            for lineno, span in _call_site_spans(text):
                for key, rx in _KWARG_RES:
                    if rx.search(span):
                        violations.append(
                            f"{rel}:{lineno}: telemetry call passes "
                            f"forbidden label key {key!r}"
                        )
            for key, rx in _DECL_RES:
                for m in rx.finditer(text):
                    lineno = text.count("\n", 0, m.start()) + 1
                    violations.append(
                        f"{rel}:{lineno}: metric registration declares "
                        f"forbidden label key {key!r}"
                    )
    return violations


def audit_shipped_registry() -> dict:
    """Runtime pass: the registry EngineMetrics ships must pass audit."""
    sys.path.insert(0, REPO)
    from grapevine_tpu.engine.metrics import EngineMetrics
    from grapevine_tpu.obs.registry import FORBIDDEN_LABEL_KEYS

    missing = set(_FORBIDDEN) - set(FORBIDDEN_LABEL_KEYS)
    if missing:
        raise SystemExit(
            f"checker's forbidden-key list drifted from obs.registry: "
            f"{sorted(missing)} not in FORBIDDEN_LABEL_KEYS"
        )
    return EngineMetrics().registry.audit()


def audit_leakmon_registry() -> dict:
    """Runtime pass over the leak monitor's metric namespace.

    Builds the registry exactly as a --leakmon engine does (EngineMetrics
    + EngineLeakMonitor on the same registry) and asserts, beyond the
    generic ``audit()``:

    - the ``grapevine_leakmon_*`` families exist (the continuous audit
      is actually exporting, not silently unregistered);
    - their only label key is ``tree`` with the declared tree names —
      aggregate-only by construction, never per-client/per-op;
    - any histogram in the namespace has registration-fixed buckets
      (audit() re-checks the boundaries object-level).
    """
    sys.path.insert(0, REPO)
    from grapevine_tpu.engine.metrics import EngineMetrics
    from grapevine_tpu.obs.flightrec import FlightRecorder
    from grapevine_tpu.obs.leakmon import EngineLeakMonitor

    em = EngineMetrics()
    mon = EngineLeakMonitor(
        mb_leaves=1 << 4, rec_leaves=1 << 7, mb_choices=2,
        registry=em.registry, recorder=FlightRecorder(capacity=8),
    )
    try:
        report = em.registry.audit()  # raises on any violation
        families = [
            m for m in em.registry.collect()
            if m.name.startswith("grapevine_leakmon_")
        ]
        if not families:
            raise SystemExit(
                "leakmon namespace missing: EngineLeakMonitor registered "
                "no grapevine_leakmon_* metrics"
            )
        for m in families:
            bad = set(m.label_keys) - {"tree"}
            if bad:
                raise SystemExit(
                    f"leakmon metric {m.name!r} carries label keys "
                    f"{sorted(bad)} — the continuous audit may only "
                    "aggregate by tree"
                )
        report["leakmon_families"] = len(families)
        return report
    finally:
        mon.close()


def audit_trace_slo_registry() -> dict:
    """Runtime pass over the round tracer's and SLO tracker's metric
    namespaces plus the tracer ring schema (ISSUE-6 satellite — the
    same TelemetryLeakError contract as the flight recorder):

    - the ``grapevine_trace_*`` / ``grapevine_slo_*`` families and the
      derived ``grapevine_round_bubble_ratio`` gauge exist and carry NO
      label keys (batch-level scalars only, no dimension to hide an
      identity in);
    - the tracer's span-name allowlist is exactly phases + derived
      windows — nothing outside the canonical PHASES vocabulary;
    - ``record_round`` rejects a per-op span name and a non-(start,dur)
      span value with TelemetryLeakError (enforcement has teeth, not
      just a clean default).
    """
    sys.path.insert(0, REPO)
    from grapevine_tpu.engine.metrics import EngineMetrics
    from grapevine_tpu.obs.phases import SPAN_NAMES
    from grapevine_tpu.obs.registry import TelemetryLeakError
    from grapevine_tpu.obs.slo import SloTracker
    from grapevine_tpu.obs.tracer import (
        ALLOWED_SPAN_NAMES, DERIVED_SPANS, RoundTracer)

    em = EngineMetrics()
    tracer = RoundTracer(capacity=8, registry=em.registry)
    SloTracker(registry=em.registry)
    report = em.registry.audit()  # raises on any violation

    families = [
        m for m in em.registry.collect()
        if m.name.startswith(("grapevine_trace_", "grapevine_slo_"))
        or m.name == "grapevine_round_bubble_ratio"
    ]
    if len(families) < 3:
        raise SystemExit(
            "trace/slo namespace missing: RoundTracer/SloTracker "
            f"registered only {[m.name for m in families]}"
        )
    for m in families:
        if m.label_keys:
            raise SystemExit(
                f"trace/slo metric {m.name!r} carries label keys "
                f"{list(m.label_keys)} — these series are batch-level "
                "scalars with no dimensions by design"
            )

    # a ledger takes the names the span primitive takes (obs/phases.py
    # SPAN_NAMES: the phases and the collector's further spans, all
    # whole-round) and the derived windows, and no other
    stray = ALLOWED_SPAN_NAMES - SPAN_NAMES - set(DERIVED_SPANS)
    if stray:
        raise SystemExit(
            f"tracer span allowlist drifted outside the phase "
            f"vocabulary: {sorted(stray)}"
        )
    for bad_ledger, bad_counts, why in (
        ({"op_read": (0.0, 1.0)}, None, "per-op span name"),
        ({"evict": "not-a-span"}, None, "non-(start,dur) span value"),
        ({"evict": (0.0, -1.0)}, None, "negative duration"),
        ({"evict": (0.0, 1.0)}, {"reads": 3}, "per-op-type count"),
        ({"evict": (0.0, 1.0)}, {"ops": "many"}, "non-numeric count"),
    ):
        try:
            tracer.record_round(bad_ledger, bad_counts)
        except TelemetryLeakError:
            continue
        raise SystemExit(
            f"tracer ring schema has no teeth: {why} was accepted"
        )
    report["trace_slo_families"] = len(families)
    return report


def audit_workload_registry() -> dict:
    """Runtime pass over the workload observatory's metric namespace
    (ISSUE-9 satellite — the ``grapevine_load_*`` families plus the
    flight recorder's queue-depth summary field):

    - the fill/depth histograms, arrival counter/gauge, utilization
      gauge, and saturation/backpressure counters exist; the ONLY
      label key anywhere in the namespace is ``phase`` (on the
      utilization gauge, with registration-declared values) — no
      dimension in which a client, key, or op type could travel;
    - histogram buckets are the registration-time FILL/DEPTH constants
      (fixed-bucket contract; a data-dependent layout is a signal);
    - schema teeth: the flight recorder accepts a scalar
      ``queue_depth`` and rejects an array-valued one with
      TelemetryLeakError (an array is how per-op data would ride a
      batch-level field).
    """
    sys.path.insert(0, REPO)
    from grapevine_tpu.engine.metrics import EngineMetrics
    from grapevine_tpu.obs.flightrec import FlightRecorder
    from grapevine_tpu.obs.registry import TelemetryLeakError
    from grapevine_tpu.obs.workload import (
        DEPTH_BUCKETS,
        FILL_BUCKETS,
        WorkloadTelemetry,
    )

    em = EngineMetrics()
    WorkloadTelemetry(em.registry, batch_size=256)
    report = em.registry.audit()  # raises on any violation

    families = [
        m for m in em.registry.collect()
        if m.name.startswith("grapevine_load_")
    ]
    if len(families) < 6:
        raise SystemExit(
            "workload namespace missing: WorkloadTelemetry registered "
            f"only {[m.name for m in families]}"
        )
    for m in families:
        bad = set(m.label_keys) - {"phase"}
        if bad:
            raise SystemExit(
                f"workload metric {m.name!r} carries label keys "
                f"{sorted(bad)} — workload telemetry may only "
                "aggregate by phase"
            )
    fill = em.registry.get("grapevine_load_batch_fill")
    depth = em.registry.get("grapevine_load_queue_depth")
    if fill is None or fill.buckets != tuple(FILL_BUCKETS):
        raise SystemExit("fill histogram buckets drifted from the "
                         "registration-time constants")
    if depth is None or depth.buckets != tuple(DEPTH_BUCKETS):
        raise SystemExit("depth histogram buckets drifted from the "
                         "registration-time constants")

    fr = FlightRecorder(capacity=2)
    fr.record({"seq": 1, "fill": 0.5, "queue_depth": 17})  # scalar: fine
    try:
        fr.record({"seq": 2, "queue_depth": [1, 2, 3]})
    except TelemetryLeakError:
        pass
    else:
        raise SystemExit(
            "flight recorder accepted an array-valued queue_depth — "
            "the batch-level schema has no teeth"
        )
    report["workload_families"] = len(families)
    return report


def audit_fleet_registry() -> dict:
    """Runtime pass over the fleet observatory's metric namespace
    (ISSUE-16 satellite — the ``grapevine_fleet_*`` families the
    aggregator and the cross-shard uniformity monitor register):

    - ``shard`` is the ONLY label key anywhere in the namespace, and
      every declared value is a bare integer index (position in the
      declared member list — public topology; a member NAME or
      ADDRESS in a label value would export deployment identity);
    - the uniformity detectors export statistic/threshold/verdict
      scalars only — label-free pairs per detector, no per-shard
      payload-derived fields (the per-shard series the detectors
      consume stay inside the monitor);
    - teeth: registering a member-name or address label value under
      ``shard``, or a ``member`` label key, raises TelemetryLeakError
      at registration — the integer-index rule is enforcement, not
      convention.
    """
    sys.path.insert(0, REPO)
    from grapevine_tpu.obs.fleet import FleetAggregator, FleetConfig
    from grapevine_tpu.obs.registry import (
        TelemetryLeakError,
        TelemetryRegistry,
    )

    agg = FleetAggregator(FleetConfig(members=("h0:1", "h1:1", "h2:1")))
    report = agg.registry.audit()  # raises on any violation

    families = [
        m for m in agg.registry.collect()
        if m.name.startswith("grapevine_fleet_")
    ]
    if len(families) < 8:
        raise SystemExit(
            "fleet namespace missing: aggregator registered only "
            f"{[m.name for m in families]}"
        )
    for m in families:
        bad = set(m.label_keys) - {"shard"}
        if bad:
            raise SystemExit(
                f"fleet metric {m.name!r} carries label keys "
                f"{sorted(bad)} — 'shard' is the only permitted key "
                "in the grapevine_fleet_* namespace"
            )
        for v in m.labels_decl.get("shard", ()):
            if not (v.isascii() and v.isdigit()):
                raise SystemExit(
                    f"fleet metric {m.name!r} declares shard value "
                    f"{v!r} — values must be bare integer indices"
                )
    # the uniformity detector exports: statistic/threshold pairs per
    # detector plus the verdict gauge, all label-free scalars
    for det in ("cadence_ratio", "fill_load_correlation"):
        for kind in ("statistic", "threshold"):
            name = f"grapevine_fleet_uniformity_{det}_{kind}"
            m = agg.registry.get(name)
            if m is None:
                raise SystemExit(f"uniformity export {name!r} missing")
            if m.label_keys:
                raise SystemExit(
                    f"uniformity export {name!r} carries label keys "
                    f"{list(m.label_keys)} — detector exports are "
                    "label-free scalars by policy"
                )
    if agg.registry.get("grapevine_fleet_uniformity_suspect") is None:
        raise SystemExit("uniformity verdict gauge missing")

    # teeth: member identity can never ride a label
    r = TelemetryRegistry()
    for labels, why in (
        ({"shard": ("engine-a.internal",)}, "member-name shard value"),
        ({"shard": ("10.0.0.7:9464",)}, "address shard value"),
        ({"member": ("0",)}, "'member' label key"),
    ):
        try:
            r.gauge("grapevine_fleet_teeth_probe", "probe", labels=labels)
        except TelemetryLeakError:
            continue
        raise SystemExit(
            f"fleet label policy has no teeth: {why} was accepted at "
            "registration"
        )
    report["fleet_families"] = len(families)
    return report


def audit_cost_registry() -> dict:
    """Runtime pass over the cost observatory's metric namespace.

    Builds the registry exactly as ``attach_round_observability`` does
    (a CostMonitor over a real EngineConfig) and asserts, beyond the
    generic ``audit()``:

    - the ``grapevine_cost_*`` families exist (the ledger is actually
      exporting: per-phase bytes/rows/cipher/sort, the steady-state
      total, the calibrated bandwidth, the roofline floor + residual);
    - ``phase`` is the only label key in the namespace, and its
      declared values are exactly the model's fixed schedule names
      (:data:`costmodel.COST_PHASES`) — public program structure.
      Geometry belongs in gauge VALUES (which any observer could
      derive from the config), never in label sets;
    - teeth: a geometry-shaped label key (``capacity``/``geometry``)
      or a geometry value smuggled into ``phase`` raises
      TelemetryLeakError at registration — the allowlist plus the
      fixed-phase rule are enforcement, not convention.
    """
    sys.path.insert(0, REPO)
    from grapevine_tpu.analysis.costmodel import COST_PHASES
    from grapevine_tpu.config import GrapevineConfig
    from grapevine_tpu.engine.state import EngineConfig
    from grapevine_tpu.obs.costmon import CostMonitor
    from grapevine_tpu.obs.registry import (
        TelemetryLeakError,
        TelemetryRegistry,
    )

    reg = TelemetryRegistry()
    ecfg = EngineConfig.from_config(GrapevineConfig(
        max_messages=1 << 10, max_recipients=1 << 7, batch_size=8,
    ))
    CostMonitor(ecfg, reg, bandwidth_gbps=8.0)
    report = reg.audit()  # raises on any violation

    families = [
        m for m in reg.collect() if m.name.startswith("grapevine_cost_")
    ]
    if len(families) < 9:
        raise SystemExit(
            "cost namespace missing: CostMonitor registered only "
            f"{[m.name for m in families]}"
        )
    for m in families:
        bad = set(m.label_keys) - {"phase"}
        if bad:
            raise SystemExit(
                f"cost metric {m.name!r} carries label keys "
                f"{sorted(bad)} — 'phase' is the only permitted key "
                "in the grapevine_cost_* namespace"
            )
        for v in m.labels_decl.get("phase", ()):
            if v not in COST_PHASES:
                raise SystemExit(
                    f"cost metric {m.name!r} declares phase value "
                    f"{v!r} — values must be the fixed schedule names "
                    f"{COST_PHASES}, never geometry"
                )
    for name in ("grapevine_cost_roofline_residual",
                 "grapevine_cost_roofline_floor_ms",
                 "grapevine_cost_steady_round_hbm_bytes"):
        m = reg.get(name)
        if m is None:
            raise SystemExit(f"cost export {name!r} missing")
        if m.label_keys:
            raise SystemExit(
                f"cost export {name!r} carries label keys "
                f"{list(m.label_keys)} — roofline exports are "
                "label-free scalars by policy"
            )

    # teeth: geometry can never ride a label in this namespace
    r = TelemetryRegistry()
    for labels, why in (
        ({"capacity": ("65536",)}, "geometry-value 'capacity' label key"),
        ({"geometry": ("h14_z4",)}, "'geometry' label key"),
        ({"leaf": ("12",)}, "'leaf' label key"),
    ):
        try:
            r.gauge("grapevine_cost_teeth_probe", "probe", labels=labels)
        except TelemetryLeakError:
            continue
        raise SystemExit(
            f"cost label policy has no teeth: {why} was accepted at "
            "registration"
        )
    report["cost_families"] = len(families)
    return report


def audit_host_registry() -> dict:
    """Runtime pass over the host serving pipeline's metric namespace
    (ISSUE-20 satellite — the ``grapevine_host_*`` families from the
    multiprocess verify/codec pool and the SLO-adaptive window policy):

    - builds the registry exactly as the serving layer does — a real
      ``HostPipeline`` (worker processes spawned, then closed) and a
      real ``AdaptiveBatchPolicy`` registering into one merged
      registry, as /metrics serves it;
    - the ONLY label keys anywhere in the namespace are ``phase``
      (declared task kinds / decision kinds — fixed vocabularies) and
      ``worker`` (pool indices declared at registration from the
      --host-workers config: public topology, never identity);
    - ``worker`` values are exactly the configured pool's digit
      strings — many channels hash onto one worker and the mapping is
      never exported, so the index reveals pool size only;
    - teeth: a channel-id-shaped ``worker`` value, a non-digit worker
      name, and a ``channel_id`` label key each raise
      TelemetryLeakError at registration — the sticky-routing design
      (sessions pinned to workers by channel hash) is precisely where
      a per-channel dimension would be tempting, so the rule is
      enforcement, not convention.
    """
    sys.path.insert(0, REPO)
    from grapevine_tpu.server.adaptive import (
        DECISION_KINDS,
        AdaptiveBatchPolicy,
    )
    from grapevine_tpu.server.hostpipe import TASK_KINDS, HostPipeline
    from grapevine_tpu.obs.registry import (
        TelemetryLeakError,
        TelemetryRegistry,
    )

    reg = TelemetryRegistry()
    pipe = HostPipeline(workers=2, registry=reg)
    try:
        AdaptiveBatchPolicy(8, 0.008, 0.002, registry=reg)
    finally:
        pipe.close()
    report = reg.audit()  # raises on any violation

    families = [
        m for m in reg.collect() if m.name.startswith("grapevine_host_")
    ]
    if len(families) < 8:
        raise SystemExit(
            "host namespace missing: serving layer registered only "
            f"{[m.name for m in families]}"
        )
    for m in families:
        bad = set(m.label_keys) - {"phase", "worker"}
        if bad:
            raise SystemExit(
                f"host metric {m.name!r} carries label keys "
                f"{sorted(bad)} — 'phase' and 'worker' are the only "
                "permitted keys in the grapevine_host_* namespace"
            )
        for v in m.labels_decl.get("worker", ()):
            if not v.isdigit():
                raise SystemExit(
                    f"host metric {m.name!r} declares worker value "
                    f"{v!r} — worker values must be pool indices "
                    "(digit strings), never names or identities"
                )
    tasks = reg.get("grapevine_host_tasks_total")
    if tasks is None or tuple(tasks.labels_decl["worker"]) != ("0", "1"):
        raise SystemExit(
            "grapevine_host_tasks_total worker values drifted from the "
            "configured pool indices"
        )
    for v in tasks.labels_decl["phase"]:
        if v not in TASK_KINDS:
            raise SystemExit(
                f"grapevine_host_tasks_total declares phase {v!r} — "
                f"values must be the fixed task kinds {TASK_KINDS}"
            )
    dec = reg.get("grapevine_host_adaptive_decisions_total")
    if dec is None:
        raise SystemExit("adaptive decision counter missing")
    for v in dec.labels_decl["phase"]:
        if v not in DECISION_KINDS:
            raise SystemExit(
                f"adaptive decision counter declares phase {v!r} — "
                f"values must be the fixed decision kinds "
                f"{DECISION_KINDS}"
            )

    # teeth: a channel identity can never ride the worker dimension
    r = TelemetryRegistry()
    for labels, why in (
        ({"worker": ("deadbeef" * 4,)}, "channel-id-shaped worker value"),
        ({"worker": ("w0",)}, "non-digit worker value"),
        ({"channel_id": ("0",)}, "'channel_id' label key"),
    ):
        try:
            r.counter("grapevine_host_teeth_probe", "probe", labels=labels)
        except TelemetryLeakError:
            continue
        raise SystemExit(
            f"host label policy has no teeth: {why} was accepted at "
            "registration"
        )
    report["host_families"] = len(families)
    return report


def main() -> int:
    violations = scan_call_sites()
    for v in violations:
        print(f"TELEMETRY POLICY VIOLATION: {v}", file=sys.stderr)
    report = audit_shipped_registry()
    lm_report = audit_leakmon_registry()
    ts_report = audit_trace_slo_registry()
    wl_report = audit_workload_registry()
    fl_report = audit_fleet_registry()
    cost_report = audit_cost_registry()
    host_report = audit_host_registry()
    print(
        f"telemetry policy: static scan "
        f"{'FAILED' if violations else 'clean'}; registry audit ok "
        f"({report['metrics']} metrics, {report['series']} series); "
        f"leakmon audit ok ({lm_report['leakmon_families']} families, "
        f"{lm_report['series']} series incl. engine); trace/slo audit "
        f"ok ({ts_report['trace_slo_families']} families, ring schema "
        f"enforced); workload audit ok ({wl_report['workload_families']} "
        "families, fixed buckets, depth-field teeth); "
        f"fleet audit ok ({fl_report['fleet_families']} families, "
        "shard-only integer labels, teeth); cost audit ok "
        f"({cost_report['cost_families']} families, phase-only labels, "
        "fixed schedule values, teeth); host audit ok "
        f"({host_report['host_families']} families, phase/worker-only "
        "labels, digit worker indices, teeth)"
    )
    return 1 if violations else 0


if __name__ == "__main__":
    raise SystemExit(main())
