"""Round tracer + commit-latency SLO engine (obs/tracer.py, obs/slo.py)
and their serving-layer wiring (ISSUE 6).

Three layers, mirroring the PR-1/2 test split:

- unit: bubble-ratio math on synthetic ledgers, ring wrap, the span
  schema's TelemetryLeakError teeth, SLO burn-rate math on a fake clock;
- endpoint: a live engine tier serves /trace as valid Chrome trace JSON
  (Perfetto-loadable), the bubble/SLO series on /metrics, and a gated
  /profile capture;
- policy: no per-op field survives in any exported span (the leak-check
  acceptance), and a burning SLO flips /healthz to 503.
"""

import json
import urllib.error
import urllib.request

import pytest

from grapevine_tpu.obs.registry import TelemetryLeakError, TelemetryRegistry
from grapevine_tpu.obs.slo import SloConfig, SloTracker
from grapevine_tpu.obs.tracer import (
    ALLOWED_SPAN_NAMES,
    STABLE_SPANS,
    RoundTracer,
)

NOW = 1_700_000_000


# -- tracer units -------------------------------------------------------


def test_bubble_ratio_math():
    """bubble = evict wait / round span, meaned over the window."""
    tr = RoundTracer(capacity=8)
    tr.record_round({"round": (0.0, 10.0), "evict": (5.0, 4.0)})
    assert tr.bubble_ratio() == pytest.approx(0.4)
    tr.record_round({"round": (10.0, 10.0), "evict": (15.0, 2.0)})
    assert tr.bubble_ratio() == pytest.approx(0.3)  # mean(0.4, 0.2)
    # zero-length rounds contribute no ratio rather than a div-by-zero
    tr.record_round({"round": (20.0, 0.0)})
    assert tr.bubble_ratio() == pytest.approx(0.3)


def test_bubble_window_bounds_the_mean():
    tr = RoundTracer(capacity=8, bubble_window=1)
    tr.record_round({"round": (0.0, 10.0), "evict": (0.0, 10.0)})
    tr.record_round({"round": (10.0, 10.0), "evict": (10.0, 0.0)})
    assert tr.bubble_ratio() == pytest.approx(0.0)  # only the last round


def test_ring_wraps_and_counts():
    tr = RoundTracer(capacity=4)
    for i in range(6):
        tr.record_round({"round": (float(i), 1.0)})
    trace = tr.chrome_trace()
    assert trace["otherData"]["rounds_recorded_total"] == 6
    assert trace["otherData"]["rounds_retained"] == 4
    seqs = {e["args"]["seq"] for e in trace["traceEvents"]
            if e.get("cat") == "round"}
    assert seqs == {3, 4, 5, 6}


def test_stable_span_shape_without_durability():
    """The satellite contract: a ledger recorded WITHOUT journal /
    checkpoint / device spans still exports all STABLE_SPANS (zero
    duration), so trace consumers see one JSON shape across configs."""
    tr = RoundTracer(capacity=4)
    tr.record_round({"dispatch": (1.0, 0.5), "evict": (1.5, 0.2),
                     "demux": (1.7, 0.1), "round": (1.0, 0.8),
                     "device": (1.4, 0.3)})
    trace = tr.chrome_trace()
    names = {e["name"] for e in trace["traceEvents"]
             if e.get("cat") == "round"}
    assert names == {f"grapevine/{s}" for s in STABLE_SPANS}
    zero = [e for e in trace["traceEvents"]
            if e["name"] in ("grapevine/journal", "grapevine/checkpoint")]
    assert zero and all(e["dur"] == 0 for e in zero)


def test_chrome_trace_is_valid_and_loadable_shape():
    tr = RoundTracer(capacity=4)
    tr.record_round({"round": (0.0, 0.01), "evict": (0.0, 0.004)})
    parsed = json.loads(tr.chrome_trace_json())
    assert isinstance(parsed["traceEvents"], list) and parsed["traceEvents"]
    for e in parsed["traceEvents"]:
        assert {"name", "ph", "pid"} <= set(e)
        if e["ph"] == "X":  # complete events: the Perfetto essentials
            assert {"ts", "dur", "tid", "cat"} <= set(e)
            assert isinstance(e["ts"], int) and e["dur"] >= 0
    # the device windows and the queue wait ride their own thread
    # tracks (seq 1 = lane 1)
    tids = {e["tid"] for e in parsed["traceEvents"] if e["ph"] == "X"}
    assert tids == {2, 4, 6, 8}
    by_name = {e["name"]: e["tid"] for e in parsed["traceEvents"]
               if e["ph"] == "X"}
    assert by_name["grapevine/device"] == by_name["grapevine/inflight"] == 4
    assert by_name["grapevine/queue"] == 6
    assert by_name["grapevine/settle"] == by_name["grapevine/round"] == 2


def test_chrome_trace_lanes_keep_pipelined_rounds_disjoint():
    """Complete ("X") events sharing a tid must nest or stay disjoint
    (the trace-event format contract). Adjacent pipelined rounds
    overlap — round k's evict/demux run after round k+1's assembly —
    so consecutive rounds must land on different lanes, and events
    within one lane must never partially overlap."""
    tr = RoundTracer(capacity=8)
    # two pipelined rounds: round 2 starts before round 1 ends
    tr.record_round({"round": (0.0, 1.0), "evict": (0.6, 0.4),
                     "device": (0.0, 0.9)})
    tr.record_round({"round": (0.5, 1.0), "evict": (1.2, 0.3),
                     "device": (0.5, 1.4)})
    events = [e for e in tr.chrome_trace()["traceEvents"]
              if e.get("ph") == "X"]
    lanes = {e["args"]["seq"]: e["tid"] for e in events
             if e["name"] == "grapevine/round"}
    assert lanes[1] != lanes[2]
    by_tid: dict = {}
    for e in events:
        by_tid.setdefault(e["tid"], []).append((e["ts"], e["ts"] + e["dur"]))
    for tid, spans in by_tid.items():
        for a0, a1 in spans:
            for b0, b1 in spans:
                # disjoint, nested, or identical — never partial overlap
                assert (a1 <= b0 or b1 <= a0
                        or (a0 >= b0 and a1 <= b1)
                        or (b0 >= a0 and b1 <= a1)), (tid, spans)


def test_span_schema_has_teeth():
    """A span is a phase, never an operation — the leak-check
    acceptance: per-op names and malformed values raise."""
    tr = RoundTracer(capacity=4)
    with pytest.raises(TelemetryLeakError, match="not a round phase"):
        tr.record_round({"op_read_client_7": (0.0, 1.0)})
    with pytest.raises(TelemetryLeakError, match="pair of numbers"):
        tr.record_round({"evict": "payload-bytes-here"})
    with pytest.raises(TelemetryLeakError, match="negative"):
        tr.record_round({"evict": (0.0, -1.0)})
    with pytest.raises(TelemetryLeakError, match="must be a"):
        tr.record_round([("evict", (0.0, 1.0))])
    # nothing leaked into the ring by the failed records
    assert tr.chrome_trace()["otherData"]["rounds_recorded_total"] == 0


def test_allowed_span_names_stay_inside_phase_vocabulary():
    from grapevine_tpu.obs.phases import SPAN_NAMES

    from grapevine_tpu.obs.tracer import DERIVED_SPANS

    assert ALLOWED_SPAN_NAMES == SPAN_NAMES | set(DERIVED_SPANS)


def test_tracer_gauges_export():
    reg = TelemetryRegistry()
    tr = RoundTracer(capacity=4, registry=reg)
    tr.record_round({"round": (0.0, 10.0), "evict": (0.0, 5.0)})
    snap = reg.snapshot()
    assert snap["grapevine_round_bubble_ratio"] == pytest.approx(0.5)
    assert snap["grapevine_trace_rounds_total"] == 1
    assert snap["grapevine_trace_ring_rounds"] == 1


# -- SLO units ----------------------------------------------------------


def _slo(clock, **kw):
    defaults = dict(commit_p99_ms=100.0, error_budget=0.1,
                    fast_window_s=10.0, slow_window_s=100.0,
                    fast_burn_threshold=2.0, slow_burn_threshold=1.0,
                    min_rounds=5)
    defaults.update(kw)
    return SloTracker(SloConfig(**defaults), clock=clock)


def test_slo_burn_rate_math_and_verdict_flip():
    t = [0.0]
    s = _slo(lambda: t[0])
    for _ in range(10):  # healthy traffic: no breach, ok
        t[0] += 0.1
        s.observe(0.01)
    v = s.verdict()
    assert v["ok"] and v["fast_burn_rate"] == 0.0
    for _ in range(10):  # every round breaches the 100 ms target
        t[0] += 0.1
        s.observe(1.0)
    v = s.verdict()
    # 10/20 breaching over a 0.1 budget = burn 5.0 in both windows
    assert v["fast_burn_rate"] == pytest.approx(5.0)
    assert v["slow_burn_rate"] == pytest.approx(5.0)
    assert v["ok"] is False
    # windows drain with time: stale breaches stop alerting
    t[0] += 1000.0
    v = s.verdict()
    assert v["ok"] and v["fast_rounds"] == 0


def test_slo_min_rounds_gate():
    """Insufficient evidence is not an outage: a cold engine's first
    compile-bearing rounds must not page."""
    t = [0.0]
    s = _slo(lambda: t[0], min_rounds=32)
    for _ in range(8):
        t[0] += 0.1
        s.observe(99.0)  # catastrophic — but only 8 rounds of evidence
    assert s.verdict()["ok"] is True


def test_slo_single_window_burn_does_not_alert():
    """The multi-window AND: a long-past burst burns the slow window
    only — no alert (the SRE-workbook shape)."""
    t = [0.0]
    s = _slo(lambda: t[0])
    for _ in range(10):
        t[0] += 0.1
        s.observe(1.0)  # burst of breaches
    t[0] += 50.0  # fast window (10 s) drains; slow window (100 s) keeps it
    for _ in range(10):
        t[0] += 0.1
        s.observe(0.01)  # healthy now
    v = s.verdict()
    assert v["slow_burn_rate"] > 1.0  # slow window still burning
    assert v["ok"] is True  # but the fast window cleared — no page


def test_slo_observe_only_reports_but_never_gates():
    """enforce=False (the CLI default until --slo-commit-p99-ms is set
    explicitly): the burn rates and the alerting flag still export, but
    ok never goes False — a fleet upgraded with a target its honest
    latency cannot meet must not flip every replica to 503 at once."""
    t = [0.0]
    s = _slo(lambda: t[0], enforce=False)
    for _ in range(10):
        t[0] += 0.1
        s.observe(1.0)  # every round breaches
    v = s.verdict()
    assert v["alerting"] is True and v["enforced"] is False
    assert v["ok"] is True
    assert v["fast_burn_rate"] > 2.0  # the signal is still there


def test_cli_slo_default_is_observe_only():
    """Without --slo-commit-p99-ms the CLI builds an observe-only
    SloConfig; setting it is the explicit opt-in to healthz gating."""
    from grapevine_tpu.server.cli import _slo_config, build_parser

    p = build_parser()
    cfg = _slo_config(p.parse_args(["--role", "engine"]))
    assert cfg.enforce is False
    cfg = _slo_config(p.parse_args(
        ["--role", "engine", "--slo-commit-p99-ms", "500"]))
    assert cfg.enforce is True and cfg.commit_p99_ms == 500.0


def test_slo_config_validation():
    with pytest.raises(ValueError, match="error budget"):
        SloTracker(SloConfig(error_budget=0.0))
    with pytest.raises(ValueError, match="error budget"):
        SloTracker(SloConfig(error_budget=1.0))


def test_slo_histogram_and_counters_export():
    reg = TelemetryRegistry()
    t = [0.0]
    s = SloTracker(SloConfig(commit_p99_ms=100.0), registry=reg,
                   clock=lambda: t[0])
    s.observe(0.01)
    s.observe(1.0)  # breach
    snap = reg.snapshot()
    assert snap["grapevine_slo_rounds_total"] == 2
    assert snap["grapevine_slo_breaches_total"] == 1
    assert snap["grapevine_slo_target_ms"] == 100.0


# -- live endpoint (one small engine; the module's single compile) ------


def _get(url, timeout=10):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return resp.status, resp.read().decode()
    except urllib.error.HTTPError as e:  # 503 still carries a body
        return e.code, e.read().decode()


@pytest.fixture(scope="module")
def tier():
    from grapevine_tpu.config import GrapevineConfig
    from grapevine_tpu.server.tier import EngineServer
    from grapevine_tpu.wire import constants as C
    from grapevine_tpu.wire.records import QueryRequest, RequestRecord

    cfg = GrapevineConfig(
        bucket_cipher_rounds=0, max_messages=64, max_recipients=16,
        mailbox_cap=4, batch_size=4, stash_size=96,
    )
    srv = EngineServer(cfg, seed=7, max_wait_ms=5.0, clock=lambda: NOW,
                       trace_ring_size=64, profile_enable=True)
    port = srv.start_metrics(0, host="127.0.0.1")
    # a couple of real rounds through the scheduler so the ring has
    # ledgers and the SLO has observations
    for i in range(2):
        resp = srv.scheduler.submit(QueryRequest(
            request_type=C.REQUEST_TYPE_CREATE,
            auth_identity=bytes([i + 1]) * 32,
            auth_signature=b"\x01" * C.SIGNATURE_SIZE,
            record=RequestRecord(msg_id=C.ZERO_MSG_ID,
                                 recipient=bytes([i + 2]) * 32,
                                 payload=b"\x07" * C.PAYLOAD_SIZE)))
        assert resp.status_code == C.STATUS_CODE_SUCCESS
    yield srv, port
    srv.stop()


def test_trace_endpoint_serves_chrome_trace_json(tier):
    srv, port = tier
    status, body = _get(f"http://127.0.0.1:{port}/trace")
    assert status == 200
    trace = json.loads(body)  # valid JSON is the acceptance bar
    assert trace["otherData"]["rounds_recorded_total"] >= 2
    events = trace["traceEvents"]
    for e in events:
        assert {"name", "ph", "pid"} <= set(e)
    spans = [e for e in events if e.get("cat") == "round"]
    names = {e["name"] for e in spans}
    # every stable span present — durability is OFF in this tier, yet
    # journal/checkpoint/device appear (the stable-shape satellite)
    assert {f"grapevine/{s}" for s in STABLE_SPANS} <= names
    # scheduler-side spans paired into the same rounds
    assert "grapevine/assembly" in names and "grapevine/verify" in names


def test_trace_spans_carry_no_per_op_fields(tier):
    """Leak check: every span name is a phase, args carry only the
    round seq and, on the round's own event, the allowlisted whole-round
    counts — nowhere for an op type, client id, or per-op timestamp to
    travel."""
    from grapevine_tpu.obs.tracer import ROUND_COUNTS

    srv, port = tier
    _, body = _get(f"http://127.0.0.1:{port}/trace")
    for e in json.loads(body)["traceEvents"]:
        if e.get("cat") != "round":
            continue
        assert e["name"].removeprefix("grapevine/") in ALLOWED_SPAN_NAMES
        extra = set(ROUND_COUNTS) if e["name"] == "grapevine/round" else set()
        assert set(e.get("args", {})) <= {"seq"} | extra
        assert all(isinstance(v, (int, float)) for v in e["args"].values())
        assert isinstance(e["ts"], int) and isinstance(e["dur"], int)


def test_bubble_and_slo_series_on_metrics(tier):
    srv, port = tier
    status, text = _get(f"http://127.0.0.1:{port}/metrics")
    assert status == 200
    for series in ("grapevine_round_bubble_ratio",
                   "grapevine_trace_rounds_total",
                   "grapevine_trace_ring_rounds",
                   "grapevine_slo_commit_latency_seconds_bucket",
                   "grapevine_slo_rounds_total",
                   "grapevine_slo_burn_rate_fast",
                   "grapevine_slo_burn_rate_slow",
                   "grapevine_slo_alert", "grapevine_slo_target_ms"):
        assert series in text, series
    # the SLO actually measured the submitted rounds
    assert "grapevine_slo_rounds_total 0\n" not in text


def test_slo_burn_rate_flips_healthz(tier):
    """The acceptance flip, directed: a tracker whose windows are both
    burning turns /healthz 503 so the LB stops routing."""
    srv, port = tier
    status, body = _get(f"http://127.0.0.1:{port}/healthz")
    assert status == 200 and json.loads(body)["slo"]["ok"] is True

    t = [0.0]
    burned = _slo(lambda: t[0])
    for _ in range(10):
        t[0] += 0.1
        burned.observe(1.0)  # every round breaches
    real = srv.slo
    srv.slo = burned
    try:
        status, body = _get(f"http://127.0.0.1:{port}/healthz")
        detail = json.loads(body)
        assert status == 503 and detail["healthy"] is False
        assert detail["slo"]["ok"] is False
        assert detail["slo"]["fast_burn_rate"] > 2.0
    finally:
        srv.slo = real
    status, _ = _get(f"http://127.0.0.1:{port}/healthz")
    assert status == 200


def test_profiler_gate_captures_with_the_python_tracer_off(monkeypatch,
                                                            tmp_path):
    """A capture of the served bus holds the program's spans and the
    device's ops, not every Python call (1.5 M events in 4 s slow the
    rounds being captured): the gate starts its trace as the benchmark
    does."""
    import jax.profiler

    from grapevine_tpu.obs.profiler import ProfilerGate

    calls = []
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda d, **kw: calls.append(("start", d, kw)))
    monkeypatch.setattr(
        jax.profiler, "stop_trace", lambda: calls.append(("stop",)))
    out = ProfilerGate(outdir=str(tmp_path)).capture(ms=1)
    (_, trace_dir, kw), stop = calls
    assert stop == ("stop",) and trace_dir == out["trace_dir"]
    assert kw["profiler_options"].python_tracer_level == 0


def test_profile_endpoint_gated_capture(tier):
    """/profile?ms=N runs a live jax.profiler capture (enabled in this
    fixture) and refuses a concurrent one with 409."""
    import os

    srv, port = tier
    # the first capture pays jax.profiler's lazy init (~10 s on this
    # sandbox); later captures are milliseconds
    status, body = _get(f"http://127.0.0.1:{port}/profile?ms=30",
                        timeout=90)
    assert status == 200
    result = json.loads(body)
    assert result["ms"] == 30 and os.path.isdir(result["trace_dir"])
    assert any(files for _, _, files in os.walk(result["trace_dir"]))
    # busy: a second capture while one holds the gate gets 409
    assert srv.profiler._lock.acquire(blocking=False)
    try:
        status, body = _get(f"http://127.0.0.1:{port}/profile?ms=10")
        assert status == 409
    finally:
        srv.profiler._lock.release()
    status, _ = _get(f"http://127.0.0.1:{port}/profile?ms=oops")
    assert status == 400


def test_profile_404_when_not_enabled():
    """Without --profile-enable the endpoint does not exist (the gate
    is absence, not a flag check at request time)."""
    from grapevine_tpu.obs.httpd import MetricsServer

    ms = MetricsServer(TelemetryRegistry(), port=0)
    port = ms.start()
    try:
        status, _ = _get(f"http://127.0.0.1:{port}/profile?ms=10")
        assert status == 404
        status, _ = _get(f"http://127.0.0.1:{port}/trace")
        assert status == 404
    finally:
        ms.stop()
