"""The load harness: open-loop replay, workload telemetry, capacity
knee, and the adversarial-vs-honest /leakaudit discrimination drill
(ISSUE 9 tentpole + satellite).

Fast always-on coverage (one tiny engine compile, shared module-wide):

- open-loop property, behaviorally: a replay against a scheduler whose
  completions are wedged still submits every op on schedule (arrival
  times independent of completion times), and never mutates the
  schedule (fingerprint-stable);
- workload telemetry lands: fill/depth histograms sampled at round
  cadence, arrival EWMA > 0, per-phase utilization from the span
  ledgers, flightrec rounds carrying the queue_depth field;
- honest traffic through the REAL engine: /leakaudit verdict PASS;
- the probe campaign + ProbeCampaignInjector: verdict flips SUSPECT
  within the soak (detection power under adversarial timing — an
  honest engine cannot be flipped by traffic shape, which is exactly
  what the honest-scenario FP gate pins);
- capacity knee math on synthetic steps (no engine).

Scenario breadth: every honest generator soaked, the no-false-positive
budget under bursty/diurnal/pop-heavy timing.
"""

import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from grapevine_tpu.config import GrapevineConfig
from grapevine_tpu.engine.batcher import GrapevineEngine
from grapevine_tpu.load import (
    ProbeCampaignInjector,
    ScenarioRunner,
    adversarial_probe,
    analyze_ramp,
    bursty_onoff,
    diurnal_sinusoid,
    find_knee,
    pop_heavy_drain,
    ramp_to_saturation,
    steady_poisson,
)
from grapevine_tpu.load.capacity import step_stats
from grapevine_tpu.obs.leakmon import PASS, SUSPECT, EngineLeakMonitor
from grapevine_tpu.obs.workload import WorkloadTelemetry
from grapevine_tpu.server.scheduler import BatchScheduler

NOW = 1_700_000_000


# ---------------------------------------------------------------------
# open-loop behavior against a fake scheduler (no engine, no jax)
# ---------------------------------------------------------------------


class _WedgedFakeScheduler:
    """Accepts every op instantly, completes none until released —
    the worst-case server an open-loop harness must not wait for."""

    def __init__(self):
        self.submit_walls: list[float] = []
        self.futures: list[Future] = []
        self._lock = threading.Lock()

    def submit_nowait(self, req, auth=None) -> Future:
        fut: Future = Future()
        with self._lock:
            self.submit_walls.append(time.perf_counter())
            self.futures.append(fut)
        return fut

    def release_all(self):
        from grapevine_tpu.wire import constants as C
        from grapevine_tpu.wire.records import QueryResponse, Record

        zero = Record(msg_id=b"\x00" * 16, sender=b"\x00" * 32,
                      recipient=b"\x00" * 32, timestamp=0,
                      payload=b"\x00" * C.PAYLOAD_SIZE)
        for fut in self.futures:
            fut.set_result(
                QueryResponse(record=zero,
                              status_code=C.STATUS_CODE_SUCCESS))


def test_replay_is_open_loop_and_schedule_immutable():
    """Submissions track the schedule even when nothing ever completes
    (no self-throttling), and the schedule object is untouched."""
    sched = steady_poisson(150.0, 1.0, 21, n_idents=8)
    fp_before = sched.fingerprint()
    fake = _WedgedFakeScheduler()
    runner = ScenarioRunner(fake, n_idents=8, settle_timeout_s=0.2)

    release = threading.Timer(1.6, fake.release_all)
    release.start()
    t0 = time.perf_counter()
    res = runner.run(sched)
    release.cancel()
    fake.release_all()  # idempotent: settle anything left

    assert len(fake.submit_walls) == sched.n_ops, (
        "open-loop replay must submit EVERY op regardless of completions"
    )
    # submissions happened on schedule, not after completions: the last
    # op went in by ~duration, far before any completion existed
    assert fake.submit_walls[-1] - t0 < sched.duration_s + 0.5
    # loose always-on bound: on this sandbox's 2-vCPU host a GC pause
    # or scheduler preemption can stall one dispatch tick by ~0.5s
    # (observed p99 0.53s) without the dispatcher actually falling
    # behind the open-loop schedule; the tight realtime bound lives in
    # the -m slow variant below
    skew = res.skew_s[~np.isnan(res.skew_s)]
    assert np.percentile(skew, 99) < 1.5, "dispatcher fell behind"
    assert sched.fingerprint() == fp_before, "replay mutated the schedule"


@pytest.mark.slow
def test_replay_dispatch_skew_tight():
    """The realtime claim at full strength: p99 dispatch skew under
    250 ms against a wedged server. Meaningful on an unloaded host;
    under tier-1's parallel suite the shared 2 vCPUs make sub-second
    scheduler stalls routine, so this tight variant rides -m slow."""
    sched = steady_poisson(150.0, 1.0, 21, n_idents=8)
    fake = _WedgedFakeScheduler()
    runner = ScenarioRunner(fake, n_idents=8, settle_timeout_s=0.2)
    release = threading.Timer(1.6, fake.release_all)
    release.start()
    res = runner.run(sched)
    release.cancel()
    fake.release_all()
    skew = res.skew_s[~np.isnan(res.skew_s)]
    assert np.percentile(skew, 99) < 0.25, "dispatcher fell behind"


def test_replay_time_scale_compresses_wall_clock():
    sched = steady_poisson(50.0, 2.0, 22, n_idents=8)
    fake = _WedgedFakeScheduler()
    runner = ScenarioRunner(fake, n_idents=8, time_scale=0.25,
                            settle_timeout_s=0.1)
    t0 = time.perf_counter()
    fake_release = threading.Timer(0.9, fake.release_all)
    fake_release.start()
    runner.run(sched)
    fake_release.cancel()
    fake.release_all()
    assert time.perf_counter() - t0 < 2.0 * 0.25 + 1.0


# ---------------------------------------------------------------------
# capacity knee math (synthetic steps; no engine)
# ---------------------------------------------------------------------


def _step(rate, burn, fail_frac=0.0, n=64):
    return {
        "offered_rate": rate, "arrival_rate": rate, "n_ops": n,
        "achieved_ops_per_sec": rate,
        "breach_fraction": burn * 0.01, "burn_rate": burn,
        "failure_fraction": fail_frac,
        "p99_commit_ms": 10.0,
    }


def test_find_knee_last_holding_step_before_failure():
    steps = [_step(100, 0.0), _step(200, 0.5), _step(400, 40.0),
             _step(800, 99.0)]
    k = find_knee(steps)
    assert k["knee_ops_per_sec"] == 200 and k["saturated"]
    assert k["first_failing_rate"] == 400


def test_find_knee_unsaturated_ramp_is_a_lower_bound():
    k = find_knee([_step(100, 0.0), _step(200, 0.2)])
    assert k["knee_ops_per_sec"] == 200 and not k["saturated"]
    assert k["first_failing_rate"] is None


def test_find_knee_lucky_late_step_cannot_inflate():
    steps = [_step(100, 0.0), _step(200, 50.0), _step(400, 0.0)]
    k = find_knee(steps)
    assert k["knee_ops_per_sec"] == 100, (
        "a pass AFTER a measured failure must not raise the knee"
    )


def test_find_knee_failing_ops_do_not_hold():
    # latency fine but the server failed 40% of ops: not holding
    steps = [_step(100, 0.0), _step(200, 0.0, fail_frac=0.4)]
    k = find_knee(steps)
    assert k["knee_ops_per_sec"] == 100 and k["saturated"]


def test_find_knee_thin_steps_grade_nothing():
    k = find_knee([_step(100, 99.0, n=2)])
    assert k["knee_ops_per_sec"] == 0.0 and not k["saturated"]


def test_step_stats_unsettled_ops_breach():
    s = step_stats(100.0, 1.0, [0.001, np.nan, 0.5], [True, False, True],
                   target_ms=250.0, error_budget=0.01)
    # NaN (never settled) and 0.5s (past target) both breach
    assert s["breach_fraction"] == pytest.approx(2 / 3, abs=1e-3)
    assert s["burn_rate"] == pytest.approx(66.67, abs=0.1)


def test_analyze_ramp_on_synthetic_replay():
    sched = ramp_to_saturation(200.0, 2.0, 3, 1.0, 23)

    class _Res:
        time_scale = 1.0
        latency_s = np.where(sched.t_s < 2.0, 0.01, 1.0)
        ok = np.ones(sched.n_ops, bool)

    out = analyze_ramp(sched, _Res(), target_ms=250.0)
    assert out["saturated"]
    assert out["knee_ops_per_sec"] == pytest.approx(400.0)
    assert len(out["steps"]) == 3


# ---------------------------------------------------------------------
# the real engine: telemetry + discrimination (one shared tiny engine)
# ---------------------------------------------------------------------


@pytest.fixture(scope="module")
def loaded_engine():
    cfg = GrapevineConfig(
        max_messages=1 << 10, max_recipients=1 << 8, batch_size=4,
        bucket_cipher_rounds=0,
    )
    engine = GrapevineEngine(cfg)
    wl = WorkloadTelemetry(engine.metrics.registry, batch_size=4)
    engine.attach_workload(wl)
    # pay the jit compile outside every test's measurement window
    sched = BatchScheduler(engine, clock=lambda: NOW)
    try:
        ScenarioRunner(sched, n_idents=8).run(
            steady_poisson(40.0, 0.2, 1, n_idents=8))
    finally:
        sched.close()
    return engine, wl


def _fresh_monitor(engine):
    return EngineLeakMonitor(
        mb_leaves=engine.ecfg.mb.leaves, rec_leaves=engine.ecfg.rec.leaves,
        mb_choices=engine.ecfg.mb_choices,
    )


def _run_scenario(engine, schedule, sink):
    engine.attach_leakmon(sink)
    sched = BatchScheduler(engine, clock=lambda: NOW)
    try:
        runner = ScenarioRunner(sched, n_idents=16, settle_timeout_s=60.0)
        return runner.run(schedule)
    finally:
        sched.close()
        sink.flush(30)
        engine.attach_leakmon(None)


def test_workload_telemetry_lands_at_round_cadence(loaded_engine):
    engine, wl = loaded_engine
    mon = _fresh_monitor(engine)
    res = _run_scenario(
        engine, steady_poisson(120.0, 1.2, 31, n_idents=16), mon)
    s = res.summary()
    assert s["n_failed"] == 0 and s["n_ok"] == s["n_ops"]
    assert s["p99_commit_ms"] > 0

    reg = engine.metrics.registry
    fill = reg.get("grapevine_load_batch_fill").child()
    depth = reg.get("grapevine_load_queue_depth").child()
    assert fill.count > 0 and depth.count > 0, (
        "fill/depth histograms must sample at round cadence"
    )
    assert reg.get("grapevine_load_arrivals_total").get() >= s["n_ops"]
    # the EWMA gauge saw the ~100 ops/s stream (wide noise bounds)
    assert reg.get("grapevine_load_arrival_rate_ops_s").get() > 1.0
    util = wl.utilization()
    assert util["device"] > 0.0, "device-window utilization never derived"
    assert all(0.0 <= u <= 1.0 for u in util.values())
    # flightrec rounds carry the queue-depth summary field
    rounds = mon.recorder.dump()["rounds"]
    assert rounds and all("queue_depth" in r for r in rounds)
    v = mon.verdict()
    assert v["verdict"] == PASS and v["rounds_observed"] > 0
    mon.close()
    reg.audit()  # the new namespace stays batch-level under live load


def test_probe_campaign_flips_leakaudit_suspect(loaded_engine):
    """The discrimination drill's detection half: a leak signature
    riding probe-shaped traffic flips the monitor within the soak. The
    engine itself stays honest — the injector rewrites only the
    transcript COPY fed to the detectors (load/harness.py docstring)."""
    engine, _ = loaded_engine
    mon = _fresh_monitor(engine)
    inj = ProbeCampaignInjector(mon, engine.ecfg)
    _run_scenario(
        engine,
        adversarial_probe(0.03, 1.5, 32, n_probe_keys=4,
                          probes_per_pulse=2),
        inj,
    )
    v = mon.verdict()
    assert v["verdict"] == SUSPECT, v
    tripped = {d["name"] for d in v["detectors"] if d["verdict"] == SUSPECT}
    assert "cross_round_repeat" in tripped, tripped
    mon.close()


def test_probe_campaign_without_leak_stays_pass(loaded_engine):
    """The FP half, fast edition: the SAME adversarial timing against
    the honest engine (no injector) must NOT flip the audit — traffic
    shape alone cannot simulate a leak, which is the obliviousness
    claim the thresholds are sized against."""
    engine, _ = loaded_engine
    mon = _fresh_monitor(engine)
    _run_scenario(
        engine,
        adversarial_probe(0.03, 1.5, 33, n_probe_keys=4,
                          probes_per_pulse=2),
        mon,
    )
    v = mon.verdict()
    assert v["verdict"] == PASS, v
    # PASS by measurement, not by missing evidence: the probe shape
    # exists to maximize detector samples
    coll = next(d for d in v["detectors"]
                if d["name"] == "samekey_collision" and d["tree"] == "mb")
    assert coll["samples"] >= coll["min_samples"], coll
    mon.close()


# ---------------------------------------------------------------------
# scenario breadth: the full honest soak + an end-to-end knee
# ---------------------------------------------------------------------


HONEST_SOAKS = {
    "bursty": lambda: bursty_onoff(250.0, 0.3, 1.0, 4.0, 41, n_idents=16),
    "diurnal": lambda: diurnal_sinusoid(120.0, 0.8, 2.0, 4.0, 42,
                                        n_idents=16),
    "pop_heavy": lambda: pop_heavy_drain(120.0, 4.0, 43, n_idents=16,
                                         n_hot=4),
}


@pytest.mark.parametrize("name", sorted(HONEST_SOAKS))
def test_honest_soak_stays_pass(loaded_engine, name):
    """ISSUE 9 satellite: the false-positive gate for the scale-aware
    thresholds under non-uniform TIMING — every honest shape soaked
    through the real engine, verdict PASS with measured evidence."""
    engine, _ = loaded_engine
    mon = _fresh_monitor(engine)
    res = _run_scenario(engine, HONEST_SOAKS[name](), mon)
    assert res.summary()["n_failed"] == 0
    v = mon.verdict()
    assert v["verdict"] == PASS, (name, v)
    assert v["rounds_observed"] >= 32
    mon.close()


def test_ramp_finds_a_knee_end_to_end(loaded_engine):
    engine, _ = loaded_engine
    mon = _fresh_monitor(engine)
    # calibrate a plausible staircase around this host's capacity
    t0 = time.perf_counter()
    sched = BatchScheduler(engine, clock=lambda: NOW)
    try:
        ScenarioRunner(sched, n_idents=16).run(
            steady_poisson(40.0, 0.3, 44, n_idents=16))
    finally:
        sched.close()
    est = 4 / max(1e-3, (time.perf_counter() - t0) / 8)  # rough ops/s
    schedule = ramp_to_saturation(max(10.0, 0.25 * est), 2.0, 4, 1.0, 45,
                                  n_idents=16)
    res = _run_scenario(engine, schedule, mon)
    out = analyze_ramp(schedule, res, target_ms=250.0)
    assert out["knee_ops_per_sec"] > 0, out
    assert len(out["steps"]) == 4
    mon.close()
