"""SLO-adaptive round collection (server/adaptive.py,
server/scheduler.py).

The policy's contract: every decision is a function of PUBLIC load
aggregates — queue depth (an integer), the arrival-rate EWMA and the
SLO burn rates. The unit tests pin
each decision kind; the scheduler tests prove the decisions actually
shape the collection window; the obliviousness teeth live in
test_oblint.py (the seeded adaptive_batch_from_contents mutant must
FAIL the analyzer).

Uses the stub-engine pattern from test_scheduler.py (no JAX) with
generous timing margins for a single-core host.
"""

import threading
import time

import pytest

from grapevine_tpu.engine.metrics import EngineMetrics
from grapevine_tpu.obs import TelemetryRegistry
from grapevine_tpu.server.adaptive import (
    DECISION_KINDS,
    AdaptiveBatchConfig,
    AdaptiveBatchPolicy,
)
from grapevine_tpu.server.scheduler import BatchScheduler
from grapevine_tpu.wire import constants as C
from grapevine_tpu.wire.records import QueryRequest, QueryResponse, Record


class _FakeWorkload:
    def __init__(self, rate):
        self.rate = rate

    def arrival_rate(self):
        return self.rate


class _FakeSlo:
    def __init__(self, fast_burn=0.0, fast_rounds=0):
        self.fast_burn = fast_burn
        self.fast_rounds = fast_rounds

    def burn_rates(self):
        return {
            "fast_burn_rate": self.fast_burn,
            "slow_burn_rate": 0.0,
            "fast_rounds": self.fast_rounds,
            "slow_rounds": self.fast_rounds,
        }


def _policy(bs=16, base_ms=8.0, gap_ms=2.0, **kw):
    return AdaptiveBatchPolicy(bs, base_ms / 1000.0, gap_ms / 1000.0, **kw)


# -- config validation -------------------------------------------------


def test_config_rejects_zero_floor():
    with pytest.raises(ValueError):
        AdaptiveBatchConfig(floor_wait_ms=0.0)


def test_config_rejects_shrinking_ceil():
    with pytest.raises(ValueError):
        AdaptiveBatchConfig(ceil_factor=0.5)


def test_policy_rejects_bad_batch_size():
    with pytest.raises(ValueError):
        _policy(bs=0)


# -- the four decision kinds -------------------------------------------


def test_fill_dispatches_at_floor_when_queue_is_full():
    pol = _policy(bs=8, base_ms=50.0)
    wait, gap, target = pol.decide(8)
    assert wait == pytest.approx(pol.cfg.floor_wait_ms / 1000.0)
    assert target == 8
    assert gap <= wait


def test_shed_under_fast_burn_with_evidence():
    pol = _policy(bs=8, base_ms=50.0,
                  workload=_FakeWorkload(500.0),
                  slo=_FakeSlo(fast_burn=3.0, fast_rounds=64))
    wait, _gap, target = pol.decide(3)
    assert wait == pytest.approx(pol.cfg.floor_wait_ms / 1000.0)
    assert target == 3  # dispatch what's queued, don't hold for a fill


def test_shed_needs_min_rounds_of_evidence():
    # a scorching burn rate over 2 rounds is noise, not overload — the
    # policy must not flinch into tiny rounds on startup transients
    pol = _policy(bs=8, base_ms=50.0,
                  workload=_FakeWorkload(500.0),
                  slo=_FakeSlo(fast_burn=9.0, fast_rounds=2))
    _wait, _gap, target = pol.decide(3)
    assert target == 8  # cruise (rate is high), not shed


def test_sparse_lone_client_commits_at_floor():
    # EWMA expects < 1 arrival inside the base window: stretching buys
    # nothing, a lone op should not sit out the full wait
    pol = _policy(bs=8, base_ms=50.0, workload=_FakeWorkload(1.0))
    wait, _gap, target = pol.decide(1)
    assert wait == pytest.approx(pol.cfg.floor_wait_ms / 1000.0)
    assert target == 1


def test_cruise_stretches_toward_full_round():
    # 400 ops/s, 7 more needed -> t_full = 17.5ms: above base 8ms,
    # below the 32ms ceiling — the window stretches to exactly t_full
    pol = _policy(bs=8, base_ms=8.0, workload=_FakeWorkload(400.0))
    wait, gap, target = pol.decide(1)
    assert wait == pytest.approx(7 / 400.0)
    assert target == 8
    assert gap <= wait


def test_cruise_caps_at_ceil_factor():
    # 30 ops/s: expected arrivals within base window >= 1 but a full
    # round would take 7/30 = 233ms — the ceiling (4 x 10ms) wins
    pol = _policy(bs=8, base_ms=10.0, workload=_FakeWorkload(130.0))
    wait, _gap, target = pol.decide(1)
    assert wait <= 0.010 * pol.cfg.ceil_factor + 1e-9
    assert target == 8


def test_missing_signals_degrade_to_sparse():
    # no workload, no slo: rate reads 0, every under-full round is
    # sparse — static-window behavior at the floor, never a crash
    pol = _policy(bs=8, base_ms=50.0)
    wait, _gap, target = pol.decide(2)
    assert wait == pytest.approx(pol.cfg.floor_wait_ms / 1000.0)
    assert target == 2


def test_decision_telemetry_counts_by_kind():
    reg = TelemetryRegistry()
    pol = _policy(bs=8, base_ms=8.0, workload=_FakeWorkload(200.0),
                  slo=_FakeSlo(fast_burn=3.0, fast_rounds=64),
                  registry=reg)
    pol.decide(1)   # shed (burn dominates)
    pol.slo = None
    pol.decide(9)   # fill
    pol.decide(1)   # cruise
    pol.workload = None
    pol.decide(1)   # sparse
    c = reg.get("grapevine_host_adaptive_decisions_total")
    for kind in DECISION_KINDS:
        assert c.get(phase=kind) == 1, kind
    assert reg.get("grapevine_host_adaptive_wait_ms").get() > 0
    assert reg.get("grapevine_host_adaptive_target_fill").get() == 1
    assert reg.audit()["ok"]


# -- through the scheduler ---------------------------------------------


class _StubEcfg:
    batch_size = 16


class _StubEngine:
    def __init__(self):
        self.ecfg = _StubEcfg()
        self.metrics = EngineMetrics()
        self.rounds: list[int] = []
        self._lock = threading.Lock()

    def handle_queries(self, reqs, now):
        with self._lock:
            self.rounds.append(len(reqs))
        zero = Record(
            msg_id=C.ZERO_MSG_ID,
            sender=C.ZERO_PUBKEY,
            recipient=C.ZERO_PUBKEY,
            timestamp=0,
            payload=b"\x00" * C.PAYLOAD_SIZE,
        )
        return [
            QueryResponse(record=zero, status_code=C.STATUS_CODE_SUCCESS)
            for _ in reqs
        ]

    def handle_queries_async(self, reqs, now):
        resps = self.handle_queries(reqs, now)

        class _Pending:
            def resolve(self):
                return resps

        return _Pending()


def _req():
    return QueryRequest(
        request_type=C.REQUEST_TYPE_READ,
        auth_identity=b"\x01" * 32,
        auth_signature=b"\x02" * C.SIGNATURE_SIZE,
        record=None,
    )


def test_adaptive_sparse_beats_static_window_latency():
    """A lone op under a huge static window would sit out the idle gap;
    the sparse decision dispatches it at the floor wait instead."""
    eng = _StubEngine()
    sched = BatchScheduler(eng, max_wait_ms=10_000.0, idle_gap_ms=5_000.0)
    sched.adaptive = AdaptiveBatchPolicy(
        _StubEcfg.batch_size, sched.max_wait, sched.idle_gap,
        workload=_FakeWorkload(0.0),
    )
    try:
        t0 = time.perf_counter()
        t = threading.Thread(target=sched.submit, args=(_req(),))
        t.start()
        t.join(timeout=10)
        assert time.perf_counter() - t0 < 3.0, (
            "sparse round sat out the static window"
        )
        assert eng.rounds == [1]
    finally:
        sched.close()


def test_frontend_role_rejects_adaptive_knobs():
    from grapevine_tpu.server.service import GrapevineServer

    with pytest.raises(ValueError):
        GrapevineServer(scheduler=object(), adaptive_batch=True)


# -- the pop-heavy soak: adaptive windows stay oblivious ---------------


def test_pop_heavy_soak_with_adaptive_windows_passes_leak_audit():
    """The acceptance soak: the PR-9 pop-heavy drain scenario through a
    scheduler running the adaptive window over a live engine. Every
    leak detector must PASS: the adaptive windows retime host-side
    collection only."""
    from grapevine_tpu.config import GrapevineConfig
    from grapevine_tpu.engine.batcher import GrapevineEngine
    from grapevine_tpu.load import ScenarioRunner, pop_heavy_drain
    from grapevine_tpu.obs import attach_round_observability
    from grapevine_tpu.obs.leakmon import PASS, EngineLeakMonitor, \
        LeakMonitorConfig

    engine = GrapevineEngine(
        GrapevineConfig(
            bucket_cipher_rounds=0, max_messages=256, max_recipients=32,
            mailbox_cap=8, batch_size=8, stash_size=96,
        ),
        seed=9,
    )
    _tracer, slo, _prof = attach_round_observability(
        engine, engine.metrics.registry
    )
    mon = EngineLeakMonitor.for_engine(
        engine, LeakMonitorConfig(window_rounds=64)
    )
    engine.attach_leakmon(mon)
    sched = BatchScheduler(engine, clock=lambda: 1_700_000_000)
    sched.adaptive = AdaptiveBatchPolicy(
        engine.ecfg.batch_size, sched.max_wait, sched.idle_gap,
        workload=engine.workload, slo=slo,
        registry=engine.metrics.registry,
    )
    try:
        runner = ScenarioRunner(sched, n_idents=16, settle_timeout_s=60.0)
        runner.run(pop_heavy_drain(100.0, 1.5, 37, n_idents=16))
    finally:
        sched.close()
        mon.flush(30)
        engine.attach_leakmon(None)
    v = mon.verdict()
    assert v["verdict"] == PASS, v
    # the adaptive policy actually decided rounds, from public inputs
    dec = engine.metrics.registry.get("grapevine_host_adaptive_decisions_total")
    assert sum(dec.get(phase=k) for k in DECISION_KINDS) >= 1
    assert engine.metrics.registry.audit()["ok"]
    mon.close()
