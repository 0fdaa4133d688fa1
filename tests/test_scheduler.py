"""BatchScheduler quiescence collection (server/scheduler.py).

The window must stay open while a wave of requests is still trickling
in (inter-arrival gap < idle_gap) and close once arrivals stall, capped
at max_wait — measured 26% round occupancy with the old fixed window
(PERF.md). Uses a stub engine (no JAX) and generous timing margins so
the test is stable on a single-core host.
"""

import threading
import time

import pytest

from grapevine_tpu.engine.metrics import EngineMetrics
from grapevine_tpu.server.scheduler import BatchScheduler
from grapevine_tpu.wire import constants as C
from grapevine_tpu.wire.records import QueryRequest, QueryResponse, Record


class _StubEcfg:
    batch_size = 16


class _StubEngine:
    """Counts rounds; responds instantly."""

    def __init__(self):
        self.ecfg = _StubEcfg()
        self.metrics = EngineMetrics()
        self.rounds: list[int] = []  # ops per round
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


def test_trickling_wave_lands_in_one_round():
    eng = _StubEngine()
    sched = BatchScheduler(eng, max_wait_ms=2000.0, idle_gap_ms=300.0)
    try:
        threads = [
            threading.Thread(target=sched.submit, args=(_req(),)) for _ in range(6)
        ]
        for t in threads:
            t.start()
            time.sleep(0.05)  # arrivals well inside the 300ms idle gap
        for t in threads:
            t.join(timeout=10)
        assert eng.rounds == [6], f"wave split across rounds: {eng.rounds}"
    finally:
        sched.close()


def test_stalled_arrivals_close_the_round():
    eng = _StubEngine()
    sched = BatchScheduler(eng, max_wait_ms=5000.0, idle_gap_ms=150.0)
    try:
        t1 = threading.Thread(target=sched.submit, args=(_req(),))
        t1.start()
        t1.join(timeout=10)  # idle gap passes with nothing else queued
        assert eng.rounds == [1], "lone request should commit after idle_gap"
        # a second burst forms its own round
        t2 = threading.Thread(target=sched.submit, args=(_req(),))
        t3 = threading.Thread(target=sched.submit, args=(_req(),))
        t2.start(); t3.start()
        t2.join(timeout=10); t3.join(timeout=10)
        assert eng.rounds[0] == 1 and sum(eng.rounds) == 3
    finally:
        sched.close()


def test_stall_age_sees_wedged_inflight_round():
    """A round wedged on the device empties the queue — stall_age()
    must age the in-flight round, or /healthz serves 200 while every
    blocked client hangs on fut.result() forever."""
    unwedge = threading.Event()

    class _WedgedEngine(_StubEngine):
        def handle_queries_async(self, reqs, now):
            resps = self.handle_queries(reqs, now)

            class _Pending:
                def resolve(self):
                    unwedge.wait(timeout=30)  # the wedge
                    return resps

            return _Pending()

    eng = _WedgedEngine()
    sched = BatchScheduler(eng, max_wait_ms=50.0, idle_gap_ms=10.0)
    try:
        assert sched.stall_age() == 0.0  # idle: no queue, nothing in flight
        t = threading.Thread(target=sched.submit, args=(_req(),))
        t.start()
        # the op leaves the queue (dispatched) but never resolves; the
        # stall signal must keep growing with an empty queue
        deadline = time.monotonic() + 10
        while sched.stall_age() < 0.2 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert sched.stall_age() >= 0.2, "wedged in-flight round invisible"
        assert sched.worker_alive()
        unwedge.set()
        t.join(timeout=10)
        deadline = time.monotonic() + 10
        while sched.stall_age() > 0.0 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert sched.stall_age() == 0.0  # settled: signal clears
    finally:
        unwedge.set()
        sched.close()


def test_full_batch_commits_without_waiting():
    eng = _StubEngine()
    sched = BatchScheduler(eng, max_wait_ms=10_000.0, idle_gap_ms=10_000.0)
    try:
        threads = [
            threading.Thread(target=sched.submit, args=(_req(),))
            for _ in range(_StubEcfg.batch_size)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
        # a full batch must not sit out the 10s window
        assert time.perf_counter() - t0 < 5.0
        assert eng.rounds and max(eng.rounds) == _StubEcfg.batch_size
    finally:
        sched.close()


# -- a round's signatures on the host's cores ---------------------------


class _CountingEngine(_StubEngine):
    """A stub whose round handles keep what the scheduler stamps on
    them, as engine/batcher.py's PendingRound does."""

    def __init__(self, batch_size):
        super().__init__()
        self.ecfg = type("Ecfg", (), {"batch_size": batch_size})()
        self.counts: list[dict] = []

    def handle_queries_async(self, reqs, now):
        resps = self.handle_queries(reqs, now)
        engine = self

        class _Pending:
            def note_span(self, name, start, dur):
                pass

            def set_queue_depth(self, depth):
                pass

            def set_enqueued_at(self, t):
                pass

            def note_counts(self, **counts):
                engine.counts.append(counts)

            def resolve(self):
                return resps

        return _Pending()


@pytest.fixture(scope="module")
def signed_ops():
    """2,048 ops' auth items, each under an identity of its own."""
    from grapevine_tpu.session import schnorrkel

    ctx = C.GRAPEVINE_CHALLENGE_SIGNING_CONTEXT
    items = []
    for i in range(2048):
        sk, pub = schnorrkel.keygen(i.to_bytes(4, "little") * 8)
        msg = i.to_bytes(32, "little")
        items.append((pub, ctx, msg, schnorrkel.sign(sk, ctx, msg)))
    return items


@pytest.mark.parametrize(
    "cores,n_ops,bad,chunks",
    [
        (8, 2048, (5, 700, 2047), 6),   # three bad ops in three chunks
        (8, 2048, (), 6),
        (8, 35, (), 1),                 # a thin round: one inline call
        (8, 35, (17,), 1),
        (8, 600, (599,), 2),            # the least chunk size holds k down
        (30, 2048, (0,), 8),
        (2, 2048, (1024,), 1),          # no core to spare: inline
    ],
)
def test_round_verifies_in_chunks_and_rejects_bad_ops_alone(
        monkeypatch, signed_ops, cores, n_ops, bad, chunks):
    """k follows from the cores the process may use and the round's
    size; the conjunction of the chunks' answers decides, and the
    bisect still finds every bad signature and refuses it alone."""
    import os

    from grapevine_tpu import native
    from grapevine_tpu.server.scheduler import AuthFailure

    if native.lib is None:
        pytest.skip("native library unavailable")
    monkeypatch.setattr(
        os, "sched_getaffinity", lambda pid: set(range(cores)), raising=False)
    eng = _CountingEngine(batch_size=2048)
    sched = BatchScheduler(eng, max_wait_ms=20_000.0, idle_gap_ms=200.0)
    try:
        futs = []
        with sched._cv:  # the window sees the ops all at once: one round
            for i in range(n_ops):
                pub, ctx, msg, sig = signed_ops[i]
                if i in bad:
                    sig = sig[:40] + bytes([sig[40] ^ 1]) + sig[41:]
                futs.append(sched.submit_nowait(_req(), (pub, ctx, msg, sig)))
        refused = set()
        for i, fut in enumerate(futs):
            try:
                fut.result(timeout=120)
            except AuthFailure:
                refused.add(i)
        assert refused == set(bad)
        assert eng.rounds == [n_ops - len(bad)]
        assert [c["verify_chunks"] for c in eng.counts] == [chunks]
        assert eng.counts[0]["rejected"] == len(bad)
    finally:
        sched.close()
    assert not sched.worker_alive()
