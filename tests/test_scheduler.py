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


# -- the dispatch rule: a short queue waits for the round in flight -----


class _GatedEngine(_CountingEngine):
    """A stub whose rounds finish on command: round k is ready when
    ``release(k)`` says so, ``resolve()`` waits for that, and every
    handle keeps the spans and counts the scheduler stamps on it.
    ``polls`` counts the scheduler's readiness probes, which only a
    hold makes: a test waits for more of them, not for a time. A
    dispatch waits inside the engine while ``dispatch_gate`` is there
    and unset, so a test can queue ops behind a round before the
    collector is back from dispatching it."""

    def __init__(self, batch_size=16):
        super().__init__(batch_size)
        self.gates: list[threading.Event] = []
        self.spans: list[dict] = []
        self.polls = 0
        self.poll_error: Exception | None = None
        self.dispatching = threading.Event()
        self.resolving = threading.Event()
        self.dispatch_gate: threading.Event | None = None

    def handle_queries_async(self, reqs, now):
        self.dispatching.set()
        if self.dispatch_gate is not None:
            assert self.dispatch_gate.wait(timeout=30), "dispatch never let go"
        resps = self.handle_queries(reqs, now)
        engine, gate, spans, counts = self, threading.Event(), {}, {}
        self.gates.append(gate)
        self.spans.append(spans)
        self.counts.append(counts)

        class _Pending:
            def note_span(self, name, start, dur):
                spans[name] = (start, dur)

            def set_queue_depth(self, depth):
                pass

            def set_enqueued_at(self, t):
                pass

            def note_counts(self, **kw):
                counts.update(kw)

            def ready(self):
                engine.polls += 1
                if engine.poll_error is not None:
                    raise engine.poll_error
                return gate.is_set()

            def resolve(self):
                engine.resolving.set()
                assert gate.wait(timeout=30), "round never released"
                return resps

        return _Pending()

    def release(self, k):
        self.gates[k].set()


def _until(cond, what, timeout=20.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.002)


def _held(eng, sched, n_ops):
    """Round 0 (one op) in flight and unreleased, ``n_ops`` more queued
    behind it before the collector was back from its dispatch, and the
    collector seen to have looked at that queue and at round 0 again
    without dispatching. Returns every future."""
    eng.dispatch_gate = threading.Event()
    futs = [sched.submit_nowait(_req())]
    _until(eng.dispatching.is_set, "round 0's dispatch")
    futs += [sched.submit_nowait(_req()) for _ in range(n_ops)]
    eng.dispatch_gate.set()
    _until(lambda: len(eng.rounds) == 1, "round 0 on the device")
    seen = eng.polls
    _until(lambda: eng.polls >= seen + 3, "the hold's probes")
    with sched._cv:
        assert len(sched._queue) == n_ops
    assert eng.rounds == [1], "a short round was queued behind round 0"
    return futs


@pytest.mark.parametrize("depth", [1, 2])
def test_short_queue_waits_for_the_round_in_flight(depth):
    """Nothing is dispatched behind a round in flight until it settles;
    what arrived meanwhile rides one round, with nothing ahead of it and
    no further window; the ``hold`` span says how long it was deferred,
    and is there, 0, on the round that was not."""
    eng = _GatedEngine()
    # a window that would take 5 s more if one were opened after the hold
    sched = BatchScheduler(eng, max_wait_ms=10_000.0, idle_gap_ms=5.0,
                           pipeline_depth=depth)
    try:
        futs = _held(eng, sched, 3)
        assert not any(f.done() for f in futs)
        t_free = time.perf_counter()
        eng.release(0)
        assert futs[0].result(timeout=10).status_code == C.STATUS_CODE_SUCCESS
        _until(lambda: eng.rounds == [1, 3], "the held ops' round")
        assert time.perf_counter() - t_free < 4.0, "a window after the hold"
        assert not any(f.done() for f in futs[1:]), "answered unresolved"
        eng.release(1)
        for f in futs[1:]:
            assert f.result(timeout=10).status_code == C.STATUS_CODE_SUCCESS
        assert [c["rounds_ahead"] for c in eng.counts] == [0, 0]
        assert [c["ops"] for c in eng.counts] == [1, 3]
        h0, h1 = (s["hold"] for s in eng.spans)
        assert h0[1] == 0.0
        assert h1[1] > 0.0
        # the hold lies between the window and the verification
        a1, v1 = eng.spans[1]["assembly"], eng.spans[1]["verify"]
        assert a1[0] + a1[1] <= h1[0] and h1[0] + h1[1] <= v1[0]
        # and the deferred ops' queue wait holds it
        assert eng.spans[1]["queue"][1] >= h1[1]
    finally:
        for g in eng.gates:
            g.set()
        sched.close()
    assert not sched.worker_alive()


@pytest.mark.parametrize("depth", [1, 2])
def test_batch_that_fills_during_a_hold_leaves_at_once(depth):
    """Branch (a): a full batch is dispatched with the head still in
    flight, and full batches keep the ledger at ``depth``, as before the
    rule: the collector stops at the settle of round 0 with ``depth``
    rounds dispatched behind it."""
    bs = 8
    eng = _GatedEngine(batch_size=bs)
    sched = BatchScheduler(eng, max_wait_ms=10_000.0, idle_gap_ms=5.0,
                           pipeline_depth=depth)
    try:
        futs = _held(eng, sched, 2)
        # the queue reaches a batch: out it goes, round 0 unreleased;
        # its dispatch waits in the engine while more batches queue up
        eng.dispatching.clear()
        eng.dispatch_gate = threading.Event()
        futs += sched.submit_many([(_req(), None)] * (bs - 2))
        _until(eng.dispatching.is_set, "the full batch's dispatch")
        assert not eng.gates[0].is_set() and not futs[0].done()
        for _ in range(depth + 1):
            futs += sched.submit_many([(_req(), None)] * bs)
        eng.dispatch_gate.set()
        # full batches go out until ``depth`` rounds stand behind the
        # one being settled, and no further
        _until(lambda: len(eng.rounds) == 1 + depth, "the ledger's bound")
        _until(eng.resolving.is_set, "the settle of round 0")
        assert len(eng.rounds) == 1 + depth
        for k in range(3 + depth):
            _until(lambda: len(eng.gates) > k, f"round {k}'s dispatch")
            eng.release(k)
        for f in futs:
            assert f.result(timeout=10).status_code == C.STATUS_CODE_SUCCESS
        assert eng.rounds == [1] + [bs] * (2 + depth)
        ahead = [c["rounds_ahead"] for c in eng.counts]
        assert ahead[:2] == [0, 1] and max(ahead) == depth
        assert eng.spans[1]["hold"][1] > 0.0
    finally:
        for g in eng.gates:
            g.set()
        sched.close()
    assert not sched.worker_alive()


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning"  # the crash case
)
@pytest.mark.parametrize("how", ["close", "crash"])
def test_a_hold_ends_as_the_collector_ends(how):
    """``close()`` during a hold refuses the queued ops and still
    answers the round in flight; a crash during one fails every queued
    and in-flight future. Nobody is left waiting."""
    from grapevine_tpu.server.scheduler import SchedulerShutdown

    eng = _GatedEngine()
    sched = BatchScheduler(eng, max_wait_ms=10_000.0, idle_gap_ms=5.0)
    try:
        futs = _held(eng, sched, 2)
        if how == "close":
            closer = threading.Thread(target=sched.close)
            closer.start()
            for f in futs[1:]:
                with pytest.raises(SchedulerShutdown):
                    f.result(timeout=10)
            assert not futs[0].done()  # its round is still on the device
            eng.release(0)
            assert futs[0].result(timeout=10).status_code == \
                C.STATUS_CODE_SUCCESS
            closer.join(timeout=10)
            assert not closer.is_alive()
        else:
            eng.poll_error = RuntimeError("probe failed")
            for f in futs:
                with pytest.raises(RuntimeError, match="worker died"):
                    f.result(timeout=10)
            with pytest.raises(SchedulerShutdown):
                sched.submit_nowait(_req())
        _until(lambda: not sched.worker_alive(), "the collector's exit")
        assert eng.rounds == [1]
    finally:
        for g in eng.gates:
            g.set()
        sched.close()


def test_hold_under_many_submitters_answers_every_op_once_in_order():
    """Time-bounded stress of the dispatch rule: more submitter threads
    than cores, a short switch interval, rounds that finish a few
    milliseconds after their dispatch. Whatever the interleaving: every
    op gets its own answer, rounds settle in dispatch order, no round is
    dispatched with more than ``depth`` ahead of it, and a short round
    never with any."""
    import random
    import struct
    import sys

    from grapevine_tpu.wire.records import RequestRecord

    bs, depth, n_threads, per_thread = 8, 2, 16, 60
    settled: list[int] = []

    class _TimedEngine(_GatedEngine):
        def handle_queries(self, reqs, now):
            with self._lock:
                self.rounds.append(len(reqs))
            return [QueryResponse(
                record=Record(msg_id=C.ZERO_MSG_ID, sender=C.ZERO_PUBKEY,
                              recipient=C.ZERO_PUBKEY, timestamp=0,
                              payload=r.record.payload),
                status_code=C.STATUS_CODE_SUCCESS) for r in reqs]

        def handle_queries_async(self, reqs, now):
            k = len(self.gates)
            pending = super().handle_queries_async(reqs, now)
            inner = pending.resolve

            def resolve():
                out = inner()
                settled.append(k)
                return out

            pending.resolve = resolve
            threading.Timer(0.003, self.gates[k].set).start()
            return pending

    eng = _TimedEngine(batch_size=bs)
    sched = BatchScheduler(eng, max_wait_ms=4.0, idle_gap_ms=1.0,
                           pipeline_depth=depth)
    errors: list = []

    def client(t):
        rng = random.Random(t)
        try:
            for i in range(per_thread):
                tag = struct.pack("<HH", t, i)
                resp = sched.submit(QueryRequest(
                    request_type=C.REQUEST_TYPE_READ,
                    auth_identity=b"\x01" * 32,
                    auth_signature=b"\x02" * C.SIGNATURE_SIZE,
                    record=RequestRecord(
                        payload=tag + bytes(C.PAYLOAD_SIZE - 4))))
                assert resp.record.payload[:4] == tag
                if rng.random() < 0.3:
                    time.sleep(rng.random() * 0.002)
        except Exception as exc:  # surfaced below
            errors.append(exc)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    try:
        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
        for g in eng.gates:
            g.set()
        sched.close()
    assert not errors, errors[0]
    assert sum(eng.rounds) == n_threads * per_thread
    assert settled == list(range(len(eng.rounds)))
    for c in eng.counts:
        assert c["rounds_ahead"] <= depth
        assert c["ops"] == bs or c["rounds_ahead"] == 0, c
