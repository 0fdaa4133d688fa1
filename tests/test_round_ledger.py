"""The round ledger under the pipelined scheduler (obs/tracer.py,
engine/batcher.py, server/scheduler.py): one clock, the round's own
``device`` time beside ``inflight``, the ``queue`` and ``settle`` spans,
and the allowlisted per-round counts — on a depth-2 engine whose clock
is a counter, so every stamp is known exactly."""

import sys
import threading
import time

import pytest

from grapevine_tpu.config import GrapevineConfig
from grapevine_tpu.engine import batcher as batcher_mod
from grapevine_tpu.engine.batcher import GrapevineEngine
from grapevine_tpu.obs.registry import TelemetryLeakError
from grapevine_tpu.obs.tracer import (
    DERIVED_SPANS,
    ROUND_COUNTS,
    STABLE_SPANS,
    RoundTracer,
)
from grapevine_tpu.server import scheduler as scheduler_mod
from grapevine_tpu.server.scheduler import BatchScheduler, round_counts
from grapevine_tpu.wire import constants as C
from grapevine_tpu.wire.records import QueryRequest, RequestRecord

#: exact in binary and a whole number of microseconds (15625), so sums
#: of stamps compare with == after ``chrome_trace()``'s microseconds
TICK = 1.0 / 64
BATCH = 4


def _ledgers(tracer) -> list[dict]:
    """The retained rounds as ``/trace`` serves them, oldest first:
    ``{"seq", "spans": {name: (start_s, dur_s)}, "counts"}`` rebuilt
    from ``chrome_trace()``'s ``grapevine/<span>`` events by ``seq``."""
    by_seq: dict[int, dict] = {}
    for ev in tracer.chrome_trace()["traceEvents"]:
        if ev.get("ph") != "X":
            continue
        name = ev["name"].removeprefix("grapevine/")
        args = dict(ev["args"])
        entry = by_seq.setdefault(
            args.pop("seq"), {"spans": {}, "counts": {}})
        entry["spans"][name] = (ev["ts"] / 1e6, ev["dur"] / 1e6)
        if name == "round":
            entry["counts"] = args
    return [{"seq": seq, **by_seq[seq]} for seq in sorted(by_seq)]


class CountingClock:
    """Stands in for the ``time`` module in the scheduler and the
    batcher: ``perf_counter`` is a counter (one tick a call, from any
    thread) that remembers which function took each stamp; there is no
    ``monotonic`` to take a second clock from."""

    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0
        self.stamps: list[tuple[float, str]] = []
        self.time, self.sleep = time.time, time.sleep

    def perf_counter(self) -> float:
        caller = sys._getframe(1).f_code.co_name
        with self._lock:
            self._n += 1
            t = self._n * TICK
            self.stamps.append((t, caller))
        return t

    def taken_in(self, function: str) -> list[float]:
        return [t for t, caller in self.stamps if caller == function]


class _Scheme:
    """Signatures are the bytes ``ok`` or not: no curve arithmetic."""

    @staticmethod
    def verify(pub, ctx, msg, sig):
        return sig == b"ok"

    @classmethod
    def batch_verify(cls, items, chunks=1):
        return all(cls.verify(*it) for it in items)


def _req(n: int) -> QueryRequest:
    return QueryRequest(
        request_type=C.REQUEST_TYPE_CREATE,
        auth_identity=bytes([1 + n % 5]) + b"\x01" * 31,
        auth_signature=b"\x01" * C.SIGNATURE_SIZE,
        record=RequestRecord(msg_id=C.ZERO_MSG_ID,
                             recipient=bytes([7 + n % 3]) + b"\x02" * 31,
                             payload=bytes([n & 0xFF]) * C.PAYLOAD_SIZE),
    )


@pytest.fixture(scope="module")
def engine():
    eng = GrapevineEngine(GrapevineConfig(
        max_messages=64, max_recipients=8, mailbox_cap=4, batch_size=BATCH,
        stash_size=64, bucket_cipher_rounds=0, pipeline_depth=2))
    eng.handle_queries([_req(0)], 1_700_000_000)  # compile outside the clock
    return eng


@pytest.fixture
def clocked(engine, monkeypatch):
    """(clock, tracer, scheduler) with the counting clock in place."""
    clock = CountingClock()
    monkeypatch.setattr(batcher_mod, "time", clock)
    monkeypatch.setattr(scheduler_mod, "time", clock)
    tracer = RoundTracer(capacity=64, registry=None)
    engine.attach_tracer(tracer)
    engine._last_ready = (0.0, False)
    sched = BatchScheduler(engine, max_wait_ms=50.0, idle_gap_ms=20.0,
                           scheme=_Scheme)
    assert sched.pipeline_depth == 2
    yield clock, tracer, sched
    sched.close()
    engine.attach_tracer(None)


def _drive(sched, waves: int, bad: frozenset = frozenset()):
    """``waves`` full batches, all enqueued before any is awaited."""
    futs = []
    for n in range(waves * BATCH):
        sig = b"no" if n in bad else b"ok"
        futs.append(sched.submit_nowait(_req(n), (b"p", b"c", b"m", sig)))
    done = []
    for f in futs:
        try:
            done.append(f.result(timeout=120))
        except Exception as exc:  # AuthFailure for the bad ones
            done.append(exc)
    return done, futs


def test_one_clock_and_every_span_on_every_ledger(clocked):
    clock, tracer, sched = clocked
    _drive(sched, 5)
    ledgers = _ledgers(tracer)
    assert [e["seq"] for e in ledgers] == list(range(1, len(ledgers) + 1))
    assert len(ledgers) >= 5
    stamps = {t for t, _ in clock.stamps}
    for e in ledgers:
        assert set(e["spans"]) == set(STABLE_SPANS)
        assert set(e["counts"]) == set(ROUND_COUNTS)
        for name, (start, dur) in e["spans"].items():
            # every start and every end is a stamp of the one clock
            assert start in stamps and (dur == 0 or start + dur in stamps), name
    assert not hasattr(clock, "monotonic")
    sched.engine.metrics.registry.audit()


def test_device_spans_tile_and_inflight_holds_them(clocked):
    _, tracer, sched = clocked
    _drive(sched, 6)
    ledgers = _ledgers(tracer)
    tiled = 0
    for prev, cur in zip(ledgers, ledgers[1:]):
        p0, pd = prev["spans"]["device"]
        c0, cd = cur["spans"]["device"]
        i0, idur = cur["spans"]["inflight"]
        assert c0 >= p0 + pd, "consecutive rounds' device time overlaps"
        assert i0 <= c0 and i0 + idur == c0 + cd  # one end, inflight >= device
        assert idur >= cd
        if cur["counts"]["rounds_ahead"] >= 1:
            # dispatched while the previous round was unresolved: its own
            # time starts where the previous round's ended
            assert c0 == p0 + pd
            assert idur > cd
            tiled += 1
    assert tiled >= 2, [e["counts"] for e in ledgers]
    assert max(e["counts"]["rounds_ahead"] for e in ledgers) == 2
    assert {e["counts"]["device_exact"] for e in ledgers} <= {0, 1}
    # the first round had nothing ahead: both windows end together and
    # its own time starts when its enqueue returned, after it began
    first = ledgers[0]["spans"]
    assert first["inflight"][0] < first["device"][0]


def test_queue_wait_sum_equals_the_hand_sum(clocked):
    clock, tracer, sched = clocked
    _drive(sched, 4)
    enqueued = clock.taken_in("submit_nowait")
    assert len(enqueued) == 4 * BATCH
    taken = 0
    for e in _ledgers(tracer):
        n = e["counts"]["ops"]
        mine = enqueued[taken:taken + n]  # rounds fill first come
        taken += n
        q0, qdur = e["spans"]["queue"]
        t_dispatch = q0 + qdur
        assert q0 == min(mine)
        assert e["counts"]["queue_wait_sum_s"] == sum(
            t_dispatch - t for t in mine)
        assert e["counts"]["rejected"] == 0
        # dispatch follows the queue wait at once, on the same clock
        assert e["spans"]["dispatch"][0] > t_dispatch
        assert e["spans"]["inflight"][0] > e["spans"]["dispatch"][0]
    assert taken == 4 * BATCH
    assert round_counts([1.0, 2.5], 3, 4.0, 2, 1) == {
        "ops": 2, "rejected": 1, "queue_wait_sum_s": 4.5, "rounds_ahead": 2,
        "verify_chunks": 1}


def test_settle_lands_on_its_own_round(clocked):
    clock, tracer, sched = clocked
    _, futs = _drive(sched, 5)
    sched.close()  # every settle has ended
    stamps = clock.taken_in("_settle")
    starts, ends = stamps[0::2], stamps[1::2]
    ledgers = _ledgers(tracer)
    assert len(starts) == len(ends) == len(ledgers)
    for e, s0, s1 in zip(ledgers, starts, ends):
        assert e["spans"]["settle"] == (s0, s1 - s0)
        r0, rdur = e["spans"]["round"]
        assert s0 > r0 + rdur  # after the ledger's own round closed
    # and each handler reads its round's stamp off its future
    settled = [f.settled_at for f in futs]
    by_round = iter(settled)
    for e, s0 in zip(ledgers, starts):
        assert {next(by_round) for _ in range(e["counts"]["ops"])} == {s0}


def test_a_held_round_carries_its_hold_on_the_ledger(clocked, monkeypatch):
    """The real handle under the dispatch rule: ``ready()`` is what the
    hold polls (kept False here until the test has seen the queue looked
    at and left alone), the ops that gathered behind the round in flight
    ride one round with nothing ahead of it, and that round's ledger has
    a ``hold`` span between its window and its verification; the round
    that was not held has one of 0."""
    _, tracer, sched = clocked
    released = threading.Event()
    polls = []
    really_ready = batcher_mod.PendingRound.ready

    def ready(self):
        polls.append(1)
        return released.is_set() and really_ready(self)

    monkeypatch.setattr(batcher_mod.PendingRound, "ready", ready)
    # the first dispatch waits inside the engine until the two ops that
    # will be held are queued behind it
    dispatching, go = threading.Event(), threading.Event()
    dispatch = sched.engine.handle_queries_async

    def gated(reqs, now):
        if not dispatching.is_set():
            dispatching.set()
            assert go.wait(timeout=60)
        return dispatch(reqs, now)

    monkeypatch.setattr(sched.engine, "handle_queries_async", gated)
    auth = (b"p", b"c", b"m", b"ok")
    futs = [sched.submit_nowait(_req(0), auth)]
    assert dispatching.wait(timeout=60)
    futs += [sched.submit_nowait(_req(n), auth) for n in (1, 2)]
    go.set()
    deadline = time.monotonic() + 60
    seen = len(polls)
    while len(polls) < seen + 3:
        assert time.monotonic() < deadline
        time.sleep(0.002)
    with sched._cv:
        assert len(sched._queue) == 2
    released.set()
    for f in futs:
        f.result(timeout=120)  # the module's engine may be at a cap
    first, second = _ledgers(tracer)
    assert [e["counts"]["ops"] for e in (first, second)] == [1, 2]
    assert [e["counts"]["rounds_ahead"] for e in (first, second)] == [0, 0]
    assert first["spans"]["hold"][1] == 0
    h0, hdur = second["spans"]["hold"]
    a0, adur = second["spans"]["assembly"]
    assert hdur > 0
    assert a0 + adur <= h0 and h0 + hdur <= second["spans"]["verify"][0]
    assert second["spans"]["queue"][1] >= hdur
    # the round in flight was settled inside the hold, before the held
    # round was dispatched: no answer waited for another round
    assert (first["spans"]["settle"][0]
            < second["spans"]["dispatch"][0])


@pytest.mark.parametrize("metric,cell", [
    ("hold_ms", "backlog-grpc-1chip"), ("hold_ms.trickle", "trickle-1chip")])
def test_the_benchmark_reads_the_hold_span(metric, cell):
    """``benchmarks/layer_metrics/hold_ms*.json`` through the reader the
    benchmark has (``ledger_span``), on a synthetic ledger: the median
    of the rounds' ``hold`` spans in ms, 0 where no round was held, and
    nothing from a program that keeps no such span (the parent)."""
    from benchmarks.lib.manifest import Benchmark, load_kind

    (entry, spec), = [(m, f) for m, f in Benchmark.load().per_layer(cell)
                      if m["name"] == metric]
    assert entry["layer"] == "scheduler" and entry["unit"] == "ms"
    read = load_kind("readers", spec["reader"]).read
    tr = RoundTracer(capacity=8)
    for k, hold_s in enumerate((0.0, 0.048, 0.052)):
        t0 = 10.0 + k
        tr.record_round({"assembly": (t0, 0.004),
                         "hold": (t0 + 0.004, hold_s),
                         "verify": (t0 + 0.06, 0.002),
                         "round": (t0, 0.13)})
    ledger = tr.chrome_trace()["traceEvents"]
    assert read(spec["params"], {"ledger": ledger, "window": (9.0, 20.0)}) \
        == pytest.approx(48.0)
    # only the first round began inside this window: it was not held
    assert read(spec["params"], {"ledger": ledger, "window": (9.0, 10.5)}) \
        == 0.0
    old_program = [ev for ev in ledger if ev.get("name") != "grapevine/hold"]
    assert read(spec["params"],
                {"ledger": old_program, "window": (9.0, 20.0)}) is None


def test_a_round_handle_says_when_the_device_is_done(engine):
    pending = engine.handle_queries_async([_req(3)], 1_700_000_002)
    deadline = time.monotonic() + 60
    while not pending.ready():  # never blocks; the device finishes
        assert time.monotonic() < deadline
        time.sleep(0.002)
    assert len(pending.resolve()) == 1
    assert pending.ready()


def test_rejected_ops_are_counted_not_admitted(clocked):
    _, tracer, sched = clocked
    out, _ = _drive(sched, 1, bad=frozenset({2}))
    assert sum(isinstance(r, Exception) for r in out) == 1
    (ledger,) = _ledgers(tracer)
    assert ledger["counts"]["ops"] == BATCH - 1
    assert ledger["counts"]["rejected"] == 1


def test_counts_ride_the_round_event_and_unlisted_ones_raise():
    tr = RoundTracer(capacity=4)
    seq = tr.record_round(
        {"round": (1.0, 2.0), "evict": (2.0, 0.5)},
        {"ops": 3, "rejected": 1, "queue_wait_sum_s": 0.75,
         "rounds_ahead": 2, "device_exact": 1})
    assert seq == 1
    events = [e for e in tr.chrome_trace()["traceEvents"] if e.get("ph") == "X"]
    (round_ev,) = [e for e in events if e["name"] == "grapevine/round"]
    assert round_ev["args"] == {"seq": 1, "ops": 3, "rejected": 1,
                                "queue_wait_sum_s": 0.75, "rounds_ahead": 2,
                                "device_exact": 1}
    assert all(e["args"] == {"seq": 1} for e in events if e is not round_ev)
    for bad in ({"reads": 2}, {"op_type": 1}, {"ops": "three"},
                {"ops": -1}, {"ops": float("nan")}, {"ops": True}):
        with pytest.raises(TelemetryLeakError):
            tr.record_round({"round": (0.0, 1.0)}, bad)
    assert tr.chrome_trace()["otherData"]["rounds_recorded_total"] == 1
    # a span added after the record goes to its own seq, under the schema
    assert tr.amend_round(1, {"settle": (3.5, 0.25)})
    assert _ledgers(tr)[0]["spans"]["settle"] == (3.5, 0.25)
    assert not tr.amend_round(7, {"settle": (0.0, 0.0)})
    with pytest.raises(TelemetryLeakError):
        tr.amend_round(1, {"op_read": (0.0, 1.0)})
    assert set(DERIVED_SPANS) == {"queue", "inflight", "device", "round"}


def test_a_round_resolved_without_a_scheduler_keeps_the_shape(engine):
    """The direct path (no queue, no settle): zero-duration spans and
    only the engine's own count."""
    tracer = RoundTracer(capacity=4)
    engine.attach_tracer(tracer)
    try:
        engine.handle_queries([_req(1), _req(2)], 1_700_000_001)
    finally:
        engine.attach_tracer(None)
    (ledger,) = _ledgers(tracer)
    assert set(ledger["spans"]) == set(STABLE_SPANS)
    assert ledger["spans"]["queue"][1] == ledger["spans"]["settle"][1] == 0
    assert set(ledger["counts"]) == {"device_exact"}
    d0, dd = ledger["spans"]["device"]
    i0, idur = ledger["spans"]["inflight"]
    assert i0 <= d0 and d0 + dd == pytest.approx(i0 + idur)


def test_a_round_behind_a_sweep_is_not_exact(engine, monkeypatch):
    """An expiry sweep (or a flush) runs on the device between two
    rounds and inside the next round's ``device`` span: that round says
    so, and the one after it is exact again."""
    monkeypatch.setattr(batcher_mod, "_still_running", lambda resp: True)
    tracer = RoundTracer(capacity=8)
    engine.attach_tracer(tracer)
    engine._last_ready = (0.0, False)
    now = 1_700_000_002
    try:
        engine.handle_queries([_req(3)], now)  # nothing known before it
        engine.handle_queries([_req(4)], now)
        engine.expire(now, period=3600)
        engine.handle_queries([_req(5)], now)  # holds the sweep's time
        engine.handle_queries([_req(6)], now)
    finally:
        engine.attach_tracer(None)
    assert [e["counts"]["device_exact"]
            for e in _ledgers(tracer)] == [0, 1, 0, 1]
