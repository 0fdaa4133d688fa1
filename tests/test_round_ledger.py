"""The round ledger under the pipelined scheduler (obs/tracer.py,
engine/batcher.py, server/scheduler.py): one clock, the round's own
``device`` time beside ``inflight``, the ``queue`` and ``settle`` spans,
and the allowlisted per-round counts — on a depth-2 engine whose clock
is a counter, so every stamp is known exactly."""

import os
import sys
import threading
import time

import pytest

from grapevine_tpu.config import GrapevineConfig
from grapevine_tpu.engine import batcher as batcher_mod
from grapevine_tpu.engine.batcher import GrapevineEngine
from grapevine_tpu.obs import phases as phases_mod
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
    """Stands in for the ``time`` module in the scheduler, the batcher
    and the span primitive: ``perf_counter`` is a counter (one tick a
    call, from any thread) that remembers which function took each
    stamp (for a span's two, the function that opened the span); there
    is no ``monotonic`` to take a second clock from. ``thread_time`` is
    the real one: CPU seconds are no stamps."""

    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0
        self.stamps: list[tuple[float, str]] = []
        self.time, self.sleep = time.time, time.sleep
        self.thread_time = time.thread_time

    def perf_counter(self) -> float:
        frame = sys._getframe(1)
        while frame.f_code.co_filename == phases_mod.__file__:
            frame = frame.f_back
        caller = frame.f_code.co_name
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
    monkeypatch.setattr(phases_mod, "time", clock)
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
    # its device arrays went where its handle used to die, after the
    # held ops were taken: the hold window does not hold the release
    r0, rdur = first["spans"]["release"]
    assert h0 + hdur <= r0 and r0 + rdur <= second["spans"]["verify"][0]


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
    only the engine's own counts (the journal's read 0: no state
    directory)."""
    tracer = RoundTracer(capacity=4)
    engine.attach_tracer(tracer)
    try:
        engine.handle_queries([_req(1), _req(2)], 1_700_000_001)
    finally:
        engine.attach_tracer(None)
    (ledger,) = _ledgers(tracer)
    assert set(ledger["spans"]) == set(STABLE_SPANS)
    assert ledger["spans"]["queue"][1] == ledger["spans"]["settle"][1] == 0
    assert set(ledger["counts"]) == {
        "device_exact", "journal_seal_s", "journal_fsync_s", "journal_bytes"}
    assert ledger["counts"]["journal_bytes"] == 0
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


# -- the collector's cycle, accounted whole (obs/phases.py span) ---------

#: the collector's states at the top level of a cycle that never wait by
#: design (verify holds verify_prep and verify_native, dispatch holds
#: journal and checkpoint); the waiting ones are in cycle_wait_s
WORKING = ("verify", "stage", "pack", "dispatch", "demux", "observe",
           "release", "settle")
NEW_SPANS = ("cycle", "stage", "pack", "verify_prep", "verify_native",
             "observe", "release")
CYCLE_COUNTS = ("cycle_wait_s", "cycle_cpu_s", "cycle_blocked_s",
                "cycle_native_wait_s", "cycle_unspanned_s")


def _cycle_accounts(ledgers):
    """For every round whose cycle is known: (the cycle's wall, the
    wall of the working spans that began inside it — its own round's
    verify to dispatch, an older round's demux to settle —, its
    counts)."""
    working = [(start, dur) for e in ledgers for name in WORKING
               for start, dur in [e["spans"][name]] if dur]
    out = []
    for e in ledgers:
        c0, cdur = e["spans"]["cycle"]
        if cdur:
            inside = sum(d for s, d in working if c0 <= s < c0 + cdur)
            out.append((cdur, inside, e["counts"]))
    return out


@pytest.fixture
def real_clock(engine):
    """(tracer, make_scheduler) on the module's engine and the real
    clocks; the schedulers made are closed afterwards."""
    tracer = RoundTracer(capacity=256, registry=None)
    engine.attach_tracer(tracer)
    engine._last_ready = (0.0, False)
    made = []

    def make(depth):
        made.append(BatchScheduler(engine, max_wait_ms=50.0, idle_gap_ms=20.0,
                                   scheme=_Scheme, pipeline_depth=depth))
        return made[-1]

    yield tracer, make
    for sched in made:
        sched.close()
    engine.attach_tracer(None)


@pytest.mark.parametrize("depth", [1, 2])
def test_a_cycle_is_partitioned_by_its_spans(real_clock, depth):
    """cycle = cycle_wait_s + the working spans' wall + cycle_unspanned_s
    round by round, at both depths, and consecutive rounds' cycles tile
    the collector's time."""
    tracer, make = real_clock
    sched = make(depth)
    _drive(sched, 12)
    sched.close()  # the last cycle has ended and is on its round
    ledgers = _ledgers(tracer)
    assert len(ledgers) >= 12
    accounts = _cycle_accounts(ledgers)
    assert len(accounts) >= 11
    for cycle, working, counts in accounts:
        assert set(CYCLE_COUNTS) <= set(counts)
        whole = counts["cycle_wait_s"] + working + counts["cycle_unspanned_s"]
        # chrome_trace() floors every start and duration to a microsecond
        assert whole == pytest.approx(cycle, rel=0.01, abs=40e-6)
        assert counts["cycle_unspanned_s"] <= cycle
        assert counts["cycle_cpu_s"] <= cycle + 1e-3
    for prev, cur in zip(ledgers, ledgers[1:]):
        p0, pd = prev["spans"]["cycle"]
        c0, _ = cur["spans"]["cycle"]
        if pd and cur["spans"]["cycle"][1] and cur["counts"]["ops"] == BATCH \
                and prev["counts"]["ops"] == BATCH:
            # full rounds back to back: no sleep between their cycles
            assert c0 == pytest.approx(p0 + pd, abs=200e-6)


def test_a_hold_with_a_settle_inside_it_is_counted_once(clocked, monkeypatch):
    """The held round's ledger ``hold`` window holds the settle of the
    round it waited for; the cycle's account gives that time to the
    settle's own spans and only the waiting to ``cycle_wait_s``: on the
    counting clock the partition is exact."""
    _, tracer, sched = clocked
    released = threading.Event()
    really_ready = batcher_mod.PendingRound.ready
    monkeypatch.setattr(
        batcher_mod.PendingRound, "ready",
        lambda self: released.is_set() and really_ready(self))
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
    time.sleep(0.1)  # the collector holds the two behind the first
    released.set()
    for f in futs:
        f.result(timeout=120)
    sched.close()
    first, second = _ledgers(tracer)
    h0, hdur = second["spans"]["hold"]
    s0, sdur = first["spans"]["settle"]
    assert hdur > 0 and h0 <= s0 and s0 + sdur <= h0 + hdur
    (_, _, _), (cycle, working, counts) = _cycle_accounts([first, second])
    # the first round's evict, demux, observe and settle ran inside the
    # second's cycle, under its hold window, and are counted once
    assert working >= sum(first["spans"][n][1]
                          for n in ("demux", "observe", "release", "settle"))
    assert counts["cycle_wait_s"] + working + counts["cycle_unspanned_s"] \
        == cycle
    assert counts["cycle_wait_s"] < hdur


def test_a_thread_spinning_on_the_gil_shows_as_blocked_not_as_cpu(real_clock):
    """A second thread that holds the GIL makes the collector wait for
    it inside its working states: ``cycle_blocked_s`` rises and
    ``cycle_cpu_s`` does not."""
    tracer, make = real_clock
    sched = make(1)

    def sums(waves):
        """Over all the cycles of ``waves`` rounds: one cycle's CPU can
        come in 10 ms ticks, and consecutive cycles tile the thread's
        time, so their sum is off by a tick at most."""
        before = len(_ledgers(tracer))
        _drive(sched, waves)
        time.sleep(0.05)  # the last round's cycle is amended on
        counts = [c for _, _, c in _cycle_accounts(_ledgers(tracer)[before:])]
        assert len(counts) >= waves - 1
        return tuple(sum(c[k] for c in counts)
                     for k in ("cycle_blocked_s", "cycle_cpu_s"))

    sums(4)  # warm
    calm_blocked, calm_cpu = sums(32)
    stop, spinning = threading.Event(), threading.Event()

    def spin():
        n = 0
        while not stop.is_set():
            n += 1
            if n == 100_000:
                spinning.set()

    spinner = threading.Thread(target=spin, daemon=True)
    spinner.start()
    try:
        # on a loaded machine the thread may take a while to get going
        assert spinning.wait(timeout=60)
        busy_blocked, busy_cpu = sums(32)
    finally:
        stop.set()
        spinner.join()
    # each GIL hand-back costs the collector up to the 5 ms switch
    # interval, tens of ms a cycle and a second or so over 32 here; its
    # own CPU time hardly moves beside that (contended locks and a
    # shared core cost it a little, the clock a tick or two)
    rose = busy_blocked - calm_blocked
    assert rose > 0.1, (calm_blocked, busy_blocked)
    assert busy_cpu - calm_cpu < 0.25 * rose + 0.02, (calm_cpu, busy_cpu,
                                                      rose)


def test_the_span_primitive_nests_and_partitions(monkeypatch):
    """One enter and one leave: wall, CPU, the ledger entry (a name
    entered twice adds up; a child with no ledger writes to its
    parent's), the histogram for a PHASES name only, own time = wall
    less children's, and a native span's wait for the GIL."""
    clock = CountingClock()
    monkeypatch.setattr(phases_mod, "time", clock)
    phases_mod.reset_thread_spans()

    class Histogram:
        seen = []

        def observe(self, value, phase):
            self.seen.append((phase, value))

    hist, ledger = Histogram(), {}
    cycle = phases_mod.span("cycle").begin()                      # tick 1
    with phases_mod.span("verify", ledger, hist):                 # 2
        with phases_mod.span("verify_prep"):                      # 3, 4
            pass
        with phases_mod.span("verify_native") as crossing:        # 5
            clock.perf_counter()                                  # 6
            clock.perf_counter()                                  # 7
            crossing.native_s = 1 * TICK                          # 8
        with phases_mod.span("verify_prep"):                      # 9, 10
            pass
    with phases_mod.span("stage", ledger, hist):                  # 11: 12, 13
        pass
    cycle.end()                                                   # 14
    assert ledger == {"verify": (2 * TICK, 9 * TICK),
                      "verify_prep": (3 * TICK, 2 * TICK),
                      "verify_native": (5 * TICK, 3 * TICK),
                      "stage": (12 * TICK, 1 * TICK)}
    assert hist.seen == [("verify", 9 * TICK)]  # stage is no PHASES name
    assert cycle.wall == 13 * TICK
    own = cycle.own
    assert own == {"verify": 4 * TICK, "verify_prep": 2 * TICK,
                   "verify_native": 3 * TICK, "stage": 1 * TICK}
    counts = cycle.cycle_counts()
    assert counts["cycle_wait_s"] == 0
    assert counts["cycle_unspanned_s"] == 3 * TICK
    assert sum(own.values()) + counts["cycle_unspanned_s"] == cycle.wall
    # the crossing's stamps read 3 ticks, the call's own clock 1: two
    # ticks waiting to get the GIL back; the rest of blocked is the
    # working spans' fake wall less their real (tiny) CPU
    assert counts["cycle_native_wait_s"] == 2 * TICK
    assert 2 * TICK <= counts["cycle_blocked_s"] <= 9 * TICK
    # the CPU clock is read for the cycle, its waits and its native
    # call, and for no working state
    assert cycle.cpu is not None and crossing.cpu is not None
    assert phases_mod.span("hold").begin().end().cpu is not None
    assert phases_mod.span("pack").begin().end().cpu is None
    assert phases_mod._tls.top is None


def test_the_new_names_pass_the_allowlist_and_no_other_does():
    tr = RoundTracer(capacity=4)
    seq = tr.record_round({name: (1.0, 0.5) for name in NEW_SPANS},
                          {name: 0.25 for name in CYCLE_COUNTS})
    for name in NEW_SPANS:
        assert name in STABLE_SPANS
        phases_mod.span(name)
    assert set(CYCLE_COUNTS) <= set(ROUND_COUNTS)
    for bad in ("op_read", "cycle_7", "client", "verify_native_op"):
        with pytest.raises(TelemetryLeakError):
            phases_mod.span(bad)
        with pytest.raises(TelemetryLeakError):
            tr.record_round({bad: (0.0, 1.0)})
        with pytest.raises(TelemetryLeakError):
            tr.amend_round(seq, {bad: (0.0, 1.0)})
    # the tracer takes what the primitive takes
    assert phases_mod.SPAN_NAMES <= set(STABLE_SPANS) | set(phases_mod.PHASES)


def test_annotations_outside_a_round_are_an_allowlist_too():
    for name in ("state_init", "ingress", "asleep"):
        with phases_mod.trace_span(name):
            pass
    for bad in ("op_read", "asleep_7", "cycle"):
        with pytest.raises(TelemetryLeakError):
            with phases_mod.trace_span(bad):
                pass
    # no name is both a span of the ledger and a bare annotation
    assert not phases_mod.ANNOTATION_NAMES & phases_mod.SPAN_NAMES


@pytest.mark.skipif(not os.path.exists("/proc/self/task"),
                    reason="thread names are read from /proc")
def test_the_collector_names_its_thread_and_no_other(real_clock):
    """The profiler names a capture's line after the thread: the
    collector's is its own, which is how a reader of the capture tells
    its annotations from a handler's or the expiry timer's."""
    _, make = real_clock
    sched = make(1)
    _drive(sched, 1)

    def comm(tid):
        with open(f"/proc/self/task/{tid}/comm") as f:
            return f.read().strip()

    assert comm(sched._worker.native_id) == phases_mod.COLLECTOR_THREAD
    assert len(phases_mod.COLLECTOR_THREAD.encode()) <= 15
    assert comm(threading.main_thread().native_id) != \
        phases_mod.COLLECTOR_THREAD


def test_amend_round_takes_counts():
    """A depth-1 round's cycle ends after its ledger was recorded: span
    and counts are added by seq, under the same schema."""
    tr = RoundTracer(capacity=4)
    seq = tr.record_round({"round": (1.0, 2.0)}, {"ops": 3})
    assert tr.amend_round(seq, {"cycle": (0.5, 3.0)},
                          {"cycle_wait_s": 1.25, "cycle_cpu_s": 0.5,
                           "cycle_blocked_s": 0.0,
                           "cycle_unspanned_s": 0.125})
    (ledger,) = _ledgers(tr)
    assert ledger["spans"]["cycle"] == (0.5, 3.0)
    assert ledger["counts"] == {"ops": 3, "cycle_wait_s": 1.25,
                                "cycle_cpu_s": 0.5, "cycle_blocked_s": 0.0,
                                "cycle_unspanned_s": 0.125}
    assert tr.amend_round(seq, counts={"cycle_wait_s": 2.0})
    assert _ledgers(tr)[0]["counts"]["cycle_wait_s"] == 2.0
    for bad in ({"cycle_ops": 1}, {"cycle_wait_s": -1.0},
                {"cycle_cpu_s": "much"}):
        with pytest.raises(TelemetryLeakError):
            tr.amend_round(seq, counts=bad)
    assert not tr.amend_round(9, counts={"cycle_wait_s": 0.0})
