"""``RoundLog`` keeps rounds and sweeps in the engine's order: a stub
engine whose own lock orders a dispatching thread and a sweeping one,
and which numbers each call as it gets that lock. The log's list must
hold the stub's numbers in order, every time; a log that appended after
the call returned, outside any lock, does not (shown on the same stub,
so the test can fail)."""

import sys
import threading
import time
import types

import pytest

from benchmarks.lib import roundlog
from benchmarks.lib.roundlog import RoundLog


class _NoSpan:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture(autouse=True)
def no_profiler(monkeypatch):
    monkeypatch.setattr(roundlog, "annotation", lambda name: _NoSpan())


class StubEngine:
    """Rounds and sweeps one at a time under one lock, as the engine
    applies them; ``seq`` is the order in which they got it."""

    def __init__(self):
        self.config = types.SimpleNamespace(expiry_period=86400)
        self._lock = threading.Lock()
        self.seq = 0

    def _ticket(self) -> int:
        with self._lock:
            self.seq += 1
            ticket = self.seq
            time.sleep(0)  # let go of the interpreter inside the lock
        # a gap between the lock's release and the return, where the
        # other thread's call can run to its end
        time.sleep(0)
        return ticket

    def handle_queries_async(self, reqs, now):
        ticket = self._ticket()
        return types.SimpleNamespace(resolve=lambda: [ticket])

    def expire(self, now, period=None):
        return self._ticket()


def _hammer(engine, n=300):
    """One thread dispatching, one sweeping, ``n`` calls each."""
    def rounds():
        for k in range(n):
            engine.handle_queries_async([k], k).resolve()

    def sweeps():
        for k in range(n):
            engine.expire(k)

    threads = [threading.Thread(target=rounds), threading.Thread(target=sweeps)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)


def _tickets(entries):
    return [e["evicted"] if e["kind"] == "sweep" else e["resps"][0]
            for e in entries]


@pytest.mark.parametrize("attempt", range(5))
def test_the_log_holds_rounds_and_sweeps_in_the_engines_order(attempt):
    engine = StubEngine()
    log = RoundLog(engine)
    _hammer(engine)
    assert len(log.entries) == 600
    assert _tickets(log.entries) == list(range(1, 601))
    assert len(log.rounds()) == len(log.sweeps()) == 300
    sweep = log.sweeps()[0]
    assert sweep["period"] == 86400 and sweep["now"] == 0
    assert sweep["t_start"] <= sweep["t_end"]
    assert log.sweeps(since=len(log.entries)) == []


def test_a_log_appended_after_the_call_returned_loses_the_order():
    """The wrapper this one replaced, on the same stub: over a few
    attempts a sweep lands behind the round dispatched after it."""
    for _ in range(20):
        engine = StubEngine()
        entries = []
        inner_round, inner_sweep = engine.handle_queries_async, engine.expire

        def recorded(reqs, now):
            pending = inner_round(reqs, now)
            entries.append(pending.resolve()[0])
            return pending

        def swept(now, period=None):
            evicted = inner_sweep(now, period)
            entries.append(evicted)
            return evicted

        engine.handle_queries_async, engine.expire = recorded, swept
        _hammer(engine)
        if entries != sorted(entries):
            return
    pytest.fail("the unlocked log kept the order 20 times over: the stub "
                "no longer shows what the log's lock is for")


def test_a_sweep_that_raises_leaves_no_entry_and_frees_the_lock():
    engine = StubEngine()

    def broken(now, period=None):
        raise RuntimeError("device lost")

    engine.expire = broken
    log = RoundLog(engine)
    with pytest.raises(RuntimeError):
        engine.expire(5)
    assert log.entries == []
    engine.handle_queries_async([1], 6)  # the lock is free again
    assert [e["kind"] for e in log.entries] == ["round"]
