"""``ops_per_s`` counts all the work over all the time: the backlog
driver closes its window at the first answers to arrive at or after
``--seconds``, so a stall that straddles ``--seconds`` is inside it."""

import gc
import threading
import time
import types

import pytest

from benchmarks.drivers import scheduler_backlog


def _drive(monkeypatch, seconds, resolve_at):
    """``run`` over a fake engine whose rounds of 4 ops resolve at the
    offsets ``resolve_at`` (seconds from the window's opening)."""
    monkeypatch.setattr(scheduler_backlog, "_build", lambda state, upto: None)
    monkeypatch.setattr(scheduler_backlog, "_submit",
                        lambda ctx, state, n: 0.0)
    log = types.SimpleNamespace(on_resolved=None, entries=[])
    ctx = types.SimpleNamespace(log=log, seconds=seconds)
    state = {"bs": 4, "outstanding": 16, "cpu": [],
             "known": types.SimpleNamespace(learn=lambda reqs, resps: None)}
    t_open = time.perf_counter()

    def engine():
        for at in resolve_at:
            time.sleep(max(0.0, t_open + at - time.perf_counter()))
            e = {"reqs": [0] * 4, "resps": [0] * 4, "ok": [True] * 4,
                 "t_resolved": time.perf_counter()}
            log.entries.append(e)
            if log.on_resolved is not None:
                log.on_resolved(e)

    feeder = threading.Thread(target=engine, daemon=True)
    feeder.start()
    try:
        t_end = scheduler_backlog.run(ctx, state, t_open)
    finally:
        gc.unfreeze()  # run() freezes its log wave by wave
    feeder.join()
    # one pair of CPU stamps per wave: every round but the closing one
    assert len(state["cpu"]) == sum(
        e["t_resolved"] < t_open + seconds for e in log.entries)
    obs = {"window": (t_open, t_end),
           "rounds": [e for e in log.entries
                      if t_open <= e["t_resolved"] <= t_end]}
    return t_end - t_open, obs, scheduler_backlog.end_to_end(ctx, obs)


def test_the_window_closes_with_the_first_answers_after_seconds(monkeypatch):
    steady = [0.05 * k for k in range(1, 12)]
    window, obs, e2e = _drive(monkeypatch, 0.3, steady)
    # rounds at 0.05 .. 0.30 s: the one at (or just after) 0.3 s closes it
    assert 0.3 <= window < 0.36 and len(obs["rounds"]) in (6, 7)
    assert e2e["ops_per_s"] == pytest.approx(4 * len(obs["rounds"]) / window)
    assert e2e["ops_per_s"] == pytest.approx(80.0, rel=0.1)


def test_a_stall_across_the_end_of_the_window_is_counted(monkeypatch):
    """The engine hangs from 0.2 s to 0.7 s of a 0.3 s window and drains
    afterwards. Timed to the last answer inside 0.3 s, the rate would
    read as if nothing had happened: 4 rounds in 0.2 s, 80 ops/s."""
    stalled = [0.05, 0.10, 0.15, 0.20, 0.70, 0.75, 0.80]
    window, obs, e2e = _drive(monkeypatch, 0.3, stalled)
    assert 0.7 <= window < 0.75 and len(obs["rounds"]) == 5
    assert e2e["ops_per_s"] == pytest.approx(20 / window)
    assert e2e["ops_per_s"] < 30.0
