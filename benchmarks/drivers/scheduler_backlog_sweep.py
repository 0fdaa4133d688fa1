"""``scheduler_backlog``'s closed loop with the bus's TTL at work: the
same signed backlog into the server's scheduler, and an expiry sweep of
both trees after every ``rounds_per_sweep``-th round answered.

A served engine with ``--expiry-period`` sweeps from a thread of its own
(``server/service.py`` ``run_expiry_loop``; an engine tier starts the
same loop), once an interval. The benchmark's server is not started as
a listener, so that thread does not run here; this driver has one in its
place, which calls what that loop calls, ``engine.expire(clock())`` with
the server's own clock, and is paced by rounds and not by seconds.
Every sweep is asked for by a round's answers: if a sweep is still
running when the next is due, the next follows it. ``rounds_per_sweep``
is no deployment's cadence (that is one sweep in 8,640 s): it is there
so that a window holds some tens of sweeps, and it is large enough for
the loop to be back in its steady state when the next sweep is called
(the scheduler's pipeline full, the device pacing the rounds), which is
how a deployment's sweep finds a busy bus. What the cell is judged on,
``sweep_stall_ms`` (``end_to_end``), then does not depend on it.

Everything else is ``scheduler_backlog``'s, by delegation and unchanged:
``prepare``, the loader's loop, the drain, ``stop``. ``ready`` runs one
sweep more, in set-up, so that a program that does not warm its sweep
with its first round meets the window warm all the same. The sweeps
themselves are recorded by the harness's ``RoundLog``, which wraps
``engine.expire`` as it wraps the rounds' dispatch (``lib/roundlog.py``).

With a TTL of a day nothing comes due inside a run, so ``finish`` makes
it come due once the window has closed and its ops are answered: one
sweep more at a clock ``expiry_period`` past the clock of the window's
middle round, so that the records the first half of the window wrote
are due and those of the second half are not, and then
``ROUNDS_AFTER`` rounds more of the script, whose by-id and next-message
ops name records on both sides of that cut. Sweep and rounds stand in
the log like any other, so the oracle's replay holds the engine to
"a record older is gone, none younger is removed, no other answer
changes" at the timed size, on the timed engine, outside the window.
Traffic parameters: ``scheduler_backlog``'s, and ``rounds_per_sweep``.
"""

from __future__ import annotations

import queue
import statistics
import threading

from . import scheduler_backlog as backlog

prepare = backlog.prepare
stop = backlog.stop

#: rounds of the script sent after the sweep that ``finish`` makes due
ROUNDS_AFTER = 2


def _sweep(ctx) -> None:
    """One call of the engine's ``expire`` with the server's clock, as
    ``run_expiry_loop`` makes it."""
    ctx.engine.expire(ctx.server.clock())


def ready(ctx, state) -> None:
    backlog.ready(ctx, state)
    _sweep(ctx)


def run(ctx, state, t_open: float) -> float:
    """The delegate's window, with the sweeps' thread beside it: a sweep
    is due at every ``rounds_per_sweep``-th round the window resolves."""
    every = int(ctx.traffic["rounds_per_sweep"])
    due: queue.SimpleQueue = queue.SimpleQueue()
    closed = threading.Event()
    resolved = 0

    def count(_entry) -> None:  # on the collector's thread
        nonlocal resolved
        resolved += 1
        if resolved % every == 0:
            due.put(resolved)

    def sweeps() -> None:
        while due.get() is not None and not closed.is_set():
            _sweep(ctx)

    thread = threading.Thread(target=sweeps, name="bench-sweeps",
                              daemon=True)
    state["first_entry"], state["t_open"] = len(ctx.log.entries), t_open
    ctx.log.watchers.append(count)
    thread.start()
    try:
        return backlog.run(ctx, state, t_open)
    finally:
        # a sweep that is running ends; one that is only due is dropped
        ctx.log.watchers.remove(count)
        closed.set()
        due.put(None)
        thread.join(backlog.STALL_S)


def finish(ctx, state) -> dict:
    backlog.finish(ctx, state)  # the window's ops are answered
    rounds = ctx.log.rounds(state["first_entry"])
    sweeps = ctx.log.sweeps(state["first_entry"])  # the window's
    period = ctx.cfg.expiry_period
    cut = rounds[len(rounds) // 2]["now"]
    evicted = ctx.engine.expire(cut + period)
    backlog._build(state, ROUNDS_AFTER * state["bs"])
    backlog._submit(ctx, state, ROUNDS_AFTER * state["bs"])
    observed = backlog.finish(ctx, state)  # those too, or counted as not
    t_open = state["t_open"]
    observed["summary"].update(
        sweeps=len(sweeps), rounds_per_sweep=ctx.traffic["rounds_per_sweep"],
        records_evicted=sum(s["evicted"] for s in sweeps),
        # [start s from the window's opening, wall s] of each
        sweep_log=[[round(s["t_start"] - t_open, 6),
                    round(s["t_end"] - s["t_start"], 6)] for s in sweeps],
        due_sweep={"cut": cut, "now": cut + period, "evicted": evicted,
                   "rounds_after": ROUNDS_AFTER})
    return observed


def stalls_ms(obs: dict) -> list[float]:
    """``expire`` called -> returned, ms on the host's clock, of every
    sweep that was called and returned inside the window. The call
    holds the engine's lock from the first instant to the last: no round
    is dispatched in it and none is answered."""
    t_open, t_end = obs["window"]
    return [1e3 * (s["t_end"] - s["t_start"]) for s in obs["sweeps"]
            if t_open <= s["t_start"] and s["t_end"] <= t_end]


def end_to_end(ctx, obs: dict) -> dict:
    """``sweep_stall_ms``: how long one sweep holds the bus, the mean
    over every sweep of the window, none left out: the wait for the two
    rounds in flight, the device's pass over both trees, the return.
    ``ops_per_s`` is the delegate's and is printed beside it (the
    ``samples`` line), not judged: in this cell it follows
    ``rounds_per_sweep``, which is no deployment's."""
    values = backlog.end_to_end(ctx, obs)
    stalls = stalls_ms(obs)
    values["sweep_stall_ms"] = statistics.fmean(stalls) if stalls else None
    return values
