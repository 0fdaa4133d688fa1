"""``scheduler_backlog``'s closed loop into the bus as OPERATIONS.md's
runbook serves it: a server built with its TTL (``--expiry-period``),
``--state-dir`` and ``--leakmon`` on together (the configuration's
``grapevine_config.expiry_period``, ``server.durability`` and
``server.leakmon``), so that every round of the window is journaled,
sealed and fsynced before it dispatches and hands its transcript to the
monitor's own thread; and behind the window the three options meet: a
sweep journaled among rounds, the auditor's verdict, the crash, and the
restart from the journal alone.

The window is ``scheduler_backlog``'s, by delegation and unchanged:
``ready``, the loader's loop, the drain. No sweep falls into it (a
deployment sweeps 0.33 s in 8,640 s; with sweeps inside, ``ops_per_s``
follows their cadence) and no checkpoint (the configuration's
``checkpoint_every_rounds`` is sized so): what is timed is what the
journal and the auditor together cost a full bus.

``finish`` is the tail, once the window's ops are answered, outside the
timed span, after the harness has taken ``memory_peak_bytes`` and the
trace (as ``scheduler_backlog_sweep``'s due sweep and
``scheduler_backlog_durable``'s tail are). The scheduler is idle
through (b)-(d).

(a) ``tail_rounds`` rounds more of the script, and after the first half
    of them a sweep made due: ``engine.expire(cut + expiry_period)``
    with ``cut`` the clock of the window's middle round, so that about
    half of the window's records go on the timed engine, and the
    sweep's frame stands among round frames in the journal.
(b) The monitor flushed and its verdict read (what ``/leakaudit``
    serves), over every round it was handed since the server was built.
(c) The crash: ``engine.abandon()``, as the durable driver's.
(d) The restart: ``engine.recover()`` **from no checkpoint**: the empty
    state built and every frame since the server was built replayed
    through the jitted programs, the warm round, the window's rounds,
    the tail's and its sweep. (``backlog-durable-1chip-2p21``'s restart
    loads a checkpoint and replays 16 frames; a deployment's meets up
    to ``checkpoint_every_rounds`` frames behind its checkpoint, at the
    rate this replay measures.)
(e) ``rounds_after`` rounds more of the script, whose by-id and
    next-message ops name records on both sides of the sweep's cut and
    from before the crash.

Sweep, tail rounds and rounds after stand in the harness's ``RoundLog``
like any other, so the comparisons that decide ``correct`` hold the
recovered engine to the oracle, which expires as the engine does.

What the driver itself refuses (counted into ``unanswered``, so the run
is not ``correct``, and named in the ``samples`` line under
``runbook_refusals``):

- the engine recovered something when it was built (a state directory
  left by another run is a different run; ``stop`` removes it), or a
  checkpoint fell into the run;
- at a round's resolve the journal's ``last_durable_seq`` was under that
  round's place in the log, counted from 1 over rounds and sweeps alike
  (``DurableWatch``): an answer left the engine before its frame was
  fsynced;
- recovery loaded a checkpoint, replayed other than the frames
  journaled, or replayed fewer sweep frames than sweeps were called;
- the monitor's verdict is not PASS, it audited no round at all, or it
  had not caught up when its verdict was read.

Traffic parameters: ``scheduler_backlog``'s, ``tail_rounds`` and
``rounds_after``. A run writes the journal's bytes (a frame a round) to
the disk the checkout is on, and no checkpoint.
"""

from __future__ import annotations

import time

from ..lib.harness import memory_peak_bytes
from . import scheduler_backlog as backlog
from . import scheduler_backlog_durable as durable

ready = backlog.ready
end_to_end = backlog.end_to_end
run = durable.run
stop = durable.stop

#: seconds the monitor is given to audit what it still holds (at most
#: its queue's 64 rounds)
FLUSH_S = 120.0


def prepare(ctx) -> dict:
    engine, lm = ctx.engine, ctx.server.leakmon
    missing = [what for what, there in (
        ("a TTL (grapevine_config.expiry_period)",
         ctx.cfg.expiry_period > 0),
        ("durability (server.durability.state_dir)",
         engine.durability is not None),
        ("a leak monitor (server.leakmon)", lm is not None)) if not there]
    if missing:
        raise RuntimeError("scheduler_backlog_runbook: the configuration's "
                           f"server has no {', no '.join(missing)}")
    dm = engine.durability
    refusals = []
    if dm.recovered_from_checkpoint or dm.replayed or dm.seq:
        refusals.append(
            f"the engine recovered from {dm.dcfg.state_dir} when it was "
            f"built (checkpoint {dm.ckpt_seq}, {dm.replayed} records "
            "replayed): a state directory left by another run is a "
            "different run")
    state = backlog.prepare(ctx)
    state["refusals"] = refusals
    state["watch"] = durable.DurableWatch(ctx)
    ctx.log.watchers.append(state["watch"])
    ctx.say(phase="state_dir", path=dm.dcfg.state_dir,
            filesystem=durable.filesystem_of(dm.dcfg.state_dir),
            journal_fsync_every=dm.dcfg.journal_fsync_every,
            checkpoint_every_rounds=dm.dcfg.checkpoint_every_rounds,
            expiry_period=ctx.cfg.expiry_period,
            leakmon_window_rounds=lm.cfg.window_rounds,
            leakmon_queue_depth=lm.cfg.queue_depth)
    return state


def _replayed(registry) -> dict:
    """Frames this process's recoveries have replayed, by kind (the
    program's counter; zeros where it keeps none)."""
    counter = registry.get("grapevine_recover_replayed_total")
    return {kind: int(counter.get(kind=kind)) if counter else 0
            for kind in ("round", "sweep")}


def finish(ctx, state) -> dict:
    backlog.finish(ctx, state)  # the window's ops are answered
    engine, dm, lm = ctx.engine, ctx.engine.durability, ctx.server.leakmon
    registry = ctx.server.metrics_registry
    tail, after = int(ctx.traffic["tail_rounds"]), int(
        ctx.traffic["rounds_after"])
    window = ctx.log.rounds(state["first_entry"])
    # the rounds that closed the window and drained it taught the
    # loader's loop nothing: their answers are learned here
    durable._learn(state, [e for e in window if e["t_resolved"] is not None
                           and e["t_resolved"] >= state["t_end"]])
    refusals = state["refusals"]
    # (a) the tail, a due sweep in its middle
    period = ctx.cfg.expiry_period
    cut = window[len(window) // 2]["now"]
    first_tail = len(ctx.log.entries)
    durable._rounds(ctx, state, tail // 2)
    evicted = engine.expire(cut + period)
    durable._rounds(ctx, state, tail - tail // 2)
    durable._learn(state, ctx.log.rounds(first_tail))
    # (b) the auditor
    caught_up = lm.flush(FLUSH_S)
    audit = lm.verdict()
    if not caught_up:
        refusals.append(f"the leak monitor had not audited what it was "
                        f"handed {FLUSH_S:.0f} s after the last round")
    if audit["verdict"] != "PASS":
        tripped = [f"{d['name']}/{d['tree']}={d['statistic']}"
                   for d in audit["detectors"] if d["verdict"] != "PASS"]
        refusals.append(f"the leak audit's verdict is {audit['verdict']} "
                        f"({', '.join(tripped)})")
    if not audit["rounds_observed"]:
        refusals.append("the leak monitor audited no round at all")
    if dm.ckpt_seq or dm.status()["last_checkpoint_seq"]:
        refusals.append(
            f"a checkpoint fell into the run (seq {dm.ckpt_seq}): "
            "checkpoint_every_rounds is too small for this window")
    journaled, sweeps_called = dm.seq, len(ctx.log.sweeps())
    before = _replayed(registry)
    engine.abandon()  # (c)
    t0 = time.perf_counter()
    engine.recover()  # (d)
    recover_s = time.perf_counter() - t0
    replayed = {k: v - before[k] for k, v in _replayed(registry).items()}
    if dm.recovered_from_checkpoint:
        refusals.append(f"recovery loaded checkpoint {dm.ckpt_seq}; the "
                        "run wrote none")
    if (dm.replayed != journaled
            or sum(replayed.values()) != journaled
            or replayed["sweep"] != sweeps_called or sweeps_called < 1):
        refusals.append(
            f"recovery replayed {dm.replayed} records ({replayed}); "
            f"{journaled} were journaled, {sweeps_called} of them sweeps")
    durable._rounds(ctx, state, after)  # (e)
    observed = backlog.finish(ctx, state)  # every op sent, the tail's too
    watch = state["watch"]
    if watch.early:
        refusals.append(f"{watch.early} of {watch.watched} rounds were "
                        "answered before their frame was fsynced")
    observed["unanswered"] += len(refusals)
    rounds = [e for e in window if e["t_resolved"] is not None]
    observed["summary"].update(
        runbook_refusals=refusals, rounds_watched=watch.watched,
        due_sweep={"cut": cut, "now": cut + period, "evicted": evicted,
                   "after_tail_round": tail // 2},
        leakaudit={"verdict": audit["verdict"],
                   "rounds_observed": audit["rounds_observed"],
                   "rounds_dropped": audit["rounds_dropped"],
                   "detectors": [
                       {k: d[k] for k in ("name", "tree", "statistic",
                                          "threshold", "samples", "verdict")}
                       for d in audit["detectors"]]},
        frames_journaled=journaled, recover_s=recover_s,
        recover_replayed=replayed, rounds_after=after,
        journal_bytes_per_round=(
            dm.journal.last_append["bytes"] if rounds else None),
        memory_peak_after_tail_bytes=memory_peak_bytes(),
        state_dir_filesystem=durable.filesystem_of(dm.dcfg.state_dir))
    return observed
