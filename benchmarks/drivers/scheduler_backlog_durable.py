"""``scheduler_backlog``'s closed loop into a bus that survives a
restart: a server built with ``--state-dir`` (the configuration's
``server.durability``), so that every round of the window is journaled,
sealed and fsynced before it dispatches, and behind the window, the
crash and the restart themselves, on the engine that was timed.

The window is ``scheduler_backlog``'s, by delegation and unchanged:
``ready``, the loader's loop, the drain. No checkpoint falls into it
(the configuration's ``checkpoint_every_rounds`` is sized so): what is
timed is what RPO 0 costs a full bus between two checkpoints, which is
where a deployment spends all but a few per cent of its time.

``finish`` is the tail, once the window's ops are answered, outside the
timed span, after the harness has taken ``memory_peak_bytes`` and the
trace (as ``scheduler_backlog_sweep``'s due sweep is). The scheduler is
idle through (a)-(d): the delegate's drain has emptied it.

(a) ``engine.checkpoint_now()``: the whole state read off the device,
    sealed, written, renamed (10.25 GB at the timed size); the journal
    rolls behind it.
(b) ``tail_rounds`` rounds more of the script, journaled behind that
    checkpoint, answered.
(c) The crash: ``engine.abandon()``, the journal's handle dropped with
    no sync and the device state deleted, with no final checkpoint and
    no drain. That is what SIGKILL leaves on the disk and on the chip.
    The process itself cannot die: it holds the chip, the round log and
    the oracle's ids, and a second process could not take the chip
    while this one lives.
(d) The restart: ``engine.recover()``, the method the engine's
    constructor runs, on the same engine object, from the state
    directory: the empty state built, the checkpoint loaded into it
    (one state on the device throughout), the frames journaled behind
    it replayed through the jitted round.
(e) ``rounds_after`` rounds more of the script, whose by-id and
    next-message ops name records written before the checkpoint,
    between checkpoint and crash (the tail's answers are learned first),
    and not at all.

Tail rounds and rounds after stand in the harness's ``RoundLog`` like
any other, so the comparisons that decide ``correct`` hold the recovered
engine to the oracle that saw every acknowledged round: ``ops_wrong``
and ``ops_unanswered`` over the rounds after, ``message_count_gap`` and
``recipient_count_gap`` from the recovered state's ``health()``.

What the driver itself refuses (counted into ``unanswered``, so the run
is not ``correct``, and named in the ``samples`` line under
``durable_refusals``):

- ``prepare`` raises unless the engine recovered nothing when it was
  built (no checkpoint, no record replayed): a state directory left by
  another run is a different run. ``stop`` removes the directory.
- recovery loaded the checkpoint whose seq (a) returned, and replayed
  exactly the records journaled behind it, at least ``tail_rounds``
  (the scheduler may close a round short, so (b)'s ops can take one
  round more than ``tail_rounds``);
- at every round's resolve, from the server's first round on, the
  journal's ``last_durable_seq`` is at least that round's place in the
  log, counted from 1 (a fresh directory's journal counts rounds from
  1, and the log holds every round since the server was built): no
  answer left the engine before its frame was fsynced.

Traffic parameters: ``scheduler_backlog``'s, ``tail_rounds`` and
``rounds_after``. The ``samples`` line also carries ``checkpoint_s``,
``recover_s``, ``recover_load_s``, ``journal_bytes_per_round``, the
device's peak memory after the tail and the file system the state
directory is on. A run writes the checkpoint's bytes (the state's size)
and the journal's (a frame a round) to the disk the checkout is on.
"""

from __future__ import annotations

import os
import shutil
import time

from ..lib.harness import memory_peak_bytes
from . import scheduler_backlog as backlog

ready = backlog.ready
end_to_end = backlog.end_to_end


def filesystem_of(path: str) -> dict:
    """The mount ``path`` lies on (``/proc/self/mounts``: the longest
    mount point that is a prefix of it): device, type, mount point."""
    path = os.path.realpath(path)
    best = ("", "?", "?")
    try:
        with open("/proc/self/mounts") as f:
            for line in f:
                dev, mnt, fstype = line.split()[:3]
                if (path == mnt or path.startswith(mnt.rstrip("/") + "/")) \
                        and len(mnt) >= len(best[0]):
                    best = (mnt, dev, fstype)
    except OSError:
        pass
    return {"mount": best[0] or "?", "device": best[1], "type": best[2]}


class DurableWatch:
    """A ``RoundLog`` watcher: at every round's resolve, whether the
    journal had fsynced as far as that round's place in the log."""

    def __init__(self, ctx):
        self.log, self.engine = ctx.log, ctx.engine
        self.place: dict[int, int] = {}
        self.seen = self.early = self.watched = 0

    def __call__(self, entry) -> None:  # on the collector's thread
        entries = self.log.entries
        while self.seen < len(entries):
            self.place[id(entries[self.seen])] = self.seen + 1
            self.seen += 1
        durable = self.engine.durability.status()["last_durable_seq"]
        self.watched += 1
        self.early += durable < self.place.pop(id(entry))


def prepare(ctx) -> dict:
    dm = ctx.engine.durability
    if dm is None:
        raise RuntimeError(
            "scheduler_backlog_durable: the configuration's server has no "
            "durability (server.durability.state_dir)")
    if dm.recovered_from_checkpoint or dm.replayed or dm.seq:
        raise RuntimeError(
            f"scheduler_backlog_durable: the engine recovered from "
            f"{dm.dcfg.state_dir} when it was built (checkpoint "
            f"{dm.ckpt_seq}, {dm.replayed} records replayed): a state "
            "directory left by another run is a different run")
    state = backlog.prepare(ctx)
    state["watch"] = DurableWatch(ctx)
    ctx.log.watchers.append(state["watch"])
    ctx.say(phase="state_dir", path=dm.dcfg.state_dir,
            filesystem=filesystem_of(dm.dcfg.state_dir),
            journal_fsync_every=dm.dcfg.journal_fsync_every,
            checkpoint_every_rounds=dm.dcfg.checkpoint_every_rounds)
    return state


def run(ctx, state, t_open: float) -> float:
    state["first_entry"] = len(ctx.log.entries)
    state["t_end"] = backlog.run(ctx, state, t_open)
    return state["t_end"]


def _rounds(ctx, state, n: int) -> None:
    """``n`` rounds' worth of the script, sent and answered."""
    ops = n * state["bs"]
    backlog._build(state, ops)
    backlog._submit(ctx, state, ops)
    backlog.finish(ctx, state)


def _learn(state, rounds) -> None:
    for e in rounds:
        if e["resps"] is not None:
            state["known"].learn(e["reqs"], e["resps"])


def finish(ctx, state) -> dict:
    backlog.finish(ctx, state)  # the window's ops are answered
    engine, dm = ctx.engine, ctx.engine.durability
    tail, after = int(ctx.traffic["tail_rounds"]), int(
        ctx.traffic["rounds_after"])
    window = ctx.log.rounds(state["first_entry"])
    # the rounds that closed the window and drained it taught the
    # loader's loop nothing: their answers are learned here
    _learn(state, [e for e in window if e["t_resolved"] is not None
                   and e["t_resolved"] >= state["t_end"]])
    refusals = []
    if dm.ckpt_seq or dm.status()["last_checkpoint_seq"]:
        refusals.append(
            f"a checkpoint fell into the run (seq {dm.ckpt_seq}): "
            "checkpoint_every_rounds is too small for this window")
    t0 = time.perf_counter()
    ckpt_seq = engine.checkpoint_now()  # (a)
    t1 = time.perf_counter()
    first_tail = len(ctx.log.entries)
    _rounds(ctx, state, tail)  # (b)
    _learn(state, ctx.log.rounds(first_tail))
    journaled = dm.seq - ckpt_seq
    engine.abandon()  # (c)
    t2 = time.perf_counter()
    engine.recover()  # (d)
    t3 = time.perf_counter()
    if dm.ckpt_seq != ckpt_seq or not dm.recovered_from_checkpoint:
        refusals.append(f"recovery loaded checkpoint {dm.ckpt_seq}, the "
                        f"tail wrote {ckpt_seq}")
    if dm.replayed != journaled or journaled < tail:
        refusals.append(f"recovery replayed {dm.replayed} records; "
                        f"{journaled} were journaled behind the "
                        f"checkpoint, tail_rounds is {tail}")
    _rounds(ctx, state, after)  # (e)
    observed = backlog.finish(ctx, state)  # every op sent, the tail's too
    watch = state["watch"]
    if watch.early:
        refusals.append(f"{watch.early} of {watch.watched} rounds were "
                        "answered before their frame was fsynced")
    observed["unanswered"] += len(refusals)
    registry = ctx.server.metrics_registry
    rounds = [e for e in window if e["t_resolved"] is not None]
    observed["summary"].update(
        durable_refusals=refusals, rounds_watched=watch.watched,
        checkpoint_s=t1 - t0, checkpoint_seq=ckpt_seq,
        checkpoint_bytes=registry.get("grapevine_checkpoint_bytes").get(),
        tail_rounds_journaled=journaled, recover_s=t3 - t2,
        recover_load_s=registry.get(
            "grapevine_recovery_load_seconds").get(),
        recover_replayed=dm.replayed, rounds_after=after,
        journal_bytes_per_round=(
            dm.journal.last_append["bytes"] if rounds else None),
        memory_peak_after_tail_bytes=memory_peak_bytes(),
        state_dir_filesystem=filesystem_of(dm.dcfg.state_dir))
    return observed


def stop(ctx, state) -> None:
    watch = state.pop("watch", None)
    if watch in ctx.log.watchers:
        ctx.log.watchers.remove(watch)
    backlog.stop(ctx, state)
    dm = ctx.engine.durability
    if dm is not None:
        ctx.engine.close()
        shutil.rmtree(dm.dcfg.state_dir, ignore_errors=True)
