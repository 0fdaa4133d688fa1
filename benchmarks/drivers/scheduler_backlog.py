"""Closed loop into the server's scheduler, in-process: the way a
``--role engine`` tier receives signed ops from its frontends.

``outstanding`` signed ops are kept in flight: whenever a round's
answers arrive, as many new ops go in as it answered. Each op carries a
real sr25519 signature over a seeded challenge, made before the window
and checked in the round's batch verification. Traffic parameters
(``traffic/<name>.json``): ``outstanding_rounds``, ``mix``,
``identities``, ``recipient_zipf``, ``presign_ops_per_s``.
"""

from __future__ import annotations

import queue
import random
import time

from ..lib import opmix
from ..lib.identities import SigningPool
from ..lib.roundlog import annotation

#: seconds without a resolved round after which the run gives up
STALL_S = 300.0


def prepare(ctx) -> dict:
    from grapevine_tpu.wire import records

    tr = ctx.traffic
    bs = ctx.cfg.batch_size
    outstanding = tr["outstanding_rounds"] * bs
    n_script = int(tr["presign_ops_per_s"] * ctx.seconds) + outstanding
    pool = SigningPool()
    try:
        idents = pool.identities(ctx.ident_seed, tr["identities"])
        script = opmix.script(ctx.seed, n_script, tr)
        auth = pool.sign_script(ctx.seed, idents, [e[1] for e in script])
    finally:
        pool.close()
    pubs = [pub for _, pub in idents]
    ctx.say(phase="traffic", identities=len(idents), presigned_ops=n_script,
            outstanding=outstanding, signing_workers=pool.n)
    state = {"idents": idents, "pubs": pubs, "script": script, "auth": auth,
             "known": opmix.KnownIds(pubs), "records": records,
             "rng": random.Random(f"{ctx.seed}-payloads"), "next": 0,
             "reused": 0, "futures": [], "ready": [], "bs": bs,
             "outstanding": outstanding}
    _build(state, outstanding)
    return state


def _build(state, upto: int) -> None:
    """Top the ready list up to ``upto`` built requests."""
    ready, script, auth = state["ready"], state["script"], state["auth"]
    while len(ready) < upto:
        j = state["next"]
        if j >= len(script):
            # a faster program than the pre-signing allowed for: the
            # script starts over (same signatures, same verification work)
            j = state["next"] = 0
            state["reused"] += 1
        item = auth[j]
        ready.append((opmix.build_request(
            script[j], item, state["known"], state["pubs"], state["rng"],
            state["records"]), item))
        state["next"] = j + 1


def _submit(ctx, state, n: int) -> None:
    ready = state["ready"]
    take, state["ready"] = ready[:n], ready[n:]
    submit = ctx.server.scheduler.submit_nowait
    state["futures"] += [submit(req, item) for req, item in take]


def run(ctx, state, t_open: float) -> float:
    """Keeps the loop fed until the first round whose answers arrive at
    or after ``t_open + seconds``; that arrival closes the window and is
    returned. So a window holds a whole number of rounds and all the
    time they took: a stall that straddles ``seconds`` is inside it."""
    resolved: queue.SimpleQueue = queue.SimpleQueue()
    ctx.log.on_resolved = resolved.put
    t_close = t_open + ctx.seconds
    bs = state["bs"]
    with annotation("bench/submit"):
        _submit(ctx, state, state["outstanding"])
    _build(state, bs)
    while True:
        with annotation("bench/wait_round"):
            e = resolved.get(timeout=STALL_S)
        if e["t_resolved"] >= t_close:
            break
        with annotation("bench/submit"):
            _submit(ctx, state, len(e["reqs"]))
        with annotation("bench/build_wave"):
            state["known"].learn(e["reqs"], e["resps"])
            _build(state, bs)
    ctx.log.on_resolved = None
    return e["t_resolved"]


def finish(ctx, state) -> dict:
    """Drain: every op sent is answered (or counted as not)."""
    unanswered = 0
    deadline = time.perf_counter() + STALL_S
    for fut in state["futures"]:
        try:
            fut.result(timeout=max(0.1, deadline - time.perf_counter()))
        except Exception:  # noqa: BLE001 — any failure is an op not answered
            unanswered += 1
    return {"attempted": len(state["futures"]), "unanswered": unanswered,
            "summary": {"presigned_script_reused": state["reused"]}}


def stop(ctx, state) -> None:
    """Nothing of this driver outlives the window."""


def end_to_end(ctx, obs: dict) -> dict:
    """``ops_per_s``: every op answered inside the window as the oracle
    answers it, over all the seconds of the window, which ``run`` closed
    at the first answers to arrive at or after ``--seconds``. Answers
    come a round at a time, so a window cut at ``--seconds`` exactly
    would count in steps of one round (1.2-1.4 % of 30 s) where runs
    differ by 0.1-0.3 %."""
    t_open, t_end = obs["window"]
    good = sum(sum(e["ok"]) for e in obs["rounds"])
    return {"ops_per_s": good / (t_end - t_open)}
