"""Closed loop into the server's scheduler, in-process: the way a
``--role engine`` tier receives signed ops from its frontends.

``outstanding`` signed ops are kept in flight: whenever a round's
answers arrive, as many new ops go in as it answered. Each op carries a
real sr25519 signature over a seeded challenge, made before the window
and checked in the round's batch verification. Traffic parameters
(``traffic/<name>.json``): ``outstanding_rounds``, ``mix``,
``identities``, ``recipient_zipf``, ``presign_ops_per_s``.

The loader shares the collector's interpreter lock, so what its thread
does inside the window is taken from the program. It is kept small and
it is measured: every request that names no message id (three in five)
is built before the window; what is kept for the oracle leaves the
cycle collector's sight wave by wave (``lib/gcwatch.py``); and the
thread's own CPU seconds (``time.thread_time``, which does not count
waiting for the lock) are stamped per wave, apart for the calls into
the program's ``submit_nowait`` and for everything else.
"""

from __future__ import annotations

import queue
import time

from ..lib import gcwatch, opmix
from ..lib.identities import SigningPool
from ..lib.roundlog import annotation

#: seconds without a resolved round after which the run gives up
STALL_S = 300.0


def prepare(ctx) -> dict:
    """Identities and the script, here; the signatures start in the
    worker processes and are collected by ``ready``, after the harness
    has warmed the round program beside them."""
    tr = ctx.traffic
    bs = ctx.cfg.batch_size
    outstanding = tr["outstanding_rounds"] * bs
    n_script = int(tr["presign_ops_per_s"] * ctx.seconds) + outstanding
    pool = SigningPool()
    try:
        idents = pool.identities(ctx.ident_seed, tr["identities"])
        script = opmix.script(ctx.seed, n_script, tr)
        signed = pool.sign_script(ctx.seed, idents, [e[1] for e in script])
    except BaseException:
        pool.close()
        raise
    return {"idents": idents, "pubs": [pub for _, pub in idents],
            "script": script, "pool": pool, "signed": signed, "next": 0,
            "reused": 0, "futures": [], "ready": [], "cpu": [], "bs": bs,
            "outstanding": outstanding}


def ready(ctx, state) -> None:
    """The rest of set-up: the signatures, then every request that can
    be built without an answer, and the first ``outstanding`` ready."""
    from grapevine_tpu.wire import records

    pool = state.pop("pool")
    try:
        auth = state["auth"] = state.pop("signed")()
    finally:
        pool.close()
    script, pubs = state["script"], state["pubs"]
    payloads = state["payloads"] = opmix.Payloads(ctx.seed)
    state["known"] = opmix.KnownIds(pubs)
    state["records"] = records
    state["prebuilt"] = [
        None if opmix.needs_answers(e) else opmix.build_request(
            e, j, auth[j], None, pubs, payloads, records)
        for j, e in enumerate(script)]
    ctx.say(phase="traffic", identities=len(pubs), presigned_ops=len(script),
            prebuilt_ops=sum(r is not None for r in state["prebuilt"]),
            outstanding=state["outstanding"], signing_workers=pool.n)
    _build(state, state["outstanding"])


def _build(state, upto: int) -> None:
    """Top the ready list up to ``upto`` requests."""
    ready_, script, auth = state["ready"], state["script"], state["auth"]
    prebuilt = state["prebuilt"]
    while len(ready_) < upto:
        j = state["next"]
        if j >= len(script):
            # a faster program than the pre-signing allowed for: the
            # script starts over (same signatures, same verification work)
            j = state["next"] = 0
            state["reused"] += 1
        req = prebuilt[j] or opmix.build_request(
            script[j], j, auth[j], state["known"], state["pubs"],
            state["payloads"], state["records"])
        ready_.append((req, auth[j]))
        state["next"] = j + 1


def _submit(ctx, state, n: int) -> float:
    """``n`` ready ops into the scheduler; returns the CPU seconds this
    thread spent inside the program's ``submit_nowait``."""
    ready_ = state["ready"]
    take, state["ready"] = ready_[:n], ready_[n:]
    submit = ctx.server.scheduler.submit_nowait
    c0 = time.thread_time()
    futures = [submit(req, item) for req, item in take]
    c1 = time.thread_time()
    state["futures"] += futures
    return c1 - c0


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
        c0 = time.thread_time()
        if e["t_resolved"] >= t_close:
            break
        with annotation("bench/submit"):
            inside = _submit(ctx, state, len(e["reqs"]))
        with annotation("bench/build_wave"):
            state["known"].learn(e["reqs"], e["resps"])
            _build(state, bs)
            gcwatch.settle()
        # [CPU s inside submit_nowait, CPU s of everything else] per wave
        state["cpu"].append((inside, time.thread_time() - c0 - inside))
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
    cpu = state["cpu"]
    return {"attempted": len(state["futures"]), "unanswered": unanswered,
            "loadgen_cpu": cpu,
            "summary": {
                # > 0: the program outran presign_ops_per_s and met ops
                # and signatures a second time; the run is not comparable
                "presigned_script_reused": state["reused"],
                "loadgen_waves": len(cpu),
                "loadgen_submit_cpu_s": sum(c[0] for c in cpu),
                "loadgen_own_cpu_s": sum(c[1] for c in cpu)}}


def stop(ctx, state) -> None:
    """Nothing of this driver outlives the window; the signing pool
    only if set-up failed before ``ready`` closed it."""
    pool = state.pop("pool", None)
    if pool is not None:
        pool.close()


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
