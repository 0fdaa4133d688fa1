"""Open loop through gRPC + session + signature, from JAX-free client
child processes (the parent holds the chip).

``children`` processes of ``sessions_per_child`` authenticated
``GrapevineClient`` sessions each replay their share of one seeded
ON/OFF schedule whose mean rate is a number in the traffic file; no
code rescales it. Latency is counted from each op's due time. Traffic
parameters: ``mean_rate_ops_per_s``, ``on_factor``, ``off_factor``,
``duty``, ``period_s``, ``children``, ``sessions_per_child``, ``mix``,
``identities``, ``recipient_zipf``.
"""

from __future__ import annotations

import collections
import hashlib
import json
import os
import statistics
import subprocess
import sys
import time

from ..lib import gcwatch, schedules
from ..lib.identities import SigningPool
from ..lib.manifest import ROOT
from ..lib.stats import percentile

CHILD = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "grpc_child.py")
#: the first arrival is due this long after the window opens, so that
#: every child has read its start line
LEAD_S = 0.05
DRAIN_S = 120.0
AUTH_S = 600.0
#: the harness records every op's enqueue -> settle seconds for this
#: driver: ``service_ms`` is read from them
SUBMIT_LOG = True


def prepare(ctx) -> dict:
    tr = ctx.traffic
    pool = SigningPool()
    try:
        idents = pool.identities(ctx.ident_seed, tr["identities"])
    finally:
        pool.close()
    n_children, per_child = tr["children"], tr["sessions_per_child"]
    n_id = len(idents)
    sched = schedules.onoff_schedule(
        tr["mean_rate_ops_per_s"], tr["on_factor"], tr["off_factor"],
        tr["duty"], tr["period_s"], ctx.seconds - LEAD_S, ctx.seed)
    port = ctx.server.start("insecure-grapevine://127.0.0.1:0")
    state = {"idents": idents, "children": [], "n_ops": len(sched["t_s"]),
             "fingerprint": schedules.fingerprint(sched["t_s"], sched["u"])}
    pubs = [pub.hex() for _, pub in idents]
    for c in range(n_children):
        job = {"root": ROOT, "child": c, "seed": ctx.seed,
               "ident_seed": ctx.ident_seed,
               "uri": f"insecure-grapevine://127.0.0.1:{port}",
               "server_static": ctx.server.identity.public.hex(),
               "pubs": pubs, "mix": tr["mix"],
               "recipient_zipf": tr["recipient_zipf"],
               # session k speaks for identity k mod n: beyond one session
               # each, an identity is a wallet on a second device
               "session_identities": [k % n_id for k in range(
                   c, n_children * per_child, n_children)],
               "t_s": [LEAD_S + float(t) for t in sched["t_s"][c::n_children]],
               "u": [float(u) for u in sched["u"][c::n_children]],
               "drain_s": DRAIN_S}
        proc = subprocess.Popen(
            [sys.executable, CHILD], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, text=True,
            env={k: v for k, v in os.environ.items() if k != "BENCH_RUN"})
        proc.stdin.write(json.dumps(job) + "\n")
        proc.stdin.flush()
        state["children"].append(proc)
    deadline = time.monotonic() + AUTH_S
    for proc in state["children"]:
        line = proc.stdout.readline().strip()
        if line != "ready" or time.monotonic() > deadline:
            raise RuntimeError(f"a client child did not come up: {line!r}")
    ctx.say(phase="traffic", identities=len(idents), children=n_children,
            sessions=n_children * per_child, arrivals=state["n_ops"],
            mean_rate_ops_per_s=tr["mean_rate_ops_per_s"],
            schedule_fingerprint=state["fingerprint"])
    return state


def run(ctx, state, t_open: float) -> float:
    """The window holds the ops due in ``seconds``; returns its end."""
    state["first_round"] = len(ctx.log.entries)
    t_start = time.monotonic() - (time.perf_counter() - t_open)
    for proc in state["children"]:
        proc.stdin.write(f"go {t_start!r}\n")
        proc.stdin.flush()
    t_close = t_open + ctx.seconds
    while (left := t_close - time.perf_counter()) > 0:
        time.sleep(min(left, 0.25))
        gcwatch.settle()  # the log kept for the oracle, out of gc's sight
    return t_close


def finish(ctx, state) -> dict:
    """Every child waits for its last answer, then reports; what the
    clients decrypted must be what the engine answered."""
    records = []
    for proc in state["children"]:
        line = proc.stdout.readline()
        if line:
            records += json.loads(line)["records"]
        proc.stdin.close()
        proc.wait(timeout=30)
    errors = [r[4] for r in records if r[4] is not None]
    unanswered = state["n_ops"] - len(records) + len(errors)
    engine = collections.Counter(
        hashlib.sha256(r.pack()).hexdigest()
        for e in ctx.log.rounds(state["first_round"])
        if e["resps"] is not None for r in e["resps"])
    client = collections.Counter(r[3] for r in records if r[3] is not None)
    differing = (sum((client - engine).values())
                 + sum((engine - client).values()))
    ms = lambda xs, p: percentile([x * 1e3 for x in xs], p)  # noqa: E731
    handed = [r[5] - r[0] for r in records] or [0.0]
    started = [r[1] - r[5] for r in records] or [0.0]
    return {"attempted": state["n_ops"], "unanswered": unanswered,
            "client_mismatch": differing, "records": records,
            "summary": {"client_ops": len(records),
                        "client_errors": errors[:3],
                        # how late the generator ran, and where: waiting
                        # for a free session, then for its thread to start
                        "due_to_handed_ms": {
                            "p50": ms(handed, 50), "p99": ms(handed, 99),
                            "max": ms(handed, 100)},
                        "handed_to_sent_ms": {
                            "p50": ms(started, 50), "p99": ms(started, 99),
                            "max": ms(started, 100)}}}


def stop(ctx, state) -> None:
    for proc in state["children"]:
        if proc.poll() is None:
            proc.kill()
        proc.wait()
        for pipe in (proc.stdin, proc.stdout):
            if pipe and not pipe.closed:
                pipe.close()


def end_to_end(ctx, obs: dict) -> dict:
    """Commit latency: decrypted answer minus due time, over every op
    due in the window, in ms."""
    lat = [(r[2] - r[0]) * 1e3 for r in obs["observed"]["records"]]
    return {"commit_p50_ms": statistics.median(lat),
            "commit_p95_ms": percentile(lat, 95.0)}
