"""Closed loop through the split tier: gRPC + session AEAD + signature
from JAX-free client children, through ``--role frontend`` processes,
into the engine's batched ingress (``server/tier.py``).

The harness's own server is the engine: its scheduler is served on
loopback by the program's ``EngineListener`` (the monolithic public
listener is never started), and the configuration's ``tier.frontends``
frontends are started against it with ``python -m
grapevine_tpu.server.cli --role frontend --engine 127.0.0.1:<port>`` as
an operator would. ``sessions`` authenticated ``GrapevineClient``
sessions in ``children`` processes (``grpc_closed_child.py``) each keep
exactly one op outstanding with no think time; session k speaks for
identity k mod ``identities`` and is sticky to frontend k mod
``tier.frontends``. No rate is offered: the sessions pace themselves.

The window is ``--seconds`` from the go line. ``ops_per_s`` counts, at
the clients, the ops whose decrypted answer arrived inside it and is one
the oracle agrees with, over ``--seconds``; ops in flight at its end are
awaited and checked but not counted. After the window the driver reads
each frontend's ``/metrics``, the engine's registry and the children's
CPU seconds into ``observed["tier"]`` for the ``tier_stage`` reader; the
``samples`` line also says what share of a core each child, each
frontend and the engine's process took over the window.
Traffic parameters: ``sessions``, ``children``, ``identities``, ``mix``,
``recipient_zipf``.
"""

from __future__ import annotations

import collections
import json
import os
import resource
import signal
import subprocess
import sys
import time
import urllib.request

from ..lib import gcwatch
from ..lib.identities import SigningPool
from ..lib.manifest import ROOT
from .grpc_closed_child import digest

CHILD = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "grpc_closed_child.py")
DRAIN_S = 120.0
START_S = 600.0
#: the frontend's registry series the reader divides
FRONTEND_SERIES = ("grapevine_service_seconds_total",
                   "grapevine_service_queries_total",
                   "grapevine_engine_rpc_batches_total",
                   "grapevine_engine_rpc_ops_total",
                   "grapevine_engine_rpc_retries_total")
ENGINE_SERIES = ("grapevine_engine_submit_batches_total",
                 "grapevine_engine_submit_ops_total",
                 "grapevine_engine_ingress_seconds_total")


def _env() -> dict:
    return {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}


def _listener(server):
    """The program's listener over the harness's scheduler: one per
    server (its counters are registered once), started per window."""
    from grapevine_tpu.server.tier import EngineListener

    if getattr(server, "engine_listener", None) is None:
        server.engine_listener = EngineListener(server.scheduler,
                                                server.metrics_registry)
    return server.engine_listener


def _line(proc, prefix: str, err_path: str) -> str:
    """The rest of the frontend's next stdout line, which must start
    with ``prefix`` (``server/cli.py`` prints three, in order)."""
    line = proc.stdout.readline().strip()
    if not line.startswith(prefix):
        with open(err_path) as f:
            tail = f.read()[-2000:]
        raise RuntimeError(f"a frontend did not come up: wanted {prefix!r}, "
                           f"got {line!r}; its stderr ends: {tail}")
    return line[len(prefix):].strip()


def _start_frontend(ctx, i: int, engine_port: int) -> dict:
    err_path = os.path.join(ctx.scratch, f"frontend-{i}.err")
    err = open(err_path, "w")
    proc = subprocess.Popen(
        [sys.executable, "-m", "grapevine_tpu.server.cli",
         "--role", "frontend", "--engine", f"127.0.0.1:{engine_port}",
         "--listen", "insecure-grapevine://127.0.0.1:0",
         "--metrics-port", "0", "--batch-size", str(ctx.cfg.batch_size)],
        cwd=ROOT, env=_env(), stdout=subprocess.PIPE, stderr=err, text=True)
    return {"proc": proc, "err": err, "err_path": err_path}


def prepare(ctx) -> dict:
    state = {"frontends": [], "children": [], "cpu_s": []}
    try:
        _start(ctx, state)
    except BaseException:
        stop(ctx, state)  # the harness stops only what prepare returned
        raise
    return state


def _start(ctx, state: dict) -> None:
    tr = ctx.traffic
    n_frontends = ctx.config["tier"]["frontends"]
    n_children, n_sessions = tr["children"], tr["sessions"]
    if n_sessions % n_children:
        raise ValueError(f"{n_sessions} sessions do not divide over "
                         f"{n_children} children")
    # a child holds a connection per session and a frontend one per
    # session it terminates: the soft limit on open files may be 1,024
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    if soft != hard:
        resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))
    engine_port = _listener(ctx.server).start("127.0.0.1:0")
    for i in range(n_frontends):
        state["frontends"].append(_start_frontend(ctx, i, engine_port))
    pool = SigningPool()
    try:
        idents = pool.identities(ctx.ident_seed, tr["identities"])
    finally:
        pool.close()
    state["idents"] = idents
    for fe in state["frontends"]:
        port = _line(fe["proc"], "grapevine-tpu listening on port",
                     fe["err_path"])
        fe["metrics_port"] = int(_line(
            fe["proc"], "metrics endpoint on port", fe["err_path"]))
        fe["static"] = _line(fe["proc"], "server static key:",
                             fe["err_path"])
        fe["uri"] = f"insecure-grapevine://127.0.0.1:{port}"
    pubs = [pub.hex() for _, pub in idents]
    per_child = n_sessions // n_children
    for c in range(n_children):
        job = {"root": ROOT, "child": c, "seed": ctx.seed,
               "ident_seed": ctx.ident_seed, "pubs": pubs, "mix": tr["mix"],
               "recipient_zipf": tr["recipient_zipf"],
               "frontends": [[fe["uri"], fe["static"]]
                             for fe in state["frontends"]],
               # session k: identity k mod n (beyond one session each, an
               # identity is a service on a second connection), sticky to
               # frontend k mod F; a child holds a block of sessions, so
               # every child talks to every frontend
               "sessions": [[k, k % len(idents), k % n_frontends]
                            for k in range(c * per_child,
                                           (c + 1) * per_child)],
               "drain_s": DRAIN_S}
        proc = subprocess.Popen([sys.executable, CHILD], stdin=subprocess.PIPE,
                                stdout=subprocess.PIPE, text=True, env=_env())
        state["children"].append(proc)
        proc.stdin.write(json.dumps(job) + "\n")
        proc.stdin.flush()
    ctx.say(phase="traffic", identities=len(idents), frontends=n_frontends,
            children=n_children, sessions=n_sessions,
            engine_listener_port=engine_port)


def ready(ctx, state) -> None:
    """The children authenticated their sessions beside the first
    round's compile or load; each says ``ready`` once all of its own
    are open."""
    deadline = time.monotonic() + START_S
    for proc in state["children"]:
        line = proc.stdout.readline().strip()
        if line != "ready" or time.monotonic() > deadline:
            raise RuntimeError(f"a client child did not come up: {line!r}")


def _cpu_seconds(pid: int) -> float:
    """User + system CPU seconds of a process so far, all its threads
    (``/proc/<pid>/stat``, fields 14 and 15)."""
    with open(f"/proc/{pid}/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    return (int(fields[11]) + int(fields[12])) / os.sysconf("SC_CLK_TCK")


def _host_cpu(state) -> list[float]:
    """CPU seconds so far of each frontend, then of this process (the
    engine's: collector, listener handlers, verify threads)."""
    return ([_cpu_seconds(fe["proc"].pid) for fe in state["frontends"]]
            + [time.process_time()])


def run(ctx, state, t_open: float) -> float:
    """The window is ``seconds`` from the go line; returns its end."""
    state["first_round"] = len(ctx.log.entries)
    cpu0 = _host_cpu(state)
    t_start = time.monotonic() - (time.perf_counter() - t_open)
    state["t_start"] = t_start
    for proc in state["children"]:
        proc.stdin.write(f"go {t_start!r} {ctx.seconds!r}\n")
        proc.stdin.flush()
    t_close = t_open + ctx.seconds
    while (left := t_close - time.perf_counter()) > 0:
        time.sleep(min(left, 0.25))
        gcwatch.settle()  # the log kept for the oracle, out of gc's sight
    state["host_cpu_share"] = [(b - a) / ctx.seconds
                               for a, b in zip(cpu0, _host_cpu(state))]
    return t_close


def _scrape(port: int) -> dict:
    """A frontend's ``/metrics`` as {series name: {label text: value}},
    the series the reader uses only."""
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics",
                                timeout=30) as r:
        text = r.read().decode()
    out: dict = {}
    for line in text.splitlines():
        if line.startswith("#") or not line.strip():
            continue
        series, _, value = line.rpartition(" ")
        name, _, labels = series.partition("{")
        if name in FRONTEND_SERIES:
            out.setdefault(name, {})[labels.rstrip("}")] = float(value)
    return out


def finish(ctx, state) -> dict:
    """Every child waits for its last answer, then reports; what the
    clients decrypted must be what the engine answered. Then the
    program's own account of the window, from every process."""
    records, errors = [], []
    for proc in state["children"]:
        line = proc.stdout.readline()
        if line:
            report = json.loads(line)
            records += report["records"]
            errors += report["errors"]
            state["cpu_s"].append(report["cpu_s"])
        else:
            errors.append("a client child died without its report")
        proc.stdin.close()
        proc.wait(timeout=30)
    engine: collections.Counter = collections.Counter()
    for e in ctx.log.rounds(state["first_round"]):
        if e["resps"] is not None:
            # kept on the round: ``end_to_end`` pairs them with the
            # oracle's verdicts without hashing 85 MB of answers again
            e["digests"] = [digest(r.pack()) for r in e["resps"]]
            engine.update(e["digests"])
    client = collections.Counter(d for _, d in records)
    differing = (sum((client - engine).values())
                 + sum((engine - client).values()))
    registry = ctx.server.metrics_registry
    tier = {"frontends": [_scrape(fe["metrics_port"])
                          for fe in state["frontends"]],
            "engine": {name: registry.get(name).get()
                       for name in ENGINE_SERIES if registry.get(name)},
            "children_cpu_s": state["cpu_s"], "window_s": ctx.seconds}
    t_end = state["t_start"] + ctx.seconds
    eng = tier["engine"]
    return {"attempted": len(records) + len(errors),
            "unanswered": len(errors), "client_mismatch": differing,
            # the window's end on the children's clock (time.monotonic)
            "records": records, "window_end": t_end, "tier": tier,
            "summary": {
                "client_ops": len(records),
                "client_ops_in_window": sum(t <= t_end for t, _ in records),
                "client_errors": errors[:3],
                "children_cpu_share": [c / ctx.seconds
                                       for c in state["cpu_s"]],
                "frontends_cpu_share": state["host_cpu_share"][:-1],
                "engine_process_cpu_share": state["host_cpu_share"][-1],
                "engine_submit_batches":
                    eng.get("grapevine_engine_submit_batches_total"),
                "engine_submit_ops":
                    eng.get("grapevine_engine_submit_ops_total")}}


def stop(ctx, state) -> None:
    for proc in state["children"]:
        if proc.poll() is None:
            proc.kill()
        proc.wait()
        for pipe in (proc.stdin, proc.stdout):
            if pipe and not pipe.closed:
                pipe.close()
    for fe in state["frontends"]:
        proc = fe["proc"]
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)  # the CLI's drain handler
            try:
                proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
        proc.stdout.close()
        fe["err"].close()
    listener = getattr(ctx.server, "engine_listener", None)
    if listener is not None:
        listener.stop()


def end_to_end(ctx, obs: dict) -> dict:
    """``ops_per_s``: answers that reached a client inside the window
    and that the oracle agrees with, over the window's seconds. An
    answer is matched to the engine's by its digest; the oracle's
    verdict on each engine answer is the replay's (``ok``)."""
    agreed = collections.Counter(
        d for e in obs["all_rounds"]
        for d, ok in zip(e.get("digests", []), e.get("ok", [])) if ok)
    t_open, t_close = obs["window"]
    t_end = obs["observed"]["window_end"]
    good = 0
    for t, d in obs["observed"]["records"]:
        if t <= t_end and agreed[d] > 0:
            agreed[d] -= 1
            good += 1
    return {"ops_per_s": good / (t_close - t_open)}

