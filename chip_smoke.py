#!/usr/bin/env python3
"""chip_smoke.py — the one command that proves the served bus runs on a TPU.

    python chip_smoke.py [--seed N]          # one chip (what the driver runs)
    python chip_smoke.py --four-chips        # the sharded path, and only it

One process owns the chip(s) from start to end. It refuses to start
unless JAX's first device is a TPU (exit 2, nothing built, no result
line), and it never sets or reads a platform override. Then, on one
chip:

1. builds the native session library from ``grapevine_tpu/native/r255.c``
   as committed (any ``_r255.so`` already there is removed first);
2. starts the same ``GrapevineServer`` that ``python -m
   grapevine_tpu.server.cli`` builds, at the bench headline geometry
   (2^20 messages, 2^12 recipients, B=2048, density 2, 1 KiB records,
   ChaCha8 trees, every other knob at the default the code resolves on
   a TPU), on ``insecure-grapevine://127.0.0.1:0``;
3. drives it with seeded traffic (a) through gRPC — 16 JAX-free
   ``GrapevineClient`` threads, Auth then signed CRUD — and (b) through
   the server's scheduler in-process, the way ``--role engine`` receives
   ops from its frontends: full rounds of 2048 signed ops,
   batch-verified; one recipient is pushed into the 62-message cap;
   then one expiry sweep that expires part of what (b) created;
4. replays every round, in the order and slot composition the engine
   saw, on the plain oracle (``testing/reference.py``) and requires
   identical statuses and records op for op, and zero stash overflow;
5. kernel phase: a few rounds through ``bucket_cipher_impl="pallas"``
   at 2^16 messages / B=256, Mosaic-compiled (``tpu_custom_call`` in the
   compiled text), state bit-identical to ``jnp`` rounds on the same ops;
   then the write-back's row-placement kernel alone against the jnp
   scatter at the mailbox row's width (PR 46).

``--four-chips`` runs steps 2-4 with ``shards=4`` at 2^22 messages (one
chip's share stays 2^20) and checks that every device holds a quarter
of both trees; it runs no other phase.

Every line of stdout is one JSON object; the last is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
and appears only if every phase passed. Anything else exits non-zero.
"""

from __future__ import annotations

import argparse
import gc
import hashlib
import json
import os
import random
import sys
import threading
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
N_CLIENTS = 16
OPS_PER_CLIENT = 20
N_IDENTITIES = 256
HOT_CREATES = 70  # > the 62-message mailbox cap


class SmokeFailure(Exception):
    """A phase's check failed; the run exits non-zero."""


def say(**kv) -> None:
    print(json.dumps(kv), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# -- phase: native library ---------------------------------------------


def build_native() -> None:
    so = os.path.join(HERE, "grapevine_tpu", "native", "_r255.so")
    if os.path.exists(so):
        os.unlink(so)  # a binary built elsewhere proves nothing here
    t0 = time.perf_counter()
    from grapevine_tpu import native

    check(native.lib is not None,
          f"native library did not build/load: {native.load_error}")
    from grapevine_tpu.session.channel import CRYPTO_BACKEND

    say(phase="native", ok=True, build_s=round(time.perf_counter() - t0, 2),
        session_crypto_backend=CRYPTO_BACKEND)


# -- seeded traffic -----------------------------------------------------


class SmokeClock:
    """The server's clock (GrapevineServer ``clock=``): real time at
    start, advanced by the smoke between waves so that record
    timestamps — and therefore what the sweep expires — are a function
    of the seed, not of how fast this machine ran."""

    def __init__(self):
        self.t = int(time.time())

    def __call__(self) -> int:
        return self.t


def make_identities(seed: int, n: int):
    from grapevine_tpu.session import get_signature_scheme

    scheme = get_signature_scheme("schnorrkel")
    out = []
    for i in range(n):
        s = hashlib.sha256(f"chip-smoke-{seed}-{i}".encode()).digest()
        out.append((s, *scheme.keygen(s)))  # (seed, sk, pub)
    return scheme, out


def payload_of(rng: random.Random) -> bytes:
    from grapevine_tpu.wire import constants as C

    return rng.randbytes(C.PAYLOAD_SIZE)


class RoundLog:
    """Observes the scheduler→engine boundary: every round's requests in
    slot order, its clock and its responses — what the oracle replay
    needs, since round composition under concurrent clients is decided
    by the scheduler, not by the seed — and the host-clock seconds from
    the round's dispatch to its resolved answers (``round_s``; this
    includes waiting behind an earlier round still in flight)."""

    class _Pending:
        def __init__(self, inner, entry):
            self._inner, self._entry = inner, entry

        def __getattr__(self, name):
            return getattr(self._inner, name)

        def resolve(self):
            self._entry["resps"] = self._inner.resolve()
            self._entry["round_s"] = time.perf_counter() - self._entry["t0"]
            return self._entry["resps"]

    def __init__(self, engine):
        #: rounds, and the sweeps between them, in the engine's order
        self.entries: list[dict] = []
        inner = engine.handle_queries_async

        def recorded(reqs, now):
            entry = {"kind": "round", "reqs": list(reqs), "now": int(now),
                     "resps": None, "t0": time.perf_counter()}
            pending = inner(reqs, now)
            self.entries.append(entry)
            return self._Pending(pending, entry)

        engine.handle_queries_async = recorded


def client_script(uri, static, ident_seed, peers, hot, rng, out, errors):
    """One gRPC client: Auth, then OPS_PER_CLIENT signed ops, each
    waiting for its answer (so these rounds are under-full)."""
    from grapevine_tpu.server.client import GrapevineClient
    from grapevine_tpu.wire import constants as C

    try:
        cl = GrapevineClient(uri, ident_seed, server_static=static)
        cl.auth()
        mine: list[tuple[bytes, bytes]] = []  # (msg_id, recipient)
        for k in range(OPS_PER_CLIENT):
            c = rng.random()
            if k < 3 or c < 0.30 or not mine:
                rcp = hot if c > 0.9 else rng.choice(peers)
                r = cl.create(rcp, payload_of(rng))
                if r.status_code == C.STATUS_CODE_SUCCESS:
                    mine.append((r.record.msg_id, rcp))
            elif c < 0.45:
                r = cl.read(rng.choice(mine)[0])
            elif c < 0.60:
                r = cl.read()  # zero id: my next message
            elif c < 0.75:
                mid, rcp = rng.choice(mine)
                r = cl.update(mid, rcp, payload_of(rng))
            elif c < 0.90:
                mid, rcp = mine.pop(rng.randrange(len(mine)))
                r = cl.delete(mid, rcp)
            else:
                r = cl.delete()  # zero id: pop my next message
            out.append(r)
        cl.close()
    except Exception:  # noqa: BLE001 — reported, fails the phase
        errors.append(traceback.format_exc())


def drive_grpc(port, server, idents, seed) -> list:
    uri = f"insecure-grapevine://127.0.0.1:{port}"
    hot = idents[0][2]
    peers = [pub for _, _, pub in idents[1:N_CLIENTS + 1]]
    got: list = []
    errors: list[str] = []
    threads = [
        threading.Thread(
            target=client_script,
            args=(uri, server.identity.public, idents[1 + i][0], peers, hot,
                  random.Random(f"{seed}-client-{i}"), got, errors),
        )
        for i in range(N_CLIENTS)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    check(not errors, "gRPC client failed:\n" + "\n".join(errors))
    check(len(got) == N_CLIENTS * OPS_PER_CLIENT, "gRPC ops went missing")
    return got


class SchedulerTraffic:
    """Seeded op waves submitted straight to the server's scheduler,
    each op signed over a fresh challenge and verified in the round's
    batch verification — the path a ``--role engine`` tier serves."""

    def __init__(self, scheme, idents, rng):
        self.scheme, self.idents, self.rng = scheme, idents, rng
        self.live: list[tuple[bytes, int, int]] = []  # (id, sender, rcp)
        self.dead: list[tuple[bytes, int, int]] = []
        #: CREATEs still to aim at identity 0, pushing it into the cap
        self.hot_left = HOT_CREATES

    def req(self, rt, who, **record):
        """One signed op: (QueryRequest, the scheduler's AuthItem)."""
        from grapevine_tpu.wire import constants as C
        from grapevine_tpu.wire.records import QueryRequest, RequestRecord

        _, sk, pub = self.idents[who]
        challenge = self.rng.randbytes(32)
        ctx = C.GRAPEVINE_CHALLENGE_SIGNING_CONTEXT
        sig = self.scheme.sign(sk, ctx, challenge)
        req = QueryRequest(
            request_type=rt, auth_identity=pub, auth_signature=sig,
            record=RequestRecord(**record),
        )
        return req, (pub, ctx, challenge, sig)

    def wave(self, n: int, reads_only: bool = False) -> list:
        from grapevine_tpu.wire import constants as C

        rng, n_id = self.rng, len(self.idents)
        pub = lambda i: self.idents[i][2]  # noqa: E731
        ops = []
        # ids this wave may name: live ones, and some already deleted
        known = self.live + self.dead[-64:]
        for _ in range(n):
            c = rng.random()
            if reads_only and known:
                mid, snd, rcp = rng.choice(known)
                ops.append(self.req(C.REQUEST_TYPE_READ, snd, msg_id=mid))
            elif self.hot_left:
                self.hot_left -= 1
                ops.append(self.req(
                    C.REQUEST_TYPE_CREATE, rng.randrange(1, n_id),
                    recipient=pub(0), payload=payload_of(rng)))
            elif c < 0.40 or not known:
                ops.append(self.req(
                    C.REQUEST_TYPE_CREATE, rng.randrange(n_id),
                    recipient=pub(rng.randrange(n_id)),
                    payload=payload_of(rng)))
            elif c < 0.55:
                mid, snd, rcp = rng.choice(known)
                who = rng.choice([snd, rcp, rng.randrange(n_id)])
                ops.append(self.req(C.REQUEST_TYPE_READ, who, msg_id=mid))
            elif c < 0.65:
                ops.append(self.req(C.REQUEST_TYPE_READ, rng.randrange(n_id)))
            elif c < 0.78:
                mid, snd, rcp = rng.choice(known)
                ops.append(self.req(
                    C.REQUEST_TYPE_UPDATE, rng.choice([snd, rcp]),
                    msg_id=mid, recipient=pub(rcp), payload=payload_of(rng)))
            elif c < 0.90:
                mid, snd, rcp = rng.choice(known)
                who = rng.choice([snd, rcp, rng.randrange(n_id)])
                ops.append(self.req(
                    C.REQUEST_TYPE_DELETE, who, msg_id=mid,
                    recipient=pub(rcp)))
            else:
                ops.append(self.req(
                    C.REQUEST_TYPE_DELETE, rng.randrange(n_id)))
        return ops

    def learn(self, ops, resps) -> None:
        from grapevine_tpu.wire import constants as C

        index = {p: i for i, (_, _, p) in enumerate(self.idents)}
        for (req, _), resp in zip(ops, resps):
            if resp.status_code != C.STATUS_CODE_SUCCESS:
                continue
            rec = resp.record
            entry = (rec.msg_id, index[rec.sender], index[rec.recipient])
            if req.request_type == C.REQUEST_TYPE_CREATE:
                self.live.append(entry)
            elif req.request_type == C.REQUEST_TYPE_DELETE:
                self.live = [e for e in self.live if e[0] != rec.msg_id]
                self.dead.append(entry)


def round_seconds(rounds) -> dict:
    """Dispatch-to-resolved seconds of a phase's rounds, in order."""
    xs = [round(e["round_s"], 3) for e in rounds]
    return {"first": xs[0], "median": sorted(xs)[len(xs) // 2],
            "last": xs[-1], "max": max(xs)}


def submit_wave(scheduler, ops) -> tuple[list, float]:
    """Enqueue a whole wave without pausing (a pause longer than the
    scheduler's idle gap would close the collection window early), then
    wait for every answer."""
    gc.disable()
    try:
        t0 = time.perf_counter()
        futs = [scheduler.submit_nowait(req, auth) for req, auth in ops]
    finally:
        gc.enable()
    resps = [f.result(timeout=600) for f in futs]
    return resps, time.perf_counter() - t0


# -- oracle replay ------------------------------------------------------


def replay_on_oracle(cfg, log: RoundLog) -> dict:
    """Replay every recorded round (and sweep) on the plain oracle and
    require identical statuses and records, op for op. Message ids are
    engine-private PRP outputs: the oracle is handed the id the engine
    returned for each successful CREATE and everything else — which
    record an id names, who may see it, zero-id order, caps, expiry —
    must then agree."""
    from grapevine_tpu.testing.reference import ReferenceEngine
    from grapevine_tpu.wire import constants as C

    oracle = ReferenceEngine(config=cfg, rng=random.Random(0))
    compared = 0
    statuses: dict[int, int] = {}
    for i, e in enumerate(log.entries):
        if e["kind"] == "sweep":
            n = oracle.expire(e["now"], e["period"])
            check(n == e["evicted"],
                  f"sweep evicted {e['evicted']} on the engine, {n} on the "
                  "oracle")
            continue
        check(e["resps"] is not None, f"round {i} never resolved")
        forced = [
            d.record.msg_id
            if r.request_type == C.REQUEST_TYPE_CREATE
            and d.status_code == C.STATUS_CODE_SUCCESS else None
            for r, d in zip(e["reqs"], e["resps"])
        ]
        ora = oracle.handle_batch(e["reqs"], e["now"], forced)
        for j, (r, d, o) in enumerate(zip(e["reqs"], e["resps"], ora)):
            same = (
                d.status_code == o.status_code
                and d.record.msg_id == o.record.msg_id
                and d.record.sender == o.record.sender
                and d.record.recipient == o.record.recipient
                and d.record.payload == o.record.payload
                and d.record.timestamp == o.record.timestamp
            )
            check(same, f"round {i} slot {j} (request type "
                  f"{r.request_type}): engine status {d.status_code}, "
                  f"oracle {o.status_code}; records "
                  f"{'equal' if d.record == o.record else 'differ'}")
            statuses[d.status_code] = statuses.get(d.status_code, 0) + 1
            compared += 1
    return {"ops_compared": compared,
            "status_counts": {str(k): v for k, v in sorted(statuses.items())},
            "oracle_messages": oracle.message_count(),
            "oracle_recipients": oracle.recipient_count()}


# -- phase: the served path ---------------------------------------------


def serve_and_compare(cfg, seed: int, label: str) -> None:
    import jax

    from grapevine_tpu.server.service import GrapevineServer
    from grapevine_tpu.wire import constants as C

    bs = cfg.batch_size
    clock = SmokeClock()
    t0 = time.perf_counter()
    server = GrapevineServer(cfg, seed=seed, clock=clock, max_wait_ms=2000.0)
    engine = server.engine
    jax.block_until_ready(engine.state)
    leaves = jax.tree.leaves(engine.state)
    per_device: dict[str, int] = {}
    for leaf in leaves:
        for sh in leaf.addressable_shards:
            per_device[str(sh.device)] = (
                per_device.get(str(sh.device), 0) + sh.data.nbytes
            )
    say(phase=f"{label}.init", ok=True,
        init_s=round(time.perf_counter() - t0, 2),
        geometry={"max_messages": cfg.max_messages,
                  "max_recipients": cfg.max_recipients,
                  "batch_size": bs, "tree_density": cfg.tree_density,
                  "shards": cfg.shards, "record_bytes": C.RECORD_SIZE,
                  "bucket_cipher": f"chacha{cfg.bucket_cipher_rounds}",
                  "bucket_cipher_impl": cfg.bucket_cipher_impl},
        resolved={"vphases_impl": engine.ecfg.vphases_impl,
                  "sort_impl": engine.ecfg.sort_impl,
                  "tree_top_cache_levels": engine.ecfg.tree_top_cache_levels,
                  "pipeline_depth": engine.pipeline_depth},
        state_bytes=sum(x.nbytes for x in leaves),
        state_bytes_per_device=per_device)
    if cfg.shards > 1:
        check(jax.device_count() == cfg.shards,
              f"want {cfg.shards} devices, have {jax.device_count()}")
        for tree in (engine.state.rec, engine.state.mb):
            shards = tree.tree_val.addressable_shards
            check(len(shards) == cfg.shards
                  and len({str(s.device) for s in shards}) == cfg.shards
                  and all(s.data.shape[0] * cfg.shards
                          == tree.tree_val.shape[0] for s in shards),
                  "tree_val is not split in equal quarters over the devices")
    log = RoundLog(engine)
    port = server.start("insecure-grapevine://127.0.0.1:0")
    try:
        scheme, idents = make_identities(seed, N_IDENTITIES)
        traffic = SchedulerTraffic(
            scheme, idents, random.Random(f"{seed}-sched"))

        # first round: one signed op through the scheduler; compiles
        t0 = time.perf_counter()
        warm = [traffic.req(C.REQUEST_TYPE_READ, 1)]
        submit_wave(server.scheduler, warm)
        compile_s = time.perf_counter() - t0
        say(phase=f"{label}.first_round", ok=True,
            compile_and_run_s=round(compile_s, 2),
            memory_stats=_memory_stats())

        # (a) gRPC: Auth + signed CRUD from JAX-free client threads
        t0 = time.perf_counter()
        r0 = len(log.entries)
        got = drive_grpc(port, server, idents, seed)
        grpc_rounds = log.entries[r0:]
        seen = sorted(r.pack() for e in grpc_rounds for r in e["resps"])
        check(seen == sorted(r.pack() for r in got),
              "what the gRPC clients decrypted is not what the engine "
              "answered")
        say(phase=f"{label}.grpc", ok=True,
            run_s=round(time.perf_counter() - t0, 2), clients=N_CLIENTS,
            ops=len(got), rounds=len(grpc_rounds),
            mean_batch_fill=round(len(got) / len(grpc_rounds) / bs, 4),
            round_s=round_seconds(grpc_rounds))
        clock.t += 100

        # (b) scheduler in-process: full rounds of bs signed ops
        t0 = time.perf_counter()
        r0 = len(log.entries)
        full, waves, wave_s, sweep_from = 0, 0, [], None
        while full < 4 and waves < 8:
            if waves == 2:
                sweep_from = clock.t  # earlier records are swept below
            ops = traffic.wave(bs)
            resps, dt = submit_wave(server.scheduler, ops)
            traffic.learn(ops, resps)
            wave_s.append(round(dt, 3))
            waves += 1
            clock.t += 100
            full = sum(len(e["reqs"]) == bs for e in log.entries[r0:])
        sched_rounds = log.entries[r0:]
        n_ops = sum(len(e["reqs"]) for e in sched_rounds)
        check(full >= 4, f"only {full} full rounds of {bs} in {waves} waves")
        cap_hits = sum(
            r.status_code == C.STATUS_CODE_TOO_MANY_MESSAGES_FOR_RECIPIENT
            for e in sched_rounds for r in e["resps"])
        check(cap_hits >= HOT_CREATES - C.MAILBOX_CAP,
              f"the {C.MAILBOX_CAP}-message cap was hit {cap_hits} times")
        say(phase=f"{label}.scheduler", ok=True,
            run_s=round(time.perf_counter() - t0, 2), ops=n_ops,
            rounds=len(sched_rounds), full_rounds=full,
            mean_batch_fill=round(n_ops / len(sched_rounds) / bs, 4),
            wave_s=wave_s, round_s=round_seconds(sched_rounds),
            mailbox_cap_refusals=cap_hits)

        # one expiry sweep: expires what was created before wave 3
        t0 = time.perf_counter()
        sweep = {"kind": "sweep", "now": clock.t,
                 "period": clock.t - sweep_from}
        before = engine.message_count()
        sweep["evicted"] = engine.expire(sweep["now"], sweep["period"])
        log.entries.append(sweep)
        check(0 < sweep["evicted"] < before,
              f"the sweep expired {sweep['evicted']} of {before} records")
        say(phase=f"{label}.sweep", ok=True,
            compile_and_run_s=round(time.perf_counter() - t0, 2),
            evicted=sweep["evicted"], of=before)
        # reads across the sweep line: expired ids must now be NOT_FOUND
        clock.t += 100
        ops = traffic.wave(min(bs, 512), reads_only=True)
        submit_wave(server.scheduler, ops)

        # the oracle
        t0 = time.perf_counter()
        rep = replay_on_oracle(cfg, log)
        health = engine.health()
        check(health["messages"] == rep["oracle_messages"]
              and health["recipients"] == rep["oracle_recipients"],
              f"engine holds {health['messages']} messages / "
              f"{health['recipients']} recipients, oracle "
              f"{rep['oracle_messages']} / {rep['oracle_recipients']}")
        overflow = {"rec": int(engine.state.rec.overflow),
                    "mb": int(engine.state.mb.overflow)}
        check(not any(overflow.values()), f"stash overflow: {overflow}")
        say(phase=f"{label}.oracle", ok=True,
            replay_s=round(time.perf_counter() - t0, 2),
            rounds_run=len(log.entries) - 1, stash_overflow=overflow,
            stash_occupancy=health["stash_occupancy"],
            stash_size=cfg.stash_size, memory_stats=_memory_stats(), **rep)
    finally:
        server.stop()


def _memory_stats() -> dict:
    import jax

    out = {}
    for d in jax.devices():
        ms = d.memory_stats() or {}
        out[str(d)] = {k: ms[k] for k in
                       ("peak_bytes_in_use", "bytes_in_use", "bytes_limit")
                       if k in ms}
    return out


# -- phase: Pallas kernels vs jnp ---------------------------------------


def kernel_phase(seed: int, cap: int = 1 << 16, batch: int = 256,
                 rounds: int = 4) -> None:
    import jax
    import numpy as np

    from grapevine_tpu.config import GrapevineConfig, on_tpu
    from grapevine_tpu.engine.batcher import pack_batch, unpack_responses
    from grapevine_tpu.engine.round_step import engine_round_step
    from grapevine_tpu.engine.state import EngineConfig, init_engine
    from grapevine_tpu.testing.compare import states_equal
    from grapevine_tpu.wire import constants as C

    scheme, idents = make_identities(seed, 32)
    traffic = SchedulerTraffic(scheme, idents, random.Random(f"{seed}-kern"))
    results = {}
    script: list = []  # the jnp run decides the ops; pallas repeats them
    for impl in ("jnp", "pallas"):
        cfg = GrapevineConfig(
            max_messages=cap, max_recipients=1 << 10, batch_size=batch,
            tree_density=2, bucket_cipher_impl=impl,
        )
        ecfg = EngineConfig.from_config(cfg)
        state = init_engine(ecfg, seed)
        t0 = time.perf_counter()
        first = pack_batch([], batch, 1)
        compiled = jax.jit(
            engine_round_step, static_argnums=(0,), donate_argnums=(1,)
        ).lower(ecfg, state, first).compile()
        compile_s = time.perf_counter() - t0
        calls = [line for line in compiled.as_text().split("\n")
                 if 'custom_call_target="tpu_custom_call"' in line]
        # the write-back's row placements (PR 46) are Mosaic kernels
        # under either cipher impl: three a round, one a tree pass
        n_placed = sum("place_rows" in line for line in calls)
        n_kernels = len(calls) - n_placed
        # off the chip (a rehearsal importing this function) the kernels
        # run interpreted and there is nothing Mosaic to count
        check(not on_tpu() or (n_kernels > 0) == (impl != "jnp"),
              f"{impl}: {n_kernels} Mosaic cipher kernels in the compiled "
              "round")
        check(not on_tpu() or n_placed == 3,
              f"{impl}: {n_placed} placement kernels in the compiled round")
        t0 = time.perf_counter()
        outs = []
        for k in range(rounds):
            if impl == "jnp":
                ops = traffic.wave(batch)
                script.append(ops)
            reqs = [r for r, _ in script[k]]
            state, resp, _ = compiled(
                state, pack_batch(reqs, batch, 1_700_000_000 + k))
            resp = {key: np.asarray(v) for key, v in resp.items()}
            outs.append(resp)
            if impl == "jnp":
                traffic.learn(script[k], unpack_responses(resp, batch))
        jax.block_until_ready(state)
        results[impl] = (outs, state)
        ok_ops = int(sum((o["status"] == C.STATUS_CODE_SUCCESS).sum()
                         for o in outs))
        say(phase=f"kernels.{impl}", ok=True, compile_s=round(compile_s, 2),
            run_s=round(time.perf_counter() - t0, 3), rounds=rounds,
            mosaic_kernels_in_round=n_kernels,
            placement_kernels_in_round=n_placed, interpret=not on_tpu(),
            successful_ops=ok_ops, capacity_log2=cap.bit_length() - 1,
            batch=batch)
    ref_outs, ref_state = results["jnp"]
    outs, state = results["pallas"]
    for k, (a, b) in enumerate(zip(ref_outs, outs)):
        for key in a:
            check(np.array_equal(a[key], b[key]),
                  f"pallas: round {k} response field {key} differs "
                  "from jnp")
    same, where = states_equal(ref_state, state)
    check(same, f"pallas: state differs from jnp at {where}")
    say(phase="kernels.compare", ok=True, bit_identical_to_jnp=["pallas"])


def placement_phase(seed: int, n: int = 1 << 13, tiles: int = 48,
                    n_dense: int = 1008, n_paths: int = 1536) -> None:
    """The write-back's row-placement kernel (oblivious/pallas_place.py)
    against the jnp scatter it stands in for, at the mailbox row's width
    (48 lane tiles, 24 KB): a dense range first, then per-path rows at
    unique targets, a tenth of them dropped; same plane bit for bit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from grapevine_tpu.config import on_tpu
    from grapevine_tpu.oblivious.pallas_place import place_rows

    rng = np.random.default_rng(seed)
    sparse = rng.permutation(np.arange(15 + n_dense, n))[:n_paths]
    tgt = np.concatenate([np.arange(15, 15 + n_dense), sparse])
    tgt[n_dense:][rng.random(n_paths) < 0.1] = n  # not owned: no copy
    tgt = jnp.asarray(tgt.astype(np.int32))
    k_plane, k_rows = jax.random.split(jax.random.key(seed))
    plane = jax.random.bits(k_plane, (n, tiles, 128), jnp.uint32)
    rows = jax.random.bits(k_rows, (tgt.shape[0], tiles, 128), jnp.uint32)
    scatter = jax.jit(
        lambda p, t, v: p.at[t].set(v, mode="drop", unique_indices=True),
        donate_argnums=(0,))
    place = jax.jit(
        lambda p, t, v: place_rows(p, t, v, interpret=not on_tpu()),
        donate_argnums=(0,))
    n_kernels = place.lower(plane, tgt, rows).compile().as_text().count(
        "tpu_custom_call")
    check(not on_tpu() or n_kernels == 1,
          f"placement: {n_kernels} Mosaic kernels in the compiled call")

    def timed(fn, calls: int = 5):
        out = jax.block_until_ready(fn(jnp.copy(plane), tgt, rows))
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(out, tgt, rows)
        jax.block_until_ready(out)
        return out, (time.perf_counter() - t0) / calls * 1e3

    want, scatter_ms = timed(scatter)
    got, place_ms = timed(place)
    check(bool(jnp.array_equal(got, want)),
          "placement: the kernel's plane differs from the jnp scatter's")
    written = np.asarray(tgt)[np.asarray(tgt) < n]
    kept = np.setdiff1d(np.arange(n), written)[:256]
    check(bool(jnp.array_equal(got[kept], plane[kept])),
          "placement: a row no copy targets changed")
    say(phase="kernels.placement", ok=True, plane_rows=n, row_words=tiles * 128,
        rows=int(tgt.shape[0]), rows_written=int(written.shape[0]),
        place_ms=round(place_ms, 3), jnp_scatter_ms=round(scatter_ms, 3),
        mosaic_kernels=n_kernels, interpret=not on_tpu())


# -- main ----------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=22)
    ap.add_argument("--four-chips", action="store_true",
                    help="run the shards=4 path at 2^22 messages, and "
                    "only it")
    args = ap.parse_args()

    import jax

    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU — JAX's first device is {dev}; "
              "nothing was built or run", file=sys.stderr)
        return 2
    t_start = time.perf_counter()
    sys.path.insert(0, HERE)
    try:
        from grapevine_tpu.config import GrapevineConfig, setup_compile_cache

        cache = setup_compile_cache()
        entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
        say(phase="start", device=dev, seed=args.seed, jax=jax.__version__,
            compile_cache=cache, compile_cache_entries=entries)
        build_native()
        if args.four_chips:
            check(dev["count"] == 4, f"--four-chips on {dev['count']} chip(s)")
            serve_and_compare(GrapevineConfig(
                max_messages=1 << 22, max_recipients=1 << 12,
                batch_size=2048, tree_density=2, shards=4,
            ), args.seed, "served4")
        else:
            serve_and_compare(GrapevineConfig(
                max_messages=1 << 20, max_recipients=1 << 12,
                batch_size=2048, tree_density=2,
            ), args.seed, "served")
            gc.collect()  # the server's 4.4 GB of trees, before the next
            kernel_phase(args.seed)
            placement_phase(args.seed)
        say(phase="done", total_s=round(time.perf_counter() - t_start, 1),
            compile_cache_entries=len(os.listdir(cache))
            if os.path.isdir(cache) else 0)
    except Exception:  # noqa: BLE001 — any failure in any phase
        traceback.print_exc()
        print("chip_smoke: FAILED (no result line)", file=sys.stderr)
        return 1
    say(ok=True, device=dev)
    return 0


if __name__ == "__main__":
    sys.exit(main())
