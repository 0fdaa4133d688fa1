#!/usr/bin/env python3
"""The BASELINE benchmark configs, with p99 round latency.

Configs (BASELINE.md / BASELINE.json, plus two extensions):
  1. crd_loop            single-client create→read→delete loop, 2^16 bus
  2. batched_read        2048 concurrent explicit-id reads, 2^20 bus
  3. zipf_mixed          mixed CRUD, Zipf recipient keys, 62-cap stress
  3b. zipf_pallas_cipher the same workload through the fused Pallas
                         cipher kernel
  4. expiry_sweep        timestamped eviction scan, 2^22 at density 4
  4d. posmap_ab          flat vs recursive position map A/B — lookup
                         machinery (B × capacity grid, with the
                         private/HBM memory split) + whole-round
                         B-sweep, interleaved (PR7; PERF.md Round 9)
  4e. tree_cache_ab      tree-top cache A/B — isolated ORAM-round
                         machinery (cap × B × k grid) + whole-round
                         k ∈ {0,2,4,auto} B-sweep, interleaved
                         (PR8; PERF.md Round 10)
  5. sharded             bucket-tree sharded over a device mesh (left
                         out, and named on stderr, with < 2 devices)
  6. server_loopback     full-stack gRPC: session crypto + batched
                         verification + pipelined scheduler + engine
                         (skipped, not errored, without `cryptography`)
  7. slo_loopback        scheduler loopback with the observability
                         stack on (round tracer + commit-latency SLO,
                         PR6): enqueue→settle latency, burn rates, and
                         the host/device bubble ratio — runs everywhere
                         (no session crypto in the loop)
  7b. pipeline_ab        round-pipeline depth A/B (PR10): depth 1
                         (serial) vs depth 2 (collection window +
                         journal fsync overlap the in-flight device
                         rounds) through the scheduler with fsync ON —
                         sustained throughput + commit p99 per depth,
                         min-of-N interleaved; runs everywhere
  8. load_scenarios      the workload observatory (PR9): open-loop
                         scenario suite (steady/bursty/diurnal/
                         pop-heavy/adversarial/ramp) through the
                         scheduler with workload telemetry + leakmon
                         on — per-scenario commit p50/p99/fill/depth,
                         adversarial-vs-honest /leakaudit verdicts,
                         and the ramp's measured saturation knee (the
                         banked capacity number) — runs everywhere
  9. fleet_loopback      the fleet observatory (PR16): TWO engines
                         behind a recipient-partitioned ramp replayed
                         concurrently (ShardedScenarioRunner) with a
                         live in-process FleetAggregator scraping both
                         registries on its fixed cadence — per-shard
                         knees, the folded fleet knee (geometry key
                         shard_count=2), merged-view liveness, and the
                         cross-shard uniformity verdict (must PASS:
                         the production scheduler is uniform) — runs
                         everywhere

stdout is ONE JSON line: the headline mixed-CRUD throughput at the
largest batched config, with every config's (ops/s, p99 round ms)
embedded under "configs". Per-config progress lines go to stderr.

``--smoke`` pins the CPU (eight virtual devices, as the tests do) and
runs every config at toy sizes to assert the harness itself works; its
numbers are never device metrics. Without ``--smoke`` the bench
measures the chip: it refuses to start unless JAX's first device is a
TPU, and it exits non-zero if any config raised. One process owns the
chip for the whole run — no config starts a JAX child.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

NOW = 1_700_000_000


def _p99(times_s: list[float]) -> float:
    return float(np.percentile(np.asarray(times_s) * 1e3, 99))


def _mk_engine(cap, recips, batch, stash=None, seed=0, density=2, cipher_impl="jnp",
               cipher_rounds=8, mailbox_cap=None,
               posmap_impl=None, tree_top_cache=None):
    import jax

    from grapevine_tpu.config import GrapevineConfig
    from grapevine_tpu.engine.round_step import engine_round_step
    from grapevine_tpu.engine.state import EngineConfig, init_engine

    extra = {} if mailbox_cap is None else {"mailbox_cap": mailbox_cap}
    cfg = GrapevineConfig(
        max_messages=cap,
        max_recipients=recips,
        batch_size=batch,
        stash_size=stash or max(128, batch // 2 + 96),
        tree_density=density,
        bucket_cipher_impl=cipher_impl,
        bucket_cipher_rounds=cipher_rounds,
        posmap_impl=posmap_impl,
        tree_top_cache_levels=tree_top_cache,
        **extra,
    )
    ecfg = EngineConfig.from_config(cfg)
    state = init_engine(ecfg, seed=seed)
    step = jax.jit(engine_round_step, static_argnums=(0,), donate_argnums=(1,))
    return cfg, ecfg, state, step


def _run_rounds(ecfg, state, step, batches, n_rounds):
    """Two measurements over the same round stream:

    - **throughput** from scan-fused rounds: the batch stream is staged
      on device once and ``lax.scan`` chains the rounds inside one jit,
      so the metric is the engine's own round rate — not the host's
      dispatch/transfer path, which the production scheduler overlaps
      with compute anyway (scheduler.py collects while the device runs);
    - **p99 latency** from individually dispatched, blocking rounds —
      the latency a serving round actually pays, host boundary included.
    """
    import jax
    import jax.numpy as jnp

    state, resp, _ = step(ecfg, state, batches[0])
    jax.block_until_ready(resp)  # warmup: compile + settle

    # p99 from per-dispatch rounds
    times = []
    for i in range(min(n_rounds, 32)):
        t0 = time.perf_counter()
        state, resp, _ = step(ecfg, state, batches[i % len(batches)])
        jax.block_until_ready(resp)
        times.append(time.perf_counter() - t0)

    # throughput from fused rounds: stack the batch stream, scan it
    from grapevine_tpu.engine.round_step import engine_round_step

    # rounds per dispatch: scan compiles its body once regardless of
    # length, so a longer chain costs no compile time and amortizes the
    # per-dispatch overhead further
    n_fused = max(16, len(batches))
    order = [i % len(batches) for i in range(n_fused)]
    stacked = {
        k: (jnp.stack([jnp.asarray(batches[i][k]) for i in order]) if k != "now"
            else jnp.asarray([batches[i]["now"] for i in order]))
        for k in batches[0]
    }
    stacked = jax.device_put(stacked)  # staged once, outside the timing

    def scan_rounds(state, xs):
        def body(st, batch):
            st2, resp, _ = engine_round_step(ecfg, st, batch)
            # responses stay on device; carry a cheap digest out so XLA
            # cannot elide any round's work
            return st2, resp["status"]
        return jax.lax.scan(body, state, xs)

    fused = jax.jit(scan_rounds, donate_argnums=(0,))
    state, statuses = fused(state, stacked)
    jax.block_until_ready(statuses)  # fused compile + settle
    n_loops = max(1, n_rounds // n_fused)
    t_all = time.perf_counter()
    for _ in range(n_loops):
        state, statuses = fused(state, stacked)
    jax.block_until_ready(statuses)
    total = time.perf_counter() - t_all
    rounds_run = n_loops * n_fused
    overflow = int(np.asarray(state.rec.overflow)) + int(np.asarray(state.mb.overflow))
    assert overflow == 0, f"stash overflow during bench: {overflow}"
    # scale `total` to what n_rounds rounds take, keeping callers' ops math
    return state, times, total * (n_rounds / rounds_run)


def _batch_arrays(reqs, ecfg):
    from grapevine_tpu.engine.state import ID_WORDS, KEY_WORDS, PAYLOAD_WORDS

    b = ecfg.batch_size
    out = {
        "req_type": np.zeros((b,), np.uint32),
        "auth": np.zeros((b, KEY_WORDS), np.uint32),
        "msg_id": np.zeros((b, ID_WORDS), np.uint32),
        "recipient": np.zeros((b, KEY_WORDS), np.uint32),
        "payload": np.zeros((b, PAYLOAD_WORDS), np.uint32),
        "now": np.uint32(NOW),
    }
    for i, (rt, auth, mid, rcp, pl) in enumerate(reqs):
        out["req_type"][i] = rt
        out["auth"][i] = auth
        out["msg_id"][i] = mid
        out["recipient"][i] = rcp
        out["payload"][i] = pl
    return out


def make_batches(n_batches: int, batch_size: int, seed: int = 7):
    """Create-heavy mixed CRUD batches (legacy helper, used by tests)."""
    from grapevine_tpu.engine.state import ID_WORDS, KEY_WORDS, PAYLOAD_WORDS

    rng = np.random.default_rng(seed)
    idents = rng.integers(1, 2**31, (64, KEY_WORDS)).astype(np.uint32)
    batches = []
    for _ in range(n_batches):
        b = batch_size
        rt = rng.choice(np.array([1, 1, 2, 2, 3, 4], np.uint32), size=b)
        auth = idents[rng.integers(0, len(idents), b)]
        recipient = idents[rng.integers(0, len(idents), b)]
        msg_id = np.zeros((b, ID_WORDS), np.uint32)
        explicit = rt == 3  # UPDATE needs nonzero id (grapevine.proto:95)
        msg_id[explicit] = rng.integers(1, 2**31, (int(explicit.sum()), ID_WORDS))
        batches.append(
            {
                "req_type": rt,
                "auth": auth,
                "msg_id": msg_id,
                "recipient": recipient,
                "payload": rng.integers(0, 2**31, (b, PAYLOAD_WORDS)).astype(np.uint32),
                "now": np.uint32(NOW),
            }
        )
    return batches


# ----------------------------------------------------------------------
# the five configs
# ----------------------------------------------------------------------


def bench_crd_loop(smoke):
    """Config 1: one client, create → zero-id read → zero-id delete."""
    # batch 64 (lane-aligned): 21 C-R-D triples + one padding dummy slot
    cap, batch, n_rounds = (1 << 10, 4, 4) if smoke else (1 << 16, 64, 32)
    cfg, ecfg, state, step = _mk_engine(cap, 1 << 8, batch)
    rng = np.random.default_rng(3)
    me = rng.integers(1, 2**31, (8,)).astype(np.uint32)
    pl = rng.integers(0, 2**31, (234,)).astype(np.uint32)
    zid = np.zeros((4,), np.uint32)
    # C,R,D triples in slot order — the per-batch form of the CRD loop
    reqs = []
    for _ in range(batch // 3):
        reqs += [(1, me, zid, me, pl), (2, me, zid, np.zeros(8, np.uint32), pl),
                 (4, me, zid, np.zeros(8, np.uint32), pl)]
    batches = [_batch_arrays(reqs, ecfg)]
    _, times, total = _run_rounds(ecfg, state, step, batches, n_rounds)
    ops = len(reqs) * n_rounds
    return {"ops_per_sec": round(ops / total, 1), "p99_round_ms": round(_p99(times), 2),
            "batch": batch, "capacity_log2": cap.bit_length() - 1}


def bench_batched_read(smoke):
    """Config 2: B concurrent explicit-id reads at 2^20."""
    cap, batch, n_rounds = (1 << 10, 8, 4) if smoke else (1 << 20, 2048, 12)
    cfg, ecfg, state, step = _mk_engine(cap, 1 << 12, batch)
    rng = np.random.default_rng(5)
    n_live = batch
    idents = rng.integers(1, 2**31, (64, 8)).astype(np.uint32)
    # populate with creates, keeping ids from the responses
    creates = [(1, idents[i % 64], np.zeros(4, np.uint32), idents[(i + 1) % 64],
                rng.integers(0, 2**31, (234,)).astype(np.uint32)) for i in range(n_live)]
    import jax
    ids = []
    for i in range(0, n_live, batch):
        b = _batch_arrays(creates[i : i + batch], ecfg)
        state, resp, _ = step(ecfg, state, b)
        ids.append(np.asarray(resp["msg_id"]))
    jax.block_until_ready(state)
    all_ids = np.concatenate(ids)[:n_live]
    reads = [(2, creates[i][3], all_ids[i], np.zeros(8, np.uint32),
              np.zeros(234, np.uint32)) for i in range(n_live)]
    batches = [_batch_arrays(reads[:batch], ecfg)]
    _, times, total = _run_rounds(ecfg, state, step, batches, n_rounds)
    ops = batch * n_rounds
    return {"ops_per_sec": round(ops / total, 1), "p99_round_ms": round(_p99(times), 2),
            "batch": batch, "capacity_log2": cap.bit_length() - 1}


def bench_zipf_mixed(smoke, cipher_impl="jnp"):
    """Config 3: mixed CRUD, Zipf(1.1) recipients — hammers hot
    mailboxes into the 62-message cap. ``cipher_impl="pallas"`` runs
    the same workload through the fused VMEM keystream kernel
    (oblivious/pallas_cipher.py) — reported as its own config line so
    a Mosaic compile issue cannot sink the headline.

    ``GRAPEVINE_BENCH_BATCH`` overrides the full-size batch (default
    2048; B=4096 runs overflow-free with the batch-scaled stash —
    PERF.md lever 5)."""
    import os

    full_batch = int(os.environ.get("GRAPEVINE_BENCH_BATCH", "2048"))
    cap, batch, n_rounds = (1 << 10, 8, 4) if smoke else (1 << 20, full_batch, 12)
    cfg, ecfg, state, step = _mk_engine(cap, 1 << 12, batch, cipher_impl=cipher_impl)
    rng = np.random.default_rng(11)
    n_id = 512
    idents = rng.integers(1, 2**31, (n_id, 8)).astype(np.uint32)
    zipf = np.minimum(rng.zipf(1.1, size=8 * batch), n_id) - 1
    batches = []
    for k in range(4):
        reqs = []
        for j in range(batch):
            r = rng.random()
            rcp = idents[zipf[(k * batch + j) % len(zipf)]]
            me = idents[rng.integers(0, n_id)]
            pl = rng.integers(0, 2**31, (234,)).astype(np.uint32)
            zid = np.zeros((4,), np.uint32)
            if r < 0.5:
                reqs.append((1, me, zid, rcp, pl))  # CREATE → hot recipient
            elif r < 0.8:
                reqs.append((2, rcp, zid, np.zeros(8, np.uint32), pl))  # pop-read
            else:
                reqs.append((4, rcp, zid, np.zeros(8, np.uint32), pl))  # pop-del
        batches.append(_batch_arrays(reqs, ecfg))
    _, times, total = _run_rounds(ecfg, state, step, batches, n_rounds)
    ops = batch * n_rounds
    return {"ops_per_sec": round(ops / total, 1), "p99_round_ms": round(_p99(times), 2),
            "batch": batch, "capacity_log2": cap.bit_length() - 1}


def bench_zipf_pallas(smoke):
    """zipf_mixed through the Pallas cipher kernel (fused VMEM
    keystream+XOR). A full-size run is a chip run (main() refuses
    anything else), so the kernel is Mosaic-compiled there; ``--smoke``
    runs it in interpret mode at toy shapes to keep the path exercised."""
    return bench_zipf_mixed(smoke, cipher_impl="pallas")


def _model_ab(kind, measured, **kw):
    """Modeled-vs-measured winner line for one A/B config group.

    Every ``_ab`` bench reports the static cost model's pick
    (analysis/costmodel.ab_verdict — amortized HBM bytes at the exact
    bench geometry, tie-band preferring least machinery) next to the
    measured winner, so a model/machine divergence is visible in the
    bench output itself, not only in the post-hoc
    ``check_cost_model --grade`` replay of the banked trajectory."""
    from grapevine_tpu.analysis.costmodel import ab_verdict

    v = ab_verdict(kind, **kw)
    return {
        "modeled_winner": v["winner"],
        "measured_winner": measured,
        "agree": v["winner"] == measured,
        "basis": v["basis"],
    }


def _min_of(fn, args, reps):
    """Interleaved-A/B timing primitive shared by the `_ab` configs:
    min of ``reps`` timed calls after one compile+warm call — the min
    is the unbiased cost of a shape-static oblivious program under this
    sandbox's 2-vCPU scheduler noise (PERF.md Round 6 methodology)."""
    import time as _time

    import jax

    jax.block_until_ready(fn(*args))  # compile + warm
    ts = []
    for _ in range(reps):
        t0 = _time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(_time.perf_counter() - t0)
    return float(np.min(ts))


def bench_posmap_ab(smoke):
    """Config 4d: flat vs recursive position map A/B (PR7).

    Two scopes, both interleaved min-of-N:

    - **machinery**: ``lookup_remap_round`` isolated — the exact code
      the knob swaps — over a (batch B × capacity) grid: flat is one
      private gather + scatter, recursive is a full internal-ORAM round
      over blocks/k blocks of k entries. This is the *cost of position
      resolution itself*, the number OPERATIONS.md §13's "when to flip"
      guidance prices against the capacity win.
    - **whole round**: B-sweep with ``posmap_impl`` as the only knob —
      what a serving round actually pays, since the recursive map adds
      its internal path fetch/evict to every ORAM round.

    Honest-reporting note (the PR-3/PR-5 lesson): the recursive map is
    NOT a speed optimization and is not expected to win wall-clock
    anywhere — it buys ~sqrt(capacity)× less *resident* position memory
    (the ≥2^30 capacity enabler) for extra HBM traffic. The A/B exists
    to price that overhead honestly; auto stays "flat" until capacity
    forces the flip or the capture's ``posmap_perf`` stage (real chip)
    shows the overhead is hidden under the round's existing
    gather/scatter wall. Override sweeps with GRAPEVINE_POSMAP_AB_BS /
    GRAPEVINE_POSMAP_AB_CAPS."""
    import os
    import time as _time

    import jax
    import jax.numpy as jnp

    from grapevine_tpu.oram.path_oram import OramConfig, init_oram
    from grapevine_tpu.oram.posmap import (
        derive_posmap_spec,
        lookup_remap_round,
        posmap_hbm_bytes,
        posmap_private_bytes,
    )
    from grapevine_tpu.oram.round import occurrence_masks

    reps = 3 if smoke else 7
    out = {"machinery": {}, "sweep": {}}

    # --- machinery: the lookup round isolated, B × capacity grid -------
    caps = [
        int(x)
        for x in os.environ.get(
            "GRAPEVINE_POSMAP_AB_CAPS",
            "4096,65536" if smoke else "65536,1048576",
        ).split(",")
    ]
    bs_m = (64, 256) if smoke else (256, 1024)
    rng = np.random.default_rng(5)
    for cap_n in caps:
        height = max(1, cap_n.bit_length() - 2)  # density-2 payload shape
        flat_cfg = OramConfig(height=height, value_words=4, n_blocks=cap_n)
        spec = derive_posmap_spec(cap_n)
        rec_cfg = OramConfig(
            height=height, value_words=4, n_blocks=cap_n, posmap=spec
        )
        pm_f = init_oram(flat_cfg, jax.random.PRNGKey(1)).posmap
        pm_r = init_oram(rec_cfg, jax.random.PRNGKey(1)).posmap
        for b in bs_m:
            idxs = jnp.asarray(
                rng.integers(0, cap_n + 1, b).astype(np.uint32)
            )
            nl = jnp.asarray(rng.integers(0, flat_cfg.leaves, b).astype(np.uint32))
            dl = jnp.asarray(rng.integers(0, flat_cfg.leaves, b).astype(np.uint32))
            pnl = jnp.asarray(
                rng.integers(0, spec.inner_leaves, b).astype(np.uint32)
            )
            pdl = jnp.asarray(
                rng.integers(0, spec.inner_leaves, b).astype(np.uint32)
            )

            def lookup(cfg, pm, pm_nl, pm_dl):
                fo, lo, _ = occurrence_masks(idxs, cfg.dummy_index)
                pm2, leaves, inner = lookup_remap_round(
                    cfg, pm, idxs, nl, dl, fo, lo,
                    pm_new_leaves=pm_nl, pm_dummy_leaves=pm_dl,
                )
                # pm2 must be a live output: dropping it lets XLA
                # dead-code-eliminate flat's remap scatter and the
                # internal round's whole eviction write-back
                return (pm2, leaves) if inner is None else (pm2, leaves, inner)

            tf = _min_of(
                jax.jit(lambda pm: lookup(flat_cfg, pm, None, None)),
                (pm_f,), reps,
            )
            tr = _min_of(
                jax.jit(lambda pm: lookup(rec_cfg, pm, pnl, pdl)),
                (pm_r,), reps,
            )
            out["machinery"][f"lookup_cap{cap_n}_b{b}"] = {
                "k": spec.entries_per_block,
                "flat_ms": round(tf * 1e3, 3),
                "recursive_ms": round(tr * 1e3, 3),
                "overhead_recursive_over_flat": round(tr / tf, 2),
                "flat_private_mib": round(
                    posmap_private_bytes(flat_cfg) / 2**20, 3
                ),
                "recursive_private_mib": round(
                    posmap_private_bytes(rec_cfg) / 2**20, 3
                ),
                "recursive_hbm_mib": round(
                    posmap_hbm_bytes(rec_cfg) / 2**20, 3
                ),
            }

    # --- whole round: posmap_impl the only knob ------------------------
    sweep = [
        int(x)
        for x in os.environ.get(
            "GRAPEVINE_POSMAP_AB_BS", "16,64" if smoke else "64,256,1024"
        ).split(",")
    ]
    n_timed = 3 if smoke else 9
    for B in sweep:
        ctxs = {}
        for impl in ("flat", "recursive"):
            cfg, ecfg, state, step = _mk_engine(
                1 << 12, 1 << 9, B, posmap_impl=impl,
                cipher_rounds=0, mailbox_cap=8,
            )
            batches = make_batches(3, B, seed=13)
            state, resp, _ = step(ecfg, state, batches[0])
            jax.block_until_ready(resp)
            ctxs[impl] = [ecfg, state, step, batches]

        def one_round(ctx, i):
            ecfg, state, step, batches = ctx
            t0 = _time.perf_counter()
            state, resp, _ = step(ecfg, state, batches[i % 3])
            jax.block_until_ready(resp)
            ctx[1] = state
            return _time.perf_counter() - t0

        times = {"flat": [], "recursive": []}
        for i in range(n_timed):  # interleaved A/B
            times["flat"].append(one_round(ctxs["flat"], i))
            times["recursive"].append(one_round(ctxs["recursive"], i))
        mf = float(np.min(times["flat"]))
        mr = float(np.min(times["recursive"]))
        out["sweep"][str(B)] = {
            "flat_round_ms": round(mf * 1e3, 2),
            "recursive_round_ms": round(mr * 1e3, 2),
            "median_flat_round_ms": round(
                float(np.median(times["flat"])) * 1e3, 2
            ),
            "median_recursive_round_ms": round(
                float(np.median(times["recursive"])) * 1e3, 2
            ),
            "overhead_recursive_over_flat": round(mr / mf, 3),
        }
    return out


def bench_tree_cache_ab(smoke):
    """Config 4e: tree-top cache A/B (PR8; ROADMAP item 1).

    Two scopes, both interleaved min-of-N (the posmap_ab
    methodology):

    - **machinery**: one records-shaped ``oram_round`` isolated (trivial
      apply callback) with ``top_cache_levels`` the only knob — the
      exact path gather/decrypt/evict/encrypt/scatter the cache cuts,
      without the engine's vphases/response machinery diluting it.
      Cap × B grid, cipher on (the cipher-row cut is part of the
      claim), with the per-k resident cache bytes reported.
    - **whole round**: engine B-sweep over k ∈ {0, 2, 4, auto} — what a
      serving round actually pays.

    Honest-reporting note (the PR-3/5 lesson): caching strictly removes
    HBM gather/scatter rows and cipher work — there is no algorithmic
    trade — but on this 2-vCPU sandbox the absolute win rides on how
    much of the round the path traffic is at the swept geometry;
    PERF.md Round 10 carries the analysis either way, and the on-chip
    number: not measured on the chip.
    Override sweeps with GRAPEVINE_TREE_CACHE_AB_BS /
    GRAPEVINE_TREE_CACHE_AB_CAPS."""
    import os
    import time as _time

    import jax
    import jax.numpy as jnp

    from grapevine_tpu.oram.path_oram import (
        OramConfig,
        init_oram,
        tree_cache_private_bytes,
    )
    from grapevine_tpu.oram.round import oram_round

    reps = 3 if smoke else 7
    out = {"machinery": {}, "sweep": {}}

    # --- machinery: one ORAM round isolated, cap × B grid --------------
    caps = [
        int(x)
        for x in os.environ.get(
            "GRAPEVINE_TREE_CACHE_AB_CAPS",
            "4096" if smoke else "65536,1048576",
        ).split(",")
    ]
    bs_m = (64,) if smoke else (256, 1024)
    ks_m = (0, 2) if smoke else (0, 2, 4, 8)
    rng = np.random.default_rng(5)
    for cap_n in caps:
        height = max(1, cap_n.bit_length() - 2)  # density-2 payload shape
        for b in bs_m:
            idxs = jnp.asarray(
                rng.integers(0, cap_n + 1, b).astype(np.uint32)
            )
            # one leaf schedule shared by every k arm (the posmap_ab
            # rule: the knob is the ONLY difference between arms —
            # round cost is leaf-independent by obliviousness, but the
            # A/B should not have to lean on that)
            nl = jnp.asarray(
                rng.integers(0, 1 << height, b).astype(np.uint32)
            )
            dl = jnp.asarray(
                rng.integers(0, 1 << height, b).astype(np.uint32)
            )
            grid = {}
            for k in ks_m:
                cfg = OramConfig(
                    height=height, value_words=64, n_blocks=cap_n,
                    cipher_rounds=8, stash_size=max(96, b // 2 + 96),
                    top_cache_levels=min(k, height),
                )
                state = init_oram(cfg, jax.random.PRNGKey(1))

                def one(st, cfg=cfg):
                    def apply_batch(vals0, present0):
                        return jnp.sum(vals0, axis=1), vals0, present0

                    st2, outs, leaves = oram_round(
                        cfg, st, idxs, nl, dl, apply_batch
                    )
                    # full-output rule: the new state must be live or
                    # XLA DCEs the write-back half of the round
                    return st2, outs, leaves

                t = _min_of(jax.jit(one), (state,), reps)
                grid[f"k{k}"] = {
                    "round_ms": round(t * 1e3, 3),
                    "cache_kib": round(
                        tree_cache_private_bytes(cfg) / 1024, 1
                    ),
                }
            base = grid["k0"]["round_ms"]
            for k in ks_m[1:]:
                grid[f"k{k}"]["speedup_over_k0"] = round(
                    base / grid[f"k{k}"]["round_ms"], 3
                )
            grid["model"] = _model_ab(
                "tree_cache",
                min((f"k{k}" for k in ks_m),
                    key=lambda a: grid[a]["round_ms"]),
                scope="machinery", cap_n=cap_n, batch=b,
                arms=list(ks_m),
            )
            out["machinery"][f"round_cap{cap_n}_b{b}"] = grid

    # --- whole round: tree_top_cache_levels the only knob --------------
    sweep = [
        int(x)
        for x in os.environ.get(
            "GRAPEVINE_TREE_CACHE_AB_BS", "64" if smoke else "256,1024"
        ).split(",")
    ]
    ks = (0, 2) if smoke else (0, 2, 4, "auto")
    n_timed = 3 if smoke else 9
    for B in sweep:
        ctxs = {}
        for k in ks:
            cfg, ecfg, state, step = _mk_engine(
                1 << 12, 1 << 9, B, mailbox_cap=8,
                tree_top_cache=None if k == "auto" else k,
            )
            batches = make_batches(3, B, seed=13)
            state, resp, _ = step(ecfg, state, batches[0])
            jax.block_until_ready(resp)
            ctxs[k] = [ecfg, state, step, batches]

        def one_round(ctx, i):
            ecfg, state, step, batches = ctx
            t0 = _time.perf_counter()
            state, resp, _ = step(ecfg, state, batches[i % 3])
            jax.block_until_ready(resp)
            ctx[1] = state
            return _time.perf_counter() - t0

        times = {k: [] for k in ks}
        for i in range(n_timed):  # interleaved A/B
            for k in ks:
                times[k].append(one_round(ctxs[k], i))
        m0 = float(np.min(times[0]))
        entry = {}
        for k in ks:
            mk = float(np.min(times[k]))
            entry[f"k{k}"] = {
                "round_ms": round(mk * 1e3, 2),
                "median_round_ms": round(
                    float(np.median(times[k])) * 1e3, 2
                ),
                "speedup_over_k0": round(m0 / mk, 3),
            }
            if k == "auto":
                entry["kauto"]["resolved_k"] = ctxs[k][0].tree_top_cache_levels
        numeric = [k for k in ks if k != "auto"]
        entry["model"] = _model_ab(
            "tree_cache",
            min((f"k{k}" for k in numeric),
                key=lambda a: entry[a]["round_ms"]),
            scope="sweep", batch=B, arms=numeric,
        )
        out["sweep"][str(B)] = entry
    return out


def bench_expiry_sweep(smoke):
    """Config 4: full-bus timestamped eviction scan (reference
    README.md:86-98) at the largest capacity that fits one chip:
    2^22 messages at tree density 4 — an 8 GB records tree on a 16 GB
    v5e, twice the 2^24 pod's 4 GB-per-chip shard (tests/
    test_capacity.py pins that shard to the 2^20-density-2 tree)."""
    import jax

    from grapevine_tpu.engine.expiry import expiry_sweep

    cap, density = ((1 << 10), 2) if smoke else ((1 << 22), 4)
    cfg, ecfg, state, step = _mk_engine(cap, 1 << 12, 64, density=density)
    # populate some traffic first so the sweep has work
    batches = make_batches(4, 64)
    for b in batches:
        state, resp, _ = step(ecfg, state, b)
    jax.block_until_ready(resp)
    sweep = jax.jit(expiry_sweep, static_argnums=(0,))
    s2 = sweep(ecfg, state, np.uint32(NOW + 10), np.uint32(5))
    jax.block_until_ready(s2)
    times = []
    for i in range(3 if smoke else 8):
        t0 = time.perf_counter()
        s2 = sweep(ecfg, s2, np.uint32(NOW + 10 + i), np.uint32(5))
        jax.block_until_ready(s2)
        times.append(time.perf_counter() - t0)
    # records scanned per second over the full bus
    per = float(np.mean(times))
    return {"records_per_sec": round(cap / per, 1), "p99_sweep_ms": round(_p99(times), 2),
            "capacity_log2": cap.bit_length() - 1, "tree_density": density}


def bench_sharded(smoke):
    """Config 5: the sharded engine on whatever mesh exists (a
    multi-chip host, or --smoke's virtual CPU devices). Needs >= 2
    devices: main() leaves it out of the list, and says so on stderr,
    when there are fewer."""
    import jax

    from grapevine_tpu.config import GrapevineConfig
    from grapevine_tpu.engine.state import EngineConfig
    from grapevine_tpu.parallel.mesh import (
        init_sharded_engine,
        make_mesh,
        make_sharded_step,
    )

    n_dev = len(jax.devices())
    cap, batch, n_rounds = (1 << 10, 8, 3) if smoke else (1 << 20, 256, 8)
    ecfg = EngineConfig.from_config(GrapevineConfig(
        max_messages=cap, max_recipients=1 << 10, batch_size=batch,
        stash_size=max(128, batch // 2 + 96), tree_density=2,
    ))
    mesh = make_mesh()
    state = init_sharded_engine(ecfg, mesh, seed=0)
    step = make_sharded_step(ecfg, mesh)
    batches = make_batches(4, batch)
    state, resp, _ = step(state, batches[0])
    jax.block_until_ready(resp)
    times = []
    t_all = time.perf_counter()
    for i in range(n_rounds):
        t0 = time.perf_counter()
        state, resp, _ = step(state, batches[i % 4])
        jax.block_until_ready(resp)
        times.append(time.perf_counter() - t0)
    total = time.perf_counter() - t_all
    overflow = int(np.asarray(state.rec.overflow)) + int(np.asarray(state.mb.overflow))
    assert overflow == 0, f"stash overflow during sharded bench: {overflow}"
    ops = batch * n_rounds
    return {"ops_per_sec": round(ops / total, 1), "p99_round_ms": round(_p99(times), 2),
            "batch": batch, "capacity_log2": cap.bit_length() - 1, "mesh": n_dev}


def bench_server_loopback(smoke):
    """End-to-end gRPC loopback: in-process server (session crypto +
    challenge lockstep + batched signature verification + engine),
    concurrent authenticated clients. Exposes the full-stack throughput
    the engine-only configs skip (round-2 review: the auth path capped the
    server at O(100) ops/s before batch verification).

    The session layer runs on the ``cryptography`` wheel when present
    and on the stdlib ChaCha20+HMAC port (session/stdcrypto.py) when
    not, so this config reports real numbers in every container — the
    historical wheel-less *skip* is gone (ISSUE 20). The active backend
    rides the result line so banked numbers are never compared across
    backends by accident."""
    import threading

    from grapevine_tpu.config import GrapevineConfig
    from grapevine_tpu.server.client import GrapevineClient
    from grapevine_tpu.server.service import GrapevineServer
    from grapevine_tpu.session.channel import CRYPTO_BACKEND
    from grapevine_tpu.wire import constants as C

    cap, n_clients, per_client = (1 << 10, 2, 4) if smoke else (1 << 16, 16, 24)
    cfg = GrapevineConfig(
        max_messages=cap,
        max_recipients=1 << 10,
        batch_size=16,
        bucket_cipher_rounds=0 if smoke else 8,
    )
    # the leak monitor rides every loopback round (ISSUE 2 acceptance:
    # p99 must hold within 3% with it on — the hand-off is one queue
    # put; detectors run on the monitor's own thread). Its verdict is
    # reported so the bench doubles as an honest-soak audit.
    from grapevine_tpu.obs.leakmon import LeakMonitorConfig

    server = GrapevineServer(config=cfg, leakmon=LeakMonitorConfig())
    port = server.start("insecure-grapevine://127.0.0.1:0")
    try:
        clients = [
            GrapevineClient(
                f"insecure-grapevine://127.0.0.1:{port}",
                identity_seed=bytes([i + 1]) * 32,
            )
            for i in range(n_clients)
        ]
        for c in clients:
            c.auth()
        errs = []
        lat: list[float] = []
        lock = threading.Lock()

        def run(c, peer):
            try:
                for i in range(per_client):
                    t0 = time.perf_counter()
                    r = c.create(recipient=peer.public_key,
                                 payload=bytes([i & 0xFF]) * C.PAYLOAD_SIZE)
                    assert r.status_code == C.STATUS_CODE_SUCCESS, r.status_code
                    r2 = c.read()  # zero-id pop of my own inbox (may be empty)
                    assert r2.status_code in (
                        C.STATUS_CODE_SUCCESS,
                        C.STATUS_CODE_NOT_FOUND,
                    )
                    with lock:
                        lat.append(time.perf_counter() - t0)
            except Exception as e:  # pragma: no cover
                errs.append(e)

        t0 = time.perf_counter()
        threads = [
            threading.Thread(target=run, args=(c, clients[(j + 1) % n_clients]))
            for j, c in enumerate(clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        total = time.perf_counter() - t0
        assert not errs, errs[0]
        ops = n_clients * per_client * 2  # create + read per iteration
        # per-phase p99s from the obs registry: where the full-stack
        # round budget actually went (assembly window vs verify vs
        # device vs demux) — the breakdown Palermo-style perf work needs
        phases = {
            k.split("phase=", 1)[1].split("}", 1)[0]: v
            for k, v in server.metrics_registry.snapshot().items()
            if k.startswith("grapevine_phase_seconds{") and k.endswith("_p99")
        }
        server.leakmon.flush(10)
        audit = server.leakmon.verdict()
        return {
            "ops_per_sec": round(ops / total, 1),
            "p99_pair_ms": round(_p99(lat), 2),
            "phase_p99_s": phases,
            "clients": n_clients,
            "capacity_log2": cap.bit_length() - 1,
            "crypto_backend": CRYPTO_BACKEND,
            "leakaudit": audit["verdict"],
            "leakaudit_rounds": audit["rounds_observed"],
        }
    finally:
        server.stop()


def bench_host_pipeline_ab(smoke):
    """Config 6b (ISSUE 20): worker-count scaling of the verify+codec
    machinery through the multiprocess hostpipe (server/hostpipe.py) —
    the off-GIL pool the scheduler fans batch verification across and
    the serving layer runs session codec (AEAD open/seal + unpack +
    validate + challenge lockstep) on.

    Three arms, interleaved rep by rep: in-process (the historical
    single-GIL path, verify only — there is no in-process pool to run
    codec tasks on), W=1, and W=2. Per arm: sr25519 batch-verify
    throughput over a round-sized item set, and codec throughput over
    pipelined `open` tasks across channels sticky-routed over the pool.

    Honesty: scaling is a property of the HOST, so ``host_cores`` (the
    scheduler-visible core count) rides the line as a perf-sentinel
    geometry key. On a single-core container W=2 physically serializes
    — the measured speedup is the serialized floor (~1.0x), and the
    ceiling analysis is the Amdahl projection from the measured
    dispatch-serial fraction (parent-side pickle + pipe send, the only
    part that cannot parallelize): what W=2 would deliver with two real
    cores. The ≥1.7x acceptance claim is gated on ``host_cores >= 2``;
    a single-core line reports the projection and says so in ``note``.
    """
    import os
    import pickle
    import threading

    from grapevine_tpu.obs import TelemetryRegistry
    from grapevine_tpu.server.hostpipe import HostPipeline
    from grapevine_tpu.session import schnorrkel
    from grapevine_tpu.session.chacha import ChallengeRng
    from grapevine_tpu.session.channel import (
        CRYPTO_BACKEND,
        client_finish,
        client_handshake,
        server_handshake,
    )
    from grapevine_tpu.wire import constants as C
    from grapevine_tpu.wire.records import QueryRequest, RequestRecord

    n_items, n_chan, opens_per_chan, reps = (
        (256, 4, 8, 2) if smoke else (2048, 8, 24, 3)
    )
    cores = len(os.sched_getaffinity(0))
    ctx = C.GRAPEVINE_CHALLENGE_SIGNING_CONTEXT

    # one signing key per 250 identities is plenty: verify cost is
    # per-item regardless of key reuse
    keys = []
    for i in range(250):
        sk, _ = schnorrkel.expand_mini_secret(bytes([i + 1]) * 32)
        keys.append((sk, schnorrkel.public_key(sk)))
    items = []
    for i in range(n_items):
        sk, pub = keys[i % len(keys)]
        msg = b"round-challenge-%06d" % i
        items.append((pub, ctx, msg, schnorrkel.sign(sk, ctx, msg)))

    def mk_sealed(chan, rng, n):
        """n sealed CREATE envelopes in lockstep order for one channel."""
        sk, pub = keys[0]
        out = []
        for i in range(n):
            ch = rng.next_challenge()
            req = QueryRequest(
                request_type=C.REQUEST_TYPE_CREATE,
                auth_identity=pub,
                auth_signature=schnorrkel.sign(sk, ctx, ch),
                record=RequestRecord(
                    recipient=pub,
                    payload=bytes([i & 0xFF]) * C.PAYLOAD_SIZE,
                ),
            )
            out.append(chan.encrypt(req.pack()))
        return out

    def setup_pool(w):
        pool = HostPipeline(w, registry=TelemetryRegistry())
        pool.verify_parallel(items[: 4 * w])  # warm every worker
        chans = []
        for j in range(n_chan):
            cid = b"host-ab-%08d" % j
            state, msg1 = client_handshake()
            reply, server_chan = server_handshake(msg1)
            cchan = client_finish(state, reply)
            seed = bytes([j + 1]) * 32
            pool.attach_session(cid, server_chan, seed)
            sealed = mk_sealed(cchan, ChallengeRng(seed),
                               opens_per_chan * reps)
            chans.append((cid, sealed))
        return pool, chans

    arms = {w: setup_pool(w) for w in (1, 2)}
    best = {
        "inproc": {"verify": 0.0},
        1: {"verify": 0.0, "codec": 0.0},
        2: {"verify": 0.0, "codec": 0.0},
    }
    schnorrkel.batch_verify(items[:8])  # warm the in-process tables
    try:
        for rep in range(reps):
            t0 = time.perf_counter()
            assert schnorrkel.batch_verify(items)
            best["inproc"]["verify"] = max(
                best["inproc"]["verify"],
                n_items / (time.perf_counter() - t0))
            for w, (pool, chans) in arms.items():
                t0 = time.perf_counter()
                assert pool.verify_parallel(items)
                best[w]["verify"] = max(
                    best[w]["verify"],
                    n_items / (time.perf_counter() - t0))
                # codec: pipeline this rep's slice of every channel's
                # sealed stream; per-channel FIFO order preserves the
                # AEAD/challenge lockstep, channels overlap across the
                # pool exactly as sticky routing spreads them
                lo, hi = rep * opens_per_chan, (rep + 1) * opens_per_chan
                t0 = time.perf_counter()
                futs = [
                    pool.submit("open", (cid, ct, b""), sticky=cid)
                    for cid, sealed in chans
                    for ct in sealed[lo:hi]
                ]
                for f in futs:
                    f.result(timeout=60.0)
                best[w]["codec"] = max(
                    best[w]["codec"],
                    len(futs) / (time.perf_counter() - t0))
        # the dispatch-side serial fraction: what the parent must do
        # alone before workers can run (chunk pickle + pipe write;
        # measured as the pickle, the pipe write rides the same bytes)
        t0 = time.perf_counter()
        pickle.dumps(("schnorrkel", items))
        t_serial = time.perf_counter() - t0
        t_w1 = n_items / best[1]["verify"]
        s_frac = min(1.0, t_serial / t_w1)
        projected = 1.0 / (s_frac + (1.0 - s_frac) / 2.0)
    finally:
        for pool, _ in arms.values():
            pool.close()

    out = {
        "host_cores": cores,
        "clients": n_chan,
        "crypto_backend": CRYPTO_BACKEND,
        "verify_items": n_items,
        "reps": reps,
        "inproc": {
            "verify_ops_per_sec": round(best["inproc"]["verify"], 1),
        },
    }
    for w in (1, 2):
        out[f"w{w}"] = {
            "verify_ops_per_sec": round(best[w]["verify"], 1),
            "codec_ops_per_sec": round(best[w]["codec"], 1),
        }
    out["speedup_verify_w2_over_w1"] = round(
        best[2]["verify"] / best[1]["verify"], 3)
    out["speedup_codec_w2_over_w1"] = round(
        best[2]["codec"] / best[1]["codec"], 3)
    out["fanout_tax_w1_over_inproc"] = round(
        best[1]["verify"] / best["inproc"]["verify"], 3)
    out["dispatch_serial_fraction"] = round(s_frac, 4)
    out["projected_w2_speedup_2cores"] = round(projected, 3)
    if cores >= 2:
        assert out["speedup_verify_w2_over_w1"] >= 1.7, (
            f"W=2 verify scaling {out['speedup_verify_w2_over_w1']}x "
            f"< 1.7x on a {cores}-core host"
        )
    else:
        out["note"] = (
            "single-core container: W=2 serializes by construction, so "
            "the measured speedup is the floor, not the machinery's "
            "ceiling; the Amdahl projection from the measured dispatch-"
            "serial fraction is the honest 2-core estimate"
        )
    return out


def bench_slo_loopback(smoke):
    """Config 7: concurrent submitters through the BatchScheduler into
    the engine with the PR-6 observability stack attached (round tracer
    + commit-latency SLO tracker) — the end-to-end *commit latency* a
    client observes (enqueue → round settle), which is what the SLO
    engine gates on, plus the derived bubble ratio that sizes the
    pipelined-round refactor (ROADMAP item 2). No session crypto in the
    loop, so unlike ``server_loopback`` this runs in every container;
    the observability overhead rides every round exactly as it does in
    production (`EngineServer` attaches the same stack)."""
    import threading

    from grapevine_tpu.config import GrapevineConfig
    from grapevine_tpu.engine.batcher import GrapevineEngine
    from grapevine_tpu.obs.slo import SloConfig, SloTracker
    from grapevine_tpu.obs.tracer import RoundTracer
    from grapevine_tpu.server.scheduler import BatchScheduler
    from grapevine_tpu.wire import constants as C
    from grapevine_tpu.wire.records import QueryRequest, RequestRecord

    cap, n_clients, per_client, batch = (
        (1 << 10, 2, 6, 4) if smoke else (1 << 16, 8, 48, 16)
    )
    cfg = GrapevineConfig(
        max_messages=cap, max_recipients=1 << 10, batch_size=batch,
        bucket_cipher_rounds=0 if smoke else 8,
    )
    engine = GrapevineEngine(cfg)
    tracer = RoundTracer(capacity=256, registry=engine.metrics.registry)
    engine.attach_tracer(tracer)
    slo = SloTracker(SloConfig(), registry=engine.metrics.registry)
    engine.attach_slo(slo)
    sched = BatchScheduler(engine, clock=lambda: NOW)
    try:
        rng = np.random.default_rng(17)
        idents = rng.integers(1, 256, (n_clients, 32)).astype(np.uint8)
        # recipients rotate through a pool wide enough that no mailbox
        # approaches the 62-message cap across warm-up + timed sends
        recips = rng.integers(1, 256, (64, 32)).astype(np.uint8)
        errs: list = []
        lat: list[float] = []
        lock = threading.Lock()

        def run(j):
            me = idents[j].tobytes()
            try:
                for i in range(per_client):
                    req = QueryRequest(
                        request_type=C.REQUEST_TYPE_CREATE,
                        auth_identity=me,
                        auth_signature=b"\x01" * C.SIGNATURE_SIZE,
                        record=RequestRecord(
                            msg_id=C.ZERO_MSG_ID,
                            recipient=recips[
                                (j * per_client + i) % len(recips)
                            ].tobytes(),
                            payload=bytes([i & 0xFF]) * C.PAYLOAD_SIZE,
                        ),
                    )
                    t0 = time.perf_counter()
                    r = sched.submit(req)
                    assert r.status_code == C.STATUS_CODE_SUCCESS, r.status_code
                    with lock:
                        lat.append(time.perf_counter() - t0)
            except Exception as e:  # pragma: no cover
                errs.append(e)

        # one warm-up op pays the compile outside the timed window (the
        # SLO tracker sees it too — exactly the cold-start breach the
        # min_rounds gate exists to not page on)
        warm = sched.submit(QueryRequest(
            request_type=C.REQUEST_TYPE_CREATE,
            auth_identity=idents[0].tobytes(),
            auth_signature=b"\x01" * C.SIGNATURE_SIZE,
            record=RequestRecord(
                msg_id=C.ZERO_MSG_ID, recipient=recips[0].tobytes(),
                payload=b"\x00" * C.PAYLOAD_SIZE,
            ),
        ))
        assert warm.status_code == C.STATUS_CODE_SUCCESS
        t0 = time.perf_counter()
        threads = [threading.Thread(target=run, args=(j,))
                   for j in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        total = time.perf_counter() - t0
        assert not errs, errs[0]
        verdict = slo.verdict()
        trace = tracer.chrome_trace()
        ops = n_clients * per_client
        return {
            "ops_per_sec": round(ops / total, 1),
            "p99_commit_ms": round(_p99(lat), 2),
            "median_commit_ms": round(float(np.median(lat)) * 1e3, 2),
            "bubble_ratio": trace["otherData"]["bubble_ratio"],
            "trace_rounds": trace["otherData"]["rounds_recorded_total"],
            "slo_target_ms": verdict["target_ms"],
            "slo_ok": verdict["ok"],
            "fast_burn_rate": verdict["fast_burn_rate"],
            "slow_burn_rate": verdict["slow_burn_rate"],
            "clients": n_clients, "batch": batch,
            "capacity_log2": cap.bit_length() - 1,
        }
    finally:
        sched.close()


def bench_pipeline_ab(smoke):
    """Config 7b: round-pipeline depth A/B (PR 10; ROADMAP item 2).

    Whole-round sustained throughput + enqueue→settle commit latency
    through the production BatchScheduler at ``pipeline_depth`` 1 (the
    serial pre-PR-10 program) vs 2 (round k+1's collection window,
    verification, and journal fsync overlap rounds k/k+1 on the
    device), **fsync on**: each arm journals every round to its own
    state dir with ``journal_fsync_every=1`` and checkpoints pushed out
    of the window, so the A/B prices exactly the claim — at depth 2 the
    fsync barrier overlaps device execution instead of serializing
    with it. Min-of-N interleaved at the whole-rep level: arms alternate rep by rep so drift
    in the shared host hits both equally; per arm the best rep's
    throughput and the minimum p99 are reported. The tracer rides both
    arms and contributes the measured journal-span p99 and the bubble
    ratio. No session crypto in the loop — runs in every container."""
    import os
    import shutil
    import tempfile
    import threading

    from grapevine_tpu.config import DurabilityConfig, GrapevineConfig
    from grapevine_tpu.engine.batcher import GrapevineEngine
    from grapevine_tpu.obs.tracer import RoundTracer
    from grapevine_tpu.server.scheduler import BatchScheduler
    from grapevine_tpu.wire import constants as C
    from grapevine_tpu.wire.records import QueryRequest, RequestRecord

    cap, n_clients, per_client, batch, reps = (
        (1 << 10, 2, 6, 4, 2) if smoke else (1 << 16, 8, 48, 16, 3)
    )
    rng = np.random.default_rng(23)
    idents = rng.integers(1, 256, (n_clients, 32)).astype(np.uint8)
    recips = rng.integers(1, 256, (64, 32)).astype(np.uint8)

    def mk_req(j, i):
        return QueryRequest(
            request_type=C.REQUEST_TYPE_CREATE,
            auth_identity=idents[j].tobytes(),
            auth_signature=b"\x01" * C.SIGNATURE_SIZE,
            record=RequestRecord(
                msg_id=C.ZERO_MSG_ID,
                recipient=recips[(j * per_client + i) % len(recips)].tobytes(),
                payload=bytes([i & 0xFF]) * C.PAYLOAD_SIZE,
            ),
        )

    tmp = tempfile.mkdtemp(prefix="gv-pipeline-ab-")
    arms: dict = {}
    try:
        for depth in (1, 2):
            cfg = GrapevineConfig(
                max_messages=cap, max_recipients=1 << 10, batch_size=batch,
                bucket_cipher_rounds=0 if smoke else 8,
                pipeline_depth=depth,
            )
            dcfg = DurabilityConfig(
                state_dir=os.path.join(tmp, f"d{depth}"),
                # no checkpoint inside the timed window: the A/B prices
                # the per-round fsync, not the periodic state seal
                checkpoint_every_rounds=1 << 20,
                journal_fsync_every=1,
            )
            engine = GrapevineEngine(cfg, durability=dcfg)
            tracer = RoundTracer(capacity=2048,
                                 registry=engine.metrics.registry)
            engine.attach_tracer(tracer)
            sched = BatchScheduler(engine, clock=lambda: NOW)
            warm = sched.submit(mk_req(0, 0))  # compile outside the window
            assert warm.status_code == C.STATUS_CODE_SUCCESS
            arms[depth] = {"engine": engine, "tracer": tracer,
                           "sched": sched, "ops": 0.0, "p99": None,
                           "p50": None}

        def one_rep(arm):
            lat: list[float] = []
            errs: list = []
            lock = threading.Lock()

            def run(j):
                try:
                    for i in range(per_client):
                        req = mk_req(j, i)
                        t0 = time.perf_counter()
                        r = arm["sched"].submit(req)
                        assert r.status_code == C.STATUS_CODE_SUCCESS, (
                            r.status_code)
                        with lock:
                            lat.append(time.perf_counter() - t0)
                except Exception as e:  # pragma: no cover
                    errs.append(e)

            threads = [threading.Thread(target=run, args=(j,))
                       for j in range(n_clients)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            total = time.perf_counter() - t0
            assert not errs, errs[0]
            ops = n_clients * per_client / total
            arm["ops"] = max(arm["ops"], ops)
            p99, p50 = _p99(lat), float(np.median(lat)) * 1e3
            arm["p99"] = p99 if arm["p99"] is None else min(arm["p99"], p99)
            arm["p50"] = p50 if arm["p50"] is None else min(arm["p50"], p50)

        for _ in range(reps):  # interleaved: drift hits both arms
            for depth in (1, 2):
                one_rep(arms[depth])

        out: dict = {"batch": batch, "capacity_log2": cap.bit_length() - 1,
                     "clients": n_clients, "reps": reps, "fsync": True}
        for depth in (1, 2):
            arm = arms[depth]
            trace = arm["tracer"].chrome_trace()
            j_ms = [ev["dur"] / 1e3 for ev in trace["traceEvents"]
                    if ev["name"] == "grapevine/journal" and ev.get("dur")]
            out[f"depth{depth}"] = {
                "ops_per_sec": round(arm["ops"], 1),
                "p99_commit_ms": round(arm["p99"], 2),
                "median_commit_ms": round(arm["p50"], 2),
                "journal_p99_ms": round(
                    float(np.percentile(j_ms, 99, method="higher")), 3)
                if j_ms else None,
                "journal_mean_ms": round(float(np.mean(j_ms)), 3)
                if j_ms else None,
                "bubble_ratio": trace["otherData"]["bubble_ratio"],
                "rounds": trace["otherData"]["rounds_recorded_total"],
            }
        d1, d2 = out["depth1"], out["depth2"]
        out["speedup_ops_d2_over_d1"] = round(
            d2["ops_per_sec"] / d1["ops_per_sec"], 3)
        out["p99_delta_ms_d1_minus_d2"] = round(
            d1["p99_commit_ms"] - d2["p99_commit_ms"], 2)
        out["model"] = _model_ab(
            "pipeline",
            "depth2" if d2["ops_per_sec"] > d1["ops_per_sec"]
            else "depth1",
        )
        return out
    finally:
        for arm in arms.values():
            arm["sched"].close()
            arm["engine"].close()
        shutil.rmtree(tmp, ignore_errors=True)


def bench_load_scenarios(smoke):
    """Config 8: the workload observatory (PR9; ROADMAP item 4's
    measurement half). Open-loop scenario suite through the production
    BatchScheduler (``submit_nowait`` — overload latency is measured,
    never self-throttled) with the workload telemetry + leak monitor
    attached, no session crypto in the loop (the ``slo_loopback``
    container-portability pattern).

    Rates are calibrated to THIS host: a warm timed round gives the
    engine's intrinsic capacity estimate, honest scenarios offer
    fractions of it, and the ramp staircases past it — so the same
    config saturates a 2-vCPU sandbox and a real TPU without hand
    tuning. The knee SLO target is ``max(250 ms, 8× the unloaded round
    time)``: the capacity question is where latency departs from the
    unloaded baseline (OPERATIONS.md §15 has the methodology).

    Hard acceptance rides inside the config (ISSUE 9): the adversarial
    probe campaign (+ the red-team leak injector — an honest engine's
    transcript cannot be flipped by traffic shape alone, which is the
    point of the FP gate) must end SUSPECT and every honest scenario
    PASS, else this config errors and ``--smoke`` fails rc!=0.

    Second pass (ISSUE 20): the same suite reruns through the
    multiprocess frontend — hostpipe pool + SLO-adaptive windows —
    against a fresh engine, with the same verdict acceptance plus a
    knee-no-worse gate vs the first pass."""
    from grapevine_tpu.config import GrapevineConfig
    from grapevine_tpu.engine.batcher import GrapevineEngine
    from grapevine_tpu.load import (
        ProbeCampaignInjector,
        ScenarioRunner,
        adversarial_probe,
        analyze_ramp,
        bursty_onoff,
        calibrate_unloaded_round,
        diurnal_sinusoid,
        pop_heavy_drain,
        ramp_to_saturation,
        steady_poisson,
    )
    from grapevine_tpu.obs.leakmon import EngineLeakMonitor
    from grapevine_tpu.obs.workload import WorkloadTelemetry
    from grapevine_tpu.server.scheduler import BatchScheduler

    cap, batch, dur = (1 << 10, 4, 1.5) if smoke else (1 << 14, 16, 3.0)
    pd = _pipeline_depth_arg()
    cfg = GrapevineConfig(
        max_messages=cap, max_recipients=1 << 10, batch_size=batch,
        bucket_cipher_rounds=0 if smoke else 8,
        pipeline_depth=pd,
    )
    engine = GrapevineEngine(cfg)
    wl = WorkloadTelemetry(engine.metrics.registry, batch_size=batch)
    engine.attach_workload(wl)
    # warm the jit + measure the unloaded round; est scales every
    # scenario to this host and target_ms is the knee SLO (the shared
    # formula — load/harness.py calibrate_unloaded_round)
    t_round, est, target_ms = calibrate_unloaded_round(engine, NOW)

    # --- the scenario suite, rates relative to the calibrated est -----
    pulse = max(2.0 * t_round, 0.02)
    n_steps = 4 if smoke else 5
    # ramp steps must dwarf the commit latency (itself a couple of
    # rounds): with steps shorter than the backlog's time constant, a
    # past-capacity step ends before its own arrivals' waits blow up
    # and the knee reads as "unsaturated" at an offered rate the
    # engine never sustained
    step_s = max(0.75, dur / 3.0, 12.0 * t_round)
    schedules = {
        "steady": steady_poisson(0.5 * est, dur, seed=11),
        "bursty": bursty_onoff(
            1.2 * est, duty=0.4, period_s=dur / 3.0, duration_s=dur,
            seed=12),
        "diurnal": diurnal_sinusoid(
            0.5 * est, rel_amplitude=0.8, period_s=dur / 2.0,
            duration_s=dur, seed=13),
        "pop_heavy": pop_heavy_drain(0.5 * est, dur, seed=14, n_hot=4),
        "adversarial": adversarial_probe(
            pulse, dur, seed=15, n_probe_keys=4, probes_per_pulse=2),
        "ramp": ramp_to_saturation(
            0.25 * est, factor=2.0, n_steps=n_steps, step_s=step_s,
            seed=16),
    }
    honest = ("steady", "bursty", "diurnal", "pop_heavy")
    out = {
        "scenarios": {},
        "calibrated_round_ms": round(t_round * 1e3, 2),
        # NOT named slo_target_ms: that is a GEOMETRY key for the perf
        # sentinel, and this value is perf_counter-calibrated — as
        # geometry it would make every run a fresh series and the
        # capacity numbers would never be gated at all
        "knee_target_ms": round(target_ms, 1),
        "batch": batch, "capacity_log2": cap.bit_length() - 1,
    }
    if pd is not None:
        # explicit depth reruns (the PR-10 knee-delta question) key
        # their own sentinel series; auto runs keep the PR-9 series
        # continuous by omitting the field entirely
        out["pipeline_depth"] = pd
    for name, schedule in schedules.items():
        # fresh monitor per scenario (registry=None: the engine registry
        # already carries the serving leakmon families; per-scenario
        # verdicts need fresh windows, not fresh gauges)
        mon = EngineLeakMonitor(
            mb_leaves=engine.ecfg.mb.leaves,
            rec_leaves=engine.ecfg.rec.leaves,
            mb_choices=engine.ecfg.mb_choices,
        )
        sink = (
            ProbeCampaignInjector(mon, engine.ecfg)
            if name == "adversarial" else mon
        )
        engine.attach_leakmon(sink)
        sched = BatchScheduler(engine, clock=lambda: NOW)
        try:
            runner = ScenarioRunner(sched, n_idents=64,
                                    settle_timeout_s=120.0)
            res = runner.run(schedule)
        finally:
            sched.close()
        mon.flush(30)
        v = mon.verdict()
        entry = res.summary()
        entry["leakaudit"] = v["verdict"]
        entry["leakaudit_rounds"] = v["rounds_observed"]
        rounds = mon.recorder.dump()["rounds"]
        if rounds:
            fills = [r["fill"] for r in rounds]
            depths = [r.get("queue_depth", 0) for r in rounds]
            entry["mean_fill"] = round(float(np.mean(fills)), 3)
            entry["queue_depth_p99"] = float(
                np.percentile(depths, 99, method="higher"))
        if name == "ramp":
            entry.update(analyze_ramp(schedule, res, target_ms))
            entry["knee_target_ms"] = entry.pop("target_ms")
        out["scenarios"][name] = entry
        mon.close()
        engine.attach_leakmon(None)
        print(f"[bench]   load_scenarios/{name}: "
              f"{entry.get('achieved_ops_per_sec')} ops/s, "
              f"p99 {entry.get('p99_commit_ms')} ms, "
              f"{entry['leakaudit']}", file=sys.stderr, flush=True)

    # ISSUE 9 acceptance, enforced in the config itself
    adv = out["scenarios"]["adversarial"]
    assert adv["leakaudit"] == "SUSPECT" and adv["leakaudit_rounds"] > 0, (
        f"probe campaign did not flip /leakaudit: {adv}"
    )
    for name in honest:
        h = out["scenarios"][name]
        assert h["leakaudit"] == "PASS" and h["leakaudit_rounds"] > 0, (
            f"honest scenario {name} not PASS: {h}"
        )
    assert out["scenarios"]["ramp"]["knee_ops_per_sec"] > 0, (
        f"ramp found no holding step: {out['scenarios']['ramp']}"
    )
    out["knee_ops_per_sec"] = out["scenarios"]["ramp"]["knee_ops_per_sec"]

    # --- second pass: the multiprocess frontend (ISSUE 20) ------------
    # Same engine, same calibrated schedules, but the scheduler now
    # carries the full host pipeline: a 2-worker hostpipe pool planted
    # for verify fan-out and the SLO-adaptive window policy fed by the
    # workload telemetry. The acceptance is behavioral, not
    # throughput: every honest generator
    # must still PASS the leak audit (the adaptive window is driven by
    # public aggregates only — a contents-driven window would flip the
    # detectors), the probe campaign must still end SUSPECT, and the
    # knee must be no worse than the single-process same-session run.
    from grapevine_tpu.obs import TelemetryRegistry
    from grapevine_tpu.server.adaptive import AdaptiveBatchPolicy
    from grapevine_tpu.server.hostpipe import HostPipeline

    # fresh engine, same config + schedules: the first pass filled a
    # meaningful fraction of the (smoke-sized) capacity, and a knee
    # measured against a half-full tree is not comparable to one
    # against a fresh one
    engine.close()
    engine = GrapevineEngine(cfg)
    wl = WorkloadTelemetry(engine.metrics.registry, batch_size=batch)
    engine.attach_workload(wl)
    calibrate_unloaded_round(engine, NOW)  # warm the jit only; the
    # schedules keep the first pass's calibrated rates for an honest
    # same-session comparison

    pool = HostPipeline(2, registry=TelemetryRegistry())
    adaptive = AdaptiveBatchPolicy(batch, 0.008, 0.002, workload=wl)
    hp: dict = {"scenarios": {}, "worker_count": 2, "adaptive_batch": True}
    try:
        for name, schedule in schedules.items():
            mon = EngineLeakMonitor(
                mb_leaves=engine.ecfg.mb.leaves,
                rec_leaves=engine.ecfg.rec.leaves,
                mb_choices=engine.ecfg.mb_choices,
            )
            sink = (
                ProbeCampaignInjector(mon, engine.ecfg)
                if name == "adversarial" else mon
            )
            engine.attach_leakmon(sink)
            sched = BatchScheduler(engine, clock=lambda: NOW)
            sched.hostpipe = pool
            sched.adaptive = adaptive
            try:
                runner = ScenarioRunner(sched, n_idents=64,
                                        settle_timeout_s=120.0)
                res = runner.run(schedule)
            finally:
                sched.close()
            mon.flush(30)
            v = mon.verdict()
            entry = res.summary()
            entry["leakaudit"] = v["verdict"]
            entry["leakaudit_rounds"] = v["rounds_observed"]
            if name == "ramp":
                entry.update(analyze_ramp(schedule, res, target_ms))
                entry["knee_target_ms"] = entry.pop("target_ms")
            hp["scenarios"][name] = entry
            mon.close()
            engine.attach_leakmon(None)
            print(f"[bench]   load_scenarios/hostpipe/{name}: "
                  f"{entry.get('achieved_ops_per_sec')} ops/s, "
                  f"p99 {entry.get('p99_commit_ms')} ms, "
                  f"{entry['leakaudit']}", file=sys.stderr, flush=True)
    finally:
        pool.close()

    adv = hp["scenarios"]["adversarial"]
    assert adv["leakaudit"] == "SUSPECT" and adv["leakaudit_rounds"] > 0, (
        f"probe campaign not SUSPECT through the frontend: {adv}"
    )
    for name in honest:
        h = hp["scenarios"][name]
        assert h["leakaudit"] == "PASS" and h["leakaudit_rounds"] > 0, (
            f"honest scenario {name} not PASS through the frontend: {h}"
        )
    hp["knee_ops_per_sec"] = hp["scenarios"]["ramp"]["knee_ops_per_sec"]
    assert hp["knee_ops_per_sec"] > 0, (
        f"frontend ramp found no holding step: {hp['scenarios']['ramp']}"
    )
    # "no worse" with single-core calibration noise: a real regression
    # halves the knee (a serialized window or a stalled pool); 0.7x is
    # outside rep-to-rep noise on the sandbox and inside any real break
    hp["knee_ratio_vs_inproc"] = round(
        hp["knee_ops_per_sec"] / out["knee_ops_per_sec"], 3)
    assert hp["knee_ratio_vs_inproc"] >= 0.7, (
        f"multiprocess frontend degraded the knee: {hp['knee_ratio_vs_inproc']}"
    )
    out["hostpipe_frontend"] = hp
    return out


def bench_fleet_loopback(smoke):
    """Config 9: the fleet observatory (PR16; ROADMAP items 1/4's
    measurement half). Two independent engines take a recipient-
    partitioned ramp concurrently while a real FleetAggregator —
    fetch wired straight to the two engine registries, no sockets —
    scrapes them on its fixed public cadence. Banks the per-shard
    knees and the folded fleet knee under the ``shard_count`` geometry
    key (tools/check_perf_regression.py never compares them against
    single-engine series), and asserts the fleet-grain acceptance
    inside the config: both members up in the merged view, and the
    cross-shard uniformity verdict PASS — the production scheduler
    dispatches uniformly, so a SUSPECT here is a harness or detector
    regression, not noise."""
    from grapevine_tpu.config import GrapevineConfig
    from grapevine_tpu.engine.batcher import GrapevineEngine
    from grapevine_tpu.load import (
        ShardedScenarioRunner,
        analyze_ramp,
        calibrate_unloaded_round,
        fleet_capacity,
        ramp_to_saturation,
    )
    from grapevine_tpu.obs.exporter import render_prometheus
    from grapevine_tpu.obs.fleet import (
        FleetAggregator,
        FleetConfig,
        _sample_value,
    )
    from grapevine_tpu.obs.workload import WorkloadTelemetry
    from grapevine_tpu.server.scheduler import BatchScheduler

    n_shards = 2
    cap, batch, dur = (1 << 10, 4, 1.5) if smoke else (1 << 13, 8, 3.0)
    cfg = GrapevineConfig(
        max_messages=cap, max_recipients=1 << 10, batch_size=batch,
        bucket_cipher_rounds=0 if smoke else 8,
    )
    engines = [GrapevineEngine(cfg) for _ in range(n_shards)]
    # workload telemetry per shard: the fill histogram is both the
    # uniformity monitor's fill series and the banked per-shard stat
    for e in engines:
        e.attach_workload(
            WorkloadTelemetry(e.metrics.registry, batch_size=batch))
    # solo calibration (warms every shard's jit), then a barrier-synced
    # CONTENDED round: all shards commit one round at the same instant,
    # which is what steady-state fleet replay looks like. On shared
    # silicon (this CPU sandbox) the contended round is ~n_shards x the
    # solo one and the knee target must be rated against it, or the
    # ramp's first step already misses; on a real fleet (one chip per
    # shard) contended == solo and this degenerates to the §15 formula
    import threading as _threading

    from grapevine_tpu.load.generators import CREATE
    from grapevine_tpu.load.harness import identity_pool
    from grapevine_tpu.wire import constants as C
    from grapevine_tpu.wire.records import QueryRequest, RequestRecord

    for e in engines:
        calibrate_unloaded_round(e, NOW)
    idents = identity_pool(8)
    calib_reqs = [
        QueryRequest(
            request_type=CREATE, auth_identity=idents[i % 8],
            auth_signature=b"\x01" * C.SIGNATURE_SIZE,
            record=RequestRecord(
                msg_id=C.ZERO_MSG_ID, recipient=idents[(i + 1) % 8],
                payload=bytes([i & 0xFF]) * C.PAYLOAD_SIZE))
        for i in range(batch)
    ]
    barrier = _threading.Barrier(n_shards)
    times: list = [[] for _ in range(n_shards)]

    def _contended(i):
        for _ in range(3):
            barrier.wait()
            t0 = time.perf_counter()
            engines[i].handle_queries(calib_reqs, NOW)
            times[i].append(time.perf_counter() - t0)

    threads = [
        _threading.Thread(target=_contended, args=(i,))
        for i in range(n_shards)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # min over reps of the slowest shard: the steady contended round
    t_round = min(max(ts[k] for ts in times) for k in range(3))
    est = batch / t_round  # per-shard contended capacity
    target_ms = max(250.0, 8.0 * t_round * 1e3)

    registries = [e.metrics.registry for e in engines]

    def loopback_fetch(url: str, timeout_s: float) -> bytes:
        addr, _, path = url.split("//")[1].partition("/")
        shard = int(addr.split(":")[0].removeprefix("shard"))
        if path == "metrics":
            return render_prometheus(registries[shard]).encode()
        return b""  # aux endpoints absent in-process: best-effort

    agg = FleetAggregator(
        FleetConfig(
            members=tuple(f"shard{i}:1" for i in range(n_shards)),
            scrape_interval_s=max(0.05, 2.0 * t_round),
        ),
        fetch=loopback_fetch,
    )
    n_steps = 4 if smoke else 5
    step_s = max(0.75, dur / 3.0, 12.0 * t_round)
    # each shard walks the single-engine staircase against its
    # CONTENDED capacity (fleet offered rate = n_shards x per-shard)
    schedule = ramp_to_saturation(
        0.25 * est * n_shards, factor=2.0, n_steps=n_steps,
        step_s=step_s, seed=17)
    scheds = [BatchScheduler(e, clock=lambda: NOW) for e in engines]
    agg.start()
    try:
        runner = ShardedScenarioRunner(scheds, n_idents=64,
                                       settle_timeout_s=120.0)
        results = runner.run(schedule)
    finally:
        agg.stop()
        for s in scheds:
            s.close()
    agg.scrape_once()  # final aligned sample after the drain
    analyses = [
        analyze_ramp(r.schedule, r, target_ms) for r in results
    ]
    fleet = fleet_capacity(analyses)
    uv = agg.uniformity.verdict()
    merged = agg.render_merged()
    # per-shard fill/cadence stats off the aggregator's final scrape —
    # the same public series the uniformity detectors consume
    for i, shard_out in enumerate(fleet["shards"]):
        fams = agg._members[i].families or {}
        rounds = _sample_value(
            fams, "grapevine_rounds_total", default=0.0)
        fill_sum = _sample_value(
            fams, "grapevine_load_batch_fill",
            "grapevine_load_batch_fill_sum", 0.0)
        fill_count = _sample_value(
            fams, "grapevine_load_batch_fill",
            "grapevine_load_batch_fill_count", 0.0)
        shard_out["rounds_total"] = int(rounds)
        shard_out["mean_fill"] = (
            round(fill_sum / fill_count, 3) if fill_count else None
        )
    out = {
        "shard_count": n_shards,
        "fleet_knee_ops_per_sec": fleet["fleet_knee_ops_per_sec"],
        "saturated": fleet["saturated"],
        "shards": fleet["shards"],
        "uniformity": uv["verdict"],
        "uniformity_window_ticks": uv["window_ticks"],
        "calibrated_round_ms": round(t_round * 1e3, 2),
        "knee_target_ms": round(target_ms, 1),
        "batch": batch, "capacity_log2": cap.bit_length() - 1,
    }
    # fleet-grain acceptance rides inside the config (ISSUE 16)
    assert all(st.up for st in agg._members), "member down in loopback"
    for i in range(n_shards):
        assert f'grapevine_rounds_total{{shard="{i}"}}' in merged, (
            f"shard {i} missing from merged view"
        )
    assert uv["verdict"] == "PASS", f"uniform fleet graded SUSPECT: {uv}"
    assert out["fleet_knee_ops_per_sec"] > 0, f"no fleet knee: {out}"
    print(f"[bench]   fleet_loopback: fleet knee "
          f"{out['fleet_knee_ops_per_sec']} ops/s over {n_shards} shards "
          f"(uniformity {uv['verdict']})", file=sys.stderr, flush=True)
    return out


def bench_failover_ab(smoke):
    """Config: hot-standby failover — measured RTO vs durable-tail
    length (ISSUE 19 acceptance: RTO ≤ tail-replay of one checkpoint
    interval, RPO 0 for durable frames).

    One primary engine ships its sealed journal to an in-process
    ``StandbyReplica`` over the real socket transport
    (engine/replication.py). The standby catches up live; then the
    link is cut, the primary appends a controlled durable tail of
    exactly ``tail_frames`` journal records past the standby's applied
    seq, and ``promote()`` is timed: fence plant + tail drain + fsync.
    Three tails — empty (pure fencing floor), 8 frames, and one full
    checkpoint interval (the worst legal tail: any longer and the
    standby would bootstrap from the next checkpoint instead). RPO is asserted, not claimed: the
    promoted state must hash bit-identical to the dead primary's.

    ``tail_frames`` (the checkpoint interval) is the geometry key:
    trajectory lines at different intervals are different experiments,
    never graded against each other (tools/check_perf_regression.py)."""
    import hashlib as _hashlib
    import os
    import tempfile as _tempfile

    from grapevine_tpu.config import DurabilityConfig, GrapevineConfig
    from grapevine_tpu.engine.batcher import GrapevineEngine
    from grapevine_tpu.engine.checkpoint import state_to_bytes
    from grapevine_tpu.engine.replication import JournalShipper, StandbyReplica
    from grapevine_tpu.load.harness import identity_pool
    from grapevine_tpu.wire import constants as C
    from grapevine_tpu.wire.records import QueryRequest, RequestRecord

    batch = 4
    ckpt_interval = 12 if smoke else 32
    cfg = GrapevineConfig(
        max_messages=64, max_recipients=8, mailbox_cap=4,
        batch_size=batch, stash_size=64, bucket_cipher_rounds=0,
    )
    idents = identity_pool(8)

    def _reqs(i):
        return [
            QueryRequest(
                request_type=C.REQUEST_TYPE_CREATE,
                auth_identity=idents[(i + j) % 8],
                auth_signature=b"\x01" * C.SIGNATURE_SIZE,
                record=RequestRecord(
                    msg_id=C.ZERO_MSG_ID,
                    recipient=idents[(i + j + 1) % 8],
                    payload=bytes([(i + j) & 0xFF]) * C.PAYLOAD_SIZE))
            for j in range(batch)
        ]

    tails = {
        "rto_empty_tail_ms": 0,
        "rto_8_tail_ms": 8,
        "rto_full_tail_ms": ckpt_interval,
    }
    out = {"tail_frames": ckpt_interval, "rpo_frames": 0}
    for metric, tail in tails.items():
        with _tempfile.TemporaryDirectory(prefix="bench-failover-") as root:
            pdir = os.path.join(root, "primary")
            sdir = os.path.join(root, "standby")
            os.makedirs(pdir)
            os.makedirs(sdir)
            # replication's standing requirement: a shared root seal key
            key = bytes(range(32))
            for d in (pdir, sdir):
                with open(os.path.join(d, "root.key"), "wb") as fh:
                    fh.write(key)
                os.chmod(os.path.join(d, "root.key"), 0o600)
            # manual checkpoint control: the interval IS the experiment
            big = 1 << 20
            primary = GrapevineEngine(cfg, seed=7, durability=DurabilityConfig(
                state_dir=pdir, checkpoint_every_rounds=big,
                journal_fsync_every=1))
            replica = StandbyReplica(cfg, seed=7, durability=DurabilityConfig(
                state_dir=sdir, checkpoint_every_rounds=big,
                journal_fsync_every=1))
            port = replica.listen()
            shipper = JournalShipper(primary, f"127.0.0.1:{port}")
            shipper.start()
            # live catch-up phase: a few warm rounds through the wire
            now = NOW
            for i in range(4):
                primary.handle_queries(_reqs(i), now)
                now += 1
            deadline = time.monotonic() + 30.0
            while (replica.dm.applied_seq < primary.durability.seq
                   and time.monotonic() < deadline):
                time.sleep(0.005)
            assert replica.dm.applied_seq == primary.durability.seq, (
                f"standby never caught up: {replica.dm.applied_seq} < "
                f"{primary.durability.seq}"
            )
            # cut the link, then append exactly ``tail`` durable frames
            shipper.close()
            i = 4
            while primary.durability.seq - replica.dm.applied_seq < tail:
                primary.handle_queries(_reqs(i), now)
                now += 1
                i += 1
            dead_seq = primary.durability.seq
            dead_hash = _hashlib.sha256(
                state_to_bytes(primary.ecfg, primary.state)
            ).hexdigest()
            primary.close()
            info = replica.promote(primary_state_dir=pdir)
            live_hash = _hashlib.sha256(
                state_to_bytes(replica.engine.ecfg, replica.engine.state)
            ).hexdigest()
            # RPO 0 for durable frames, bit for bit — asserted inside
            # the config so a regression fails the bench, not just a
            # number drifting
            assert replica.dm.applied_seq == dead_seq, (
                f"promoted replica at seq {replica.dm.applied_seq}, "
                f"primary died at {dead_seq}"
            )
            assert live_hash == dead_hash, (
                "promoted state is not bit-identical to the dead primary"
            )
            assert info["drained_frames"] >= tail, (
                f"tail drain too short: {info['drained_frames']} < {tail}"
            )
            out[metric] = round(info["rto_seconds"] * 1e3, 2)
            replica.close()
    assert out["rto_full_tail_ms"] < 60_000, (
        f"full-interval tail replay blew the RTO budget: {out}"
    )
    print(f"[bench]   failover_ab: rto empty/{tails['rto_8_tail_ms']}f/"
          f"{out['tail_frames']}f = {out['rto_empty_tail_ms']}/"
          f"{out['rto_8_tail_ms']}/{out['rto_full_tail_ms']} ms "
          f"(rpo 0, bit-identical)", file=sys.stderr, flush=True)
    return out


# Headline config FIRST: if the run later hits a budget wall or the
# driver's own timeout, the metric that matters is already captured
# (round-3 review, next-round #1b).
CONFIGS = [
    ("zipf_mixed", bench_zipf_mixed),
    ("batched_read", bench_batched_read),
    ("zipf_pallas_cipher", bench_zipf_pallas),
    ("crd_loop", bench_crd_loop),
    ("posmap_ab", bench_posmap_ab),
    ("tree_cache_ab", bench_tree_cache_ab),
    ("expiry_sweep", bench_expiry_sweep),
    ("sharded", bench_sharded),
    ("server_loopback", bench_server_loopback),
    ("host_pipeline_ab", bench_host_pipeline_ab),
    ("slo_loopback", bench_slo_loopback),
    ("pipeline_ab", bench_pipeline_ab),
    ("load_scenarios", bench_load_scenarios),
    ("fleet_loopback", bench_fleet_loopback),
    ("failover_ab", bench_failover_ab),
]


#: configs that build a device mesh: left out of the run (and named on
#: stderr) when fewer than two devices are visible
MESH_CONFIGS = ("sharded",)


def _device() -> dict:
    """The device this run measures, as JAX reports it."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class _ConfigTimeout(Exception):
    pass


def _run_capped(fn, smoke: bool, cap_s: float):
    """Run one config under a SIGALRM cap. The benches loop in Python
    between device dispatches, so the alarm lands between iterations;
    snapshot emission keeps the last stdout line parseable if the
    driver kills a run that is stuck inside one call."""
    import signal

    def _handler(signum, frame):
        raise _ConfigTimeout(f"config exceeded {cap_s:.0f}s cap")

    old = signal.signal(signal.SIGALRM, _handler)
    signal.alarm(max(1, int(cap_s)))
    try:
        return fn(smoke)
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


def _emit(results, meta):
    """Print the full result JSON as one line. Called after EVERY
    config: if the driver kills the process mid-run, the last complete
    stdout line is still a parseable snapshot with the configs that
    finished — never again an empty ``parsed: null`` artifact."""
    headline = results.get("zipf_mixed", {}).get("ops_per_sec", 0.0)
    line = {
        "metric": "oblivious_crud_ops_per_sec",
        "value": headline,
        "unit": "ops/s",
        "vs_baseline": round(headline / 1_000_000, 6),
        "configs": results,
    }
    line.update(meta)
    sys.stdout.write(json.dumps(line) + "\n")
    sys.stdout.flush()
    return line


def _pr_tag() -> str:
    """The PR tag for the trajectory line: ``--pr TAG`` (or ``--pr=TAG``)
    on the command line, else $GRAPEVINE_PR, else empty."""
    import os

    val = _argv_flag_value("--pr")
    return val if val is not None else os.environ.get("GRAPEVINE_PR", "")


def _append_trajectory(line: dict, tag: str) -> None:
    """Append the final result line to BENCH_trajectory.jsonl next to
    this file, so the perf trajectory accumulates across PRs instead of
    living only in per-run artifacts. Best-effort: a read-only checkout
    must not fail the bench itself."""
    import os

    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_trajectory.jsonl"
    )
    entry = {"ts": int(time.time()), "pr": tag, **line}
    try:
        with open(path, "a", encoding="utf-8") as fh:
            fh.write(json.dumps(entry) + "\n")
    except OSError as e:
        print(f"[bench] trajectory append failed: {e}", file=sys.stderr)


def _argv_flag_value(name: str) -> str | None:
    """Last value of ``--name V`` / ``--name=V`` on the command line,
    or None — the one token scan shared by the bench's ad-hoc flags
    (``--pr``, ``--only``, ``--pipeline-depth``)."""
    argv = sys.argv[1:]
    val = None
    for i, tok in enumerate(argv):
        if tok == name and i + 1 < len(argv):
            val = argv[i + 1]
        elif tok.startswith(name + "="):
            val = tok[len(name) + 1:]
    return val


def _pipeline_depth_arg() -> int | None:
    """``--pipeline-depth N`` (or ``=N``): run the scheduler-driven
    configs (load_scenarios) at an explicit round-pipeline depth — the
    ISSUE-10 knee-delta rerun — instead of the engine auto."""
    val = _argv_flag_value("--pipeline-depth")
    if val is None:
        return None
    try:
        return int(val)
    except ValueError:
        raise SystemExit(
            f"--pipeline-depth: want an integer depth, got {val!r}"
        ) from None


def _only_filter() -> list | None:
    """``--only a,b`` (or ``--only=a,b``): run just those configs — for
    banking one config's line (e.g. a PR's A/B) without paying the full
    suite on a weak builder core. Unknown names fail fast."""
    val = _argv_flag_value("--only")
    if val is None:
        return None
    names = [n.strip() for n in val.split(",") if n.strip()]
    known = {n for n, _ in CONFIGS}
    bad = [n for n in names if n not in known]
    if bad:
        raise SystemExit(f"--only: unknown config(s) {bad}; known: {sorted(known)}")
    return names


def main() -> int:
    import os

    smoke = "--smoke" in sys.argv
    budget_s = float(os.environ.get("GRAPEVINE_BENCH_BUDGET_S", "1500"))
    per_cfg_env = os.environ.get("GRAPEVINE_BENCH_CONFIG_S")
    if smoke:
        # smoke mode checks the harness and never takes a chip: the CPU
        # with eight virtual devices (tests/conftest.py), pinned before
        # jax loads, so the mesh configs run in this process too
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count=8".strip()
            )
    from grapevine_tpu.config import setup_compile_cache

    setup_compile_cache()
    t_start = time.perf_counter()
    results: dict = {}
    dev = _device()
    meta: dict = {"sizes": "smoke" if smoke else "full",
                  "backend": dev["platform"], "device": dev}
    if not smoke and dev["platform"] != "tpu":
        # a full-size run is a measurement of the chip; there is no
        # fallback — a CPU number under a device metric's name is worse
        # than no number
        print(f"[bench] no TPU: JAX's first device is {dev}; a run "
              "without --smoke measures the chip and refuses anything "
              "else", file=sys.stderr, flush=True)
        return 2
    # cold full-size compiles alone take minutes per config on the chip
    per_cfg_s = float(per_cfg_env) if per_cfg_env else (
        420.0 if smoke else 900.0
    )
    only = _only_filter()
    configs = (
        CONFIGS if only is None
        else [(n, f) for n, f in CONFIGS if n in only]
    )
    if dev["count"] < 2:
        left_out = [n for n, _ in configs if n in MESH_CONFIGS]
        if left_out:
            print(f"[bench] {dev['count']} device visible: leaving out "
                  f"the mesh configs {left_out}", file=sys.stderr,
                  flush=True)
            configs = [(n, f) for n, f in configs if n not in MESH_CONFIGS]
    # a parseable line exists before the first config; _emit after
    # EVERY config keeps the last stdout line a snapshot of what finished
    _emit(results, meta)
    for name, fn in configs:
        elapsed = time.perf_counter() - t_start
        if elapsed > budget_s:
            results[name] = {"skipped":
                             f"global budget {budget_s:.0f}s exhausted"}
            _emit(results, meta)
            continue
        cap = min(per_cfg_s, max(60.0, budget_s - elapsed))
        t0 = time.perf_counter()
        try:
            results[name] = _run_capped(fn, smoke, cap)
        except Exception as e:  # the others still run; the exit code says
            results[name] = {"error": f"{type(e).__name__}: {e}"}
        print(f"[bench] {name}: {results[name]} ({time.perf_counter()-t0:.1f}s)",
              file=sys.stderr, flush=True)
        _emit(results, meta)
    line = _emit(results, meta)
    # trajectory first, exit code after: a failed config must still
    # leave its line in the cross-PR record (the artifact tells the story)
    _append_trajectory(line, _pr_tag())
    failed = [n for n, r in results.items() if "error" in r]
    if failed:
        print(f"[bench] FAILED configs: {failed}", file=sys.stderr,
              flush=True)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
