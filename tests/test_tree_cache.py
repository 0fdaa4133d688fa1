"""Tree-top cache equivalence + audit (ISSUE 8 tentpole).

The contract of ``GrapevineConfig.tree_top_cache_levels = k``
(oram/path_oram.py, ROADMAP item 1 — the measured path-HBM bottleneck):

1. responses AND final engine state bit-identical cached↔uncached↔oracle
   — "state" in the canonical logical form
   (testing/compare.py:assert_logical_state_equal): decrypted tree
   planes with the cache overlaid, stashes, maps, scalars. Raw
   ciphertext at cached levels legitimately diverges (the cached run
   never rewrites those HBM rows), which is exactly what the overlay
   normalizes;
2. stash occupancy and overflow identical cached↔uncached at EVERY
   round of a soak (a top-cache bug — wrong eviction eligibility, a
   dropped cache write — would first show up as silent stash drift),
   read through ``health()``'s ``stash_occupancy`` fold;
3. the cached round is index-blind and moves exactly
   (2^Ld − 2^k) + B·(path_len − Ld) HBM bucket rows per plane — the
   levels the batch covers once, the rest per path
   (tools/check_tree_cache_oblivious.py, k=0 positive control);
4. a cached checkpoint can never silently restore into a
   differently-cached engine (geometry fingerprint covers k);
5. the leak monitor stays PASS on a live soak with caching enabled.

The first half shares ONE cached + ONE uncached engine compile
(plaintext BASE geometry) + small directed-ORAM compiles + trace-only
audits; the second half builds fresh pairs: the cipher pair (every
configuration runs ChaCha8 at rest), regime breadth and chaos.
Recursive-posmap pairs ride ``-m slow`` until that fork is decided.
"""

from __future__ import annotations

import os
import random
import sys

import jax
import numpy as np
import pytest

from test_vphases import (
    BASE,
    NOW,
    SAT_BUS,
    _assert_responses_bitequal,
    _campaign_plan,
    _gen_batch,
    key,
    req,
)

from grapevine_tpu.config import GrapevineConfig
from grapevine_tpu.engine.batcher import GrapevineEngine
from grapevine_tpu.testing.compare import assert_logical_state_equal
from grapevine_tpu.testing.reference import ReferenceEngine
from grapevine_tpu.wire import constants as C

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


def _mk_cache_pair(cfg_kwargs, seed, k=4):
    uncached = GrapevineEngine(
        GrapevineConfig(tree_top_cache_levels=0, **cfg_kwargs), seed=seed
    )
    cached = GrapevineEngine(
        GrapevineConfig(tree_top_cache_levels=k, **cfg_kwargs), seed=seed
    )
    return uncached, cached


def _run_tc_campaign(cfg_kwargs, seed, n_batches=3, batch_fill=None,
                     pair=None, sweep=False, k=4):
    """One campaign: uncached/cached pair + oracle over mixed batches,
    with per-round stash-occupancy equality (the drift canary) and
    final logical-state equality. ``pair`` reuses compiled engines."""
    rng = np.random.default_rng(seed)
    e0, ek = pair or _mk_cache_pair(
        cfg_kwargs, seed=int(rng.integers(1 << 30)), k=k
    )
    oracle = None
    if pair is None:
        oracle = ReferenceEngine(
            config=GrapevineConfig(**cfg_kwargs), rng=random.Random(seed)
        )
    idents = [key(i) for i in range(1, 1 + int(rng.integers(2, 6)))]
    live_ids: list[tuple[bytes, bytes]] = []
    bs = cfg_kwargs["batch_size"]
    for bi in range(n_batches):
        n = batch_fill or int(rng.integers(1, bs + 1))
        reqs = _gen_batch(rng, idents, live_ids, n)
        t = NOW + bi
        r0 = e0.handle_queries(reqs, t)
        rk = ek.handle_queries(reqs, t)
        _assert_responses_bitequal(r0, rk, f"tree_cache seed {seed} b {bi}")
        # per-round stash drift canary through the health() fold
        h0, hk = e0.health(), ek.health()
        assert h0["stash_occupancy"] == hk["stash_occupancy"], (
            f"tree_cache seed {seed} batch {bi}: stash occupancy drifts "
            f"cached vs uncached: {h0['stash_occupancy']} vs "
            f"{hk['stash_occupancy']}"
        )
        assert h0["stash_overflow"] == hk["stash_overflow"] == 0
        if oracle is not None:
            forced = [
                d.record.msg_id
                if r.request_type == C.REQUEST_TYPE_CREATE
                and d.status_code == C.STATUS_CODE_SUCCESS
                else None
                for r, d in zip(reqs, r0)
            ]
            ro = oracle.handle_batch(reqs, t, forced)
            for j, (d, o) in enumerate(zip(r0, ro)):
                assert d.status_code == o.status_code, (
                    f"tree_cache seed {seed} batch {bi} slot {j}: engine "
                    f"{d.status_code} != oracle {o.status_code}"
                )
                assert d.record.msg_id == o.record.msg_id
                assert d.record.payload == o.record.payload
            assert e0.message_count() == oracle.message_count()
            assert e0.recipient_count() == oracle.recipient_count()
        for r, d in zip(reqs, r0):
            if (r.request_type == C.REQUEST_TYPE_CREATE
                    and d.status_code == C.STATUS_CODE_SUCCESS):
                live_ids.append((d.record.msg_id, r.record.recipient))
            elif (r.request_type == C.REQUEST_TYPE_DELETE
                    and d.status_code == C.STATUS_CODE_SUCCESS):
                live_ids = [
                    (m, o_) for m, o_ in live_ids if m != d.record.msg_id
                ]
    if sweep:
        e0.expire(NOW + 10_000, 5_000)
        ek.expire(NOW + 10_000, 5_000)
    assert_logical_state_equal(
        e0.ecfg, e0.state, ek.ecfg, ek.state, f"tree_cache seed {seed}"
    )
    return e0, ek


# -- always-on: one compiled pair carries every fast assertion ----------


def test_tree_cache_campaign_with_sweep_soak_and_leakmon():
    """The budget-shaped always-on path: ONE uncached + ONE cached
    engine (plaintext BASE geometry) run a randomized oracle campaign
    with the per-round stash-drift canary, an expiry sweep, single-op
    batches, and a leakmon soak with caching enabled — zero additional
    compiles after the first round."""
    e0, ek = _run_tc_campaign(BASE, seed=5100, n_batches=4, sweep=True)
    assert ek.ecfg.rec.top_cache_levels == 4
    assert ek.ecfg.mb.top_cache_levels > 0  # clamped to the mb height

    # single-op batches on the same compiled pair (fill=1 → 7 dummies)
    _run_tc_campaign(BASE, seed=5101, n_batches=2, batch_fill=1,
                     pair=(e0, ek))

    # acceptance: leak monitor PASS on a live soak with caching enabled
    from grapevine_tpu.obs.leakmon import EngineLeakMonitor, LeakMonitorConfig

    mon = EngineLeakMonitor.for_engine(ek, LeakMonitorConfig(window_rounds=64))
    ek.attach_leakmon(mon)
    rng = np.random.default_rng(78)
    idents = [key(i) for i in range(1, 5)]
    live: list[tuple[bytes, bytes]] = []
    for bi in range(12):
        reqs = _gen_batch(rng, idents, live, 8)
        ek.handle_queries(reqs, NOW + 100 + bi)
    assert mon.flush(), "leak monitor did not drain"
    v = mon.verdict()
    assert v["verdict"] == "PASS", v
    mon.close()


def test_tree_cache_oram_level_directed():
    """Directed small-ORAM checks with NO engine compile: single
    ``oram_access`` CRUD against cached and uncached trees stays
    logically identical, the cache planes really hold the top levels,
    and k=0 state shapes are bit-for-bit the pre-PR-8 layout."""
    import jax.numpy as jnp

    from grapevine_tpu.oram.path_oram import (
        OramConfig,
        init_oram,
        oram_access,
        stash_occupancy,
    )
    from grapevine_tpu.testing.compare import logical_tree_planes

    kkey = jax.random.PRNGKey(5)
    cfgs = [
        OramConfig(height=4, value_words=4, n_blocks=16, cipher_rounds=8,
                   top_cache_levels=k)
        for k in (0, 2)
    ]
    states = [init_oram(c, kkey) for c in cfgs]
    assert states[0].cache_idx.size == 0
    assert states[1].cache_idx.size == 3 * 4  # (2^2−1) buckets × Z

    def wr(value, present, operand):
        return jnp.full((4,), operand, jnp.uint32), jnp.bool_(True), \
            jnp.bool_(True), present

    def rd(value, present, operand):
        return value, jnp.bool_(True), jnp.bool_(False), value

    # one small jit per (cfg, fn) — per-op eager dispatch of the whole
    # access program is ~10× slower on this sandbox
    import functools

    wrs = [
        jax.jit(functools.partial(oram_access, c, fn=wr)) for c in cfgs
    ]
    rds = [
        jax.jit(functools.partial(oram_access, c, fn=rd)) for c in cfgs
    ]

    rng = np.random.default_rng(3)
    for i in range(24):
        idx = np.uint32(rng.integers(0, 16))
        nl = np.uint32(rng.integers(0, 16))
        op = np.uint32(i + 1)
        outs = []
        for j in range(2):
            s, out, _leaf = wrs[j](states[j], idx, nl, op)
            states[j] = s
            outs.append(out)
        assert bool(outs[0]) == bool(outs[1]), f"access {i}: presence"
        assert int(stash_occupancy(states[0])) == int(
            stash_occupancy(states[1])
        ), f"access {i}: stash occupancy drifts"
    # reads see identical values through either path
    for idx in range(16):
        vals = []
        for j in range(2):
            s, out, _ = rds[j](
                states[j], np.uint32(idx), np.uint32(idx % 16), None
            )
            states[j] = s
            vals.append(np.asarray(out))
        assert np.array_equal(vals[0], vals[1]), f"read {idx}"
    p0 = logical_tree_planes(cfgs[0], states[0])
    p1 = logical_tree_planes(cfgs[1], states[1])
    assert np.array_equal(p0[0][:-1], p1[0][:-1])
    assert np.array_equal(p0[1][:-1], p1[1][:-1])
    # cached blocks live in the cache planes, not the HBM tree: the
    # cached state's top HBM rows must decrypt to NO live blocks — they
    # are stale by design (raw tree_idx is ciphertext under
    # cipher_rounds=8, so assert on the decrypted view, not raw bytes);
    # decode through the k=0 geometry (same tree shape, no overlay)
    from grapevine_tpu.oblivious.primitives import SENTINEL

    hbm_top = logical_tree_planes(cfgs[0], states[1])[0][
        : cfgs[1].cache_buckets
    ]
    assert np.all(hbm_top == int(SENTINEL)), (
        "cached top buckets' HBM rows must stay logically empty"
    )
    assert int(states[1].overflow) == 0
    # the cache really holds blocks (top levels fill under churn)
    assert np.any(np.asarray(states[1].cache_idx) != SENTINEL), (
        "24 accesses on a height-4 tree never evicted into the top "
        "2 levels — the cache is not being written"
    )


def test_tree_cache_access_schedule_audit():
    """CI gate (trace-only, flat map): index-blind census + the HBM
    row-count accounting with k=0 positive control — ISSUE-8's
    acceptance audit, wired into tier-1 next to the posmap/telemetry/
    seal gates."""
    from check_tree_cache_oblivious import check_tree_cache_schedule

    out = check_tree_cache_schedule(b=8, height=5, recursive=False)
    # B=8 covers levels 0-3 (Ld=4): those move once, whole — 15 buckets
    # at k=0, the 12 under the cache at k=2 — and levels 4-5 per path;
    # the cache planes pass through whole (no op names them)
    assert out["k0"]["tree_val"] == [15 + 8 * 2]
    assert out["k2"]["tree_val"] == [12 + 8 * 2]
    assert "cache_val" not in out["k2"]


def test_level_dense_row_accounting_in_its_three_regimes():
    """The level-dense rule (ISSUE 26) at a geometry per regime —
    tree smaller than the batch (Ld = path_len), dense range plus
    per-path rows, batch smaller than the cache top (Ld = k) — traced
    rows per HBM plane against the gate's own arithmetic, and never
    more than the per-path count the rule replaced."""
    from check_tree_cache_oblivious import check_dense_regimes

    out = check_dense_regimes()
    assert out["tree_under_batch"] == {"rows": 14, "per_path": 48, "Ld": 4}
    assert out["mixed"] == {"rows": 18, "per_path": 20, "Ld": 3}
    assert out["batch_under_cache"] == {"rows": 6, "per_path": 6, "Ld": 3}


@pytest.mark.parametrize("batch", [1, 2, 3, 12])
def test_cache_never_changes_where_a_block_is_placed(batch):
    """Contract 1 at the ORAM level, every round: the same op stream and
    the same leaves through ``oram_round`` at k = 0 and k = 3 leave
    bit-identical decrypted planes (cache overlaid) and stashes — also
    where the batch is smaller than the cache top (B = 1, 2, 3 cover 1-2
    levels of the 3 cached), where the deeper cache planes are held
    whole but a bucket is an eviction target only if a path meets it."""
    import jax.numpy as jnp

    from test_round import _random_kv_batches, kv_apply_batch

    from grapevine_tpu.oram.path_oram import OramConfig, init_oram
    from grapevine_tpu.oram.round import oram_round
    from grapevine_tpu.testing.compare import logical_tree_planes

    cfgs = [OramConfig(height=5, value_words=4, stash_size=96,
                       cipher_rounds=8, top_cache_levels=k) for k in (0, 3)]
    assert [c.covered_levels(batch) for c in cfgs] == [batch.bit_length()] * 2
    assert cfgs[1].dense_levels(batch) == max(batch.bit_length(), 3)
    steps = [
        jax.jit(lambda st, idxs, nl, dl, codes, vals, c=c: oram_round(
            c, st, idxs, nl, dl, kv_apply_batch(c, idxs, codes, vals)))
        for c in cfgs
    ]
    states = [init_oram(c, jax.random.PRNGKey(0)) for c in cfgs]
    rkey = jax.random.PRNGKey(11)
    for bi, (idxs, codes, vals) in enumerate(
        _random_kv_batches(cfgs[0], 16, batch, 13, p_write=0.7)
    ):
        rkey, k1, k2 = jax.random.split(rkey, 3)
        nl, dl = (jax.random.bits(k, (batch,), jnp.uint32)
                  & jnp.uint32(cfgs[0].leaves - 1) for k in (k1, k2))
        outs = []
        for j in range(2):
            states[j], out, leaves = steps[j](
                states[j], jnp.asarray(idxs), nl, dl, jnp.asarray(codes),
                jnp.asarray(vals))
            outs.append((np.asarray(out["value"]), np.asarray(leaves)))
        for a, b_ in zip(*outs):
            assert np.array_equal(a, b_), f"round {bi}: answers or leaves"
        for a, b_ in zip(*(logical_tree_planes(c, st)
                           for c, st in zip(cfgs, states))):
            assert np.array_equal(a, b_), f"round {bi}: placement diverges"
        for name in ("stash_idx", "stash_val"):
            assert np.array_equal(getattr(states[0], name),
                                  getattr(states[1], name)), f"round {bi}"
    if batch < 4:
        # few paths a round leave blocks high in the tree: the comparison
        # above was over cache planes that really hold some
        assert np.any(np.asarray(states[1].cache_idx) != 0xFFFFFFFF)


def test_tree_cache_checkpoint_fingerprint_rejects_cross_k(tmp_path):
    """A cached checkpoint must fail loudly against a differently-cached
    engine — the state shapes differ AND the fingerprint covers k. Pure
    serialization, no engine compile."""
    from grapevine_tpu.engine.checkpoint import (
        CheckpointError,
        bytes_to_state,
        engine_fingerprint,
        state_to_bytes,
    )
    from grapevine_tpu.engine.state import EngineConfig, init_engine

    kw = dict(BASE, max_messages=32, batch_size=4)
    ec0 = EngineConfig.from_config(
        GrapevineConfig(tree_top_cache_levels=0, **kw)
    )
    ec2 = EngineConfig.from_config(
        GrapevineConfig(tree_top_cache_levels=2, **kw)
    )
    assert engine_fingerprint(ec0) != engine_fingerprint(ec2)
    blob0 = state_to_bytes(ec0, init_engine(ec0, seed=1))
    blob2 = state_to_bytes(ec2, init_engine(ec2, seed=1))
    assert bytes_to_state(ec2, blob2) is not None  # control: self-loads
    with pytest.raises(CheckpointError, match="fingerprint"):
        bytes_to_state(ec2, blob0)
    with pytest.raises(CheckpointError, match="fingerprint"):
        bytes_to_state(ec0, blob2)


def test_tree_cache_config_validation():
    with pytest.raises(ValueError, match="tree_top_cache_levels"):
        GrapevineConfig(tree_top_cache_levels=-1)
    with pytest.raises(ValueError, match="tree_top_cache_levels"):
        GrapevineConfig(commit="op", tree_top_cache_levels=2)
    # per-tree clamp: k never exceeds a tree's height
    from grapevine_tpu.engine.state import EngineConfig

    ecfg = EngineConfig.from_config(
        GrapevineConfig(tree_top_cache_levels=30, **BASE)
    )
    assert ecfg.rec.top_cache_levels == ecfg.rec.height
    assert ecfg.mb.top_cache_levels == ecfg.mb.height
    # auto resolves per backend (4 under the phase engine everywhere —
    # the cache strictly removes HBM/cipher rows; PERF.md Round 10);
    # op-major (the differential oracle) stays cache-free
    auto = EngineConfig.from_config(GrapevineConfig(**BASE))
    assert auto.tree_top_cache_levels == 4
    op = EngineConfig.from_config(GrapevineConfig(commit="op", **BASE))
    assert op.tree_top_cache_levels == 0
    assert op.rec.top_cache_levels == 0
    # the OramConfig itself refuses k > height
    from grapevine_tpu.oram.path_oram import OramConfig

    with pytest.raises(ValueError, match="top_cache_levels"):
        OramConfig(height=3, value_words=4, top_cache_levels=4)
    # sizing helper: 2^k−1 bucket rows of idx+val words
    from grapevine_tpu.oram.path_oram import tree_cache_private_bytes

    c = OramConfig(height=5, value_words=8, top_cache_levels=3)
    assert tree_cache_private_bytes(c) == 7 * 4 * (4 + 4 * 8)


# -- breadth, cipher, geometry, chaos (fresh engine pairs) -------------


def test_randomized_tree_cache_campaigns_full():
    """Regime breadth: steady-state, saturation fallback, single-op
    batches — fresh pairs + oracle per campaign, k varied."""
    n = int(os.environ.get("GRAPEVINE_TREE_CACHE_CAMPAIGNS", "12"))
    for i, (cfg, fill) in enumerate(_campaign_plan(n)):
        _run_tc_campaign(cfg, seed=5200 + i, batch_fill=fill,
                         k=(1, 2, 4)[i % 3])


def test_tree_cache_campaign_cipher_on():
    """The at-rest cipher pair: cached levels skip cipher entirely while
    bottom levels re-key per round — the mixed regime must preserve the
    logical bit-identity end to end, sweep re-key included."""
    cfg = dict(BASE, bucket_cipher_rounds=8)
    _run_tc_campaign(cfg, seed=5300, n_batches=4, sweep=True)


@pytest.mark.slow
def test_tree_cache_campaign_recursive_posmap():
    """ROADMAP item 1 ∘ item 5: the cache applied to the payload trees
    AND the recursive posmap's internal tree (its top levels are touched
    every round too) stays bit-identical, leaf-metadata planes
    included."""
    cfg = dict(BASE, posmap_impl="recursive", bucket_cipher_rounds=8)
    _run_tc_campaign(cfg, seed=5400, n_batches=3, sweep=True, k=2)


def test_tree_cache_single_op_batch_geometry():
    """batch_size=1 end to end: the B=1 cached round (degenerate owner
    map, single path) stays bit-identical and oracle-true."""
    cfg = dict(BASE, batch_size=1)
    for i in range(2):
        _run_tc_campaign(cfg, seed=5600 + i, n_batches=5, batch_fill=1,
                         k=3)


def test_tree_cache_saturation_fallback_bitequal():
    """Bus saturation: rounds resolve through _admission_slow with the
    cache in the loop and must stay bit-identical, including
    TOO_MANY_MESSAGES admission order."""
    e0, ek = _mk_cache_pair(SAT_BUS, seed=9, k=3)
    a, x = key(1), key(2)
    rf = []
    for bi in range(3):
        reqs = [
            req(C.REQUEST_TYPE_CREATE, a, recipient=x, tag=bi * 8 + j)
            for j in range(8)
        ]
        rf = e0.handle_queries(reqs, NOW + bi)
        rk = ek.handle_queries(reqs, NOW + bi)
        _assert_responses_bitequal(rf, rk, f"sat batch {bi}")
    codes = {r.status_code for r in rf}
    assert C.STATUS_CODE_TOO_MANY_MESSAGES in codes
    assert_logical_state_equal(e0.ecfg, e0.state, ek.ecfg, ek.state, "sat")


@pytest.mark.slow
def test_tree_cache_recursive_audit():
    """The trace audit over a recursive-posmap geometry (inner tree's
    own cache planes included) — the heavier trace rides -m slow."""
    from check_tree_cache_oblivious import check_tree_cache_schedule

    check_tree_cache_schedule(b=8, height=5, recursive=True)


def test_chaos_recovery_with_tree_cache():
    """SIGKILL trials with the tree-top cache on: sealed checkpoints
    cover the cache planes (they are ordinary state leaves), so
    recovered state and every response hash stay bit-identical to the
    uninterrupted oracle with leakmon PASS."""
    import chaos_run

    args = chaos_run.parse_args(
        ["--events", "14", "--tree-top-cache-levels", "2", "--seed", "43"]
    )
    failures = chaos_run.run_trials(3, args)
    assert not failures, "\n".join(failures)
