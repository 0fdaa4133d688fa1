"""Batched ORAM rounds (oram/round.py) and the phase-major engine.

- round vs sequential ORAM: identical logical results on random KV op
  sequences with duplicates and dummies;
- phase-major engine vs the oracle's ``handle_batch`` on random CRUD;
- single-op batches: phase-major ≡ per-op oracle semantics;
- R/U/D transcript bit-equality for the round engine;
- duplicate-key dedup keeps transcript leaves uncorrelated.
"""

import random

import jax
import pytest
import jax.numpy as jnp
import numpy as np

from grapevine_tpu.config import GrapevineConfig
from grapevine_tpu.engine.batcher import GrapevineEngine
from grapevine_tpu.oram.path_oram import (
    OramConfig,
    init_oram,
    oram_access_batch,
    path_bucket_indices,
    stash_occupancy,
)
from grapevine_tpu.oram.round import (
    _assign_evictions,
    _bucket_owner_map,
    occurrence_masks,
    oram_round,
)
from grapevine_tpu.testing.reference import ReferenceEngine
from grapevine_tpu.wire import constants as C
from grapevine_tpu.wire.records import QueryRequest, RequestRecord

U32 = jnp.uint32
NOW = 1_700_000_000

OP_READ, OP_WRITE, OP_DELETE = 1, 2, 3


def kv_fn(value, present, opnd):
    code, val = opnd
    is_w = code == OP_WRITE
    is_d = code == OP_DELETE
    new_value = jnp.where(is_w, val, value)
    keep = ~(is_d & present)
    insert = is_w
    out = {"present": present, "value": jnp.where(present, value, 0)}
    return new_value, keep, insert, out


def kv_apply_batch(cfg, idxs, codes, vals):
    """Vectorized slot-order chain semantics for the simple KV ops —
    the test-side model of the engine's vphases approach: the last
    state-changing op (write/delete) before each op defines its view."""

    def apply_batch(vals0, present0):
        b = idxs.shape[0]
        real = idxs != U32(cfg.dummy_index)
        eq = (idxs[:, None] == idxs[None, :]) & real[:, None] & real[None, :]
        tril_s = jnp.tril(jnp.ones((b, b), jnp.bool_), k=-1)
        iota = jnp.arange(b, dtype=jnp.int32)
        is_w = (codes == OP_WRITE) & real
        is_d = (codes == OP_DELETE) & real
        ch = eq & (is_w | is_d)[None, :]

        def state_at(mask):
            lj = jnp.max(jnp.where(mask, iota[None, :], -1), axis=1)
            has = lj >= 0
            ljc = jnp.clip(lj, 0, b - 1)
            alive = jnp.where(has, is_w[ljc], present0 & real)
            value = jnp.where(
                (has & is_w[ljc])[:, None],
                vals[ljc],
                jnp.where(present0[:, None], vals0, 0),
            )
            return alive, value

        present_i, value_i = state_at(ch & tril_s)  # state before each op
        out = {
            "present": present_i,
            "value": jnp.where(present_i[:, None], value_i, 0),
        }
        final_alive, final_val = state_at(ch)  # state after the round
        return out, final_val, final_alive

    return apply_batch


def _random_kv_batches(cfg, n_batches, batch, seed, p_write=0.4):
    rng = np.random.default_rng(seed)
    w_hi = 0.1 + p_write  # dummy < 0.1 <= write < w_hi <= read < r_hi <= delete
    r_hi = w_hi + 0.6 * (0.9 - p_write)
    live = set()
    batches = []
    for _ in range(n_batches):
        idxs = np.empty((batch,), np.uint32)
        codes = np.empty((batch,), np.uint32)
        vals = rng.integers(1, 2**31, (batch, cfg.value_words)).astype(np.uint32)
        for i in range(batch):
            r = rng.random()
            if r < 0.1:
                idxs[i] = cfg.dummy_index
                codes[i] = OP_READ
            elif r < w_hi or not live:
                idxs[i] = rng.integers(0, cfg.blocks)
                codes[i] = OP_WRITE
                live.add(int(idxs[i]))
            elif r < r_hi:
                idxs[i] = rng.choice(sorted(live))
                codes[i] = OP_READ
            else:
                x = int(rng.choice(sorted(live)))
                idxs[i] = x
                codes[i] = OP_DELETE
                live.discard(x)
        batches.append((idxs, codes, vals))
    return batches


#: The level-dense layout's regimes (ISSUE 26), each a geometry of its
#: own: (height, batch, cached levels k, cipher rounds, recursive
#: posmap, shards, batches) and the Ld the shapes must resolve to.
#: ``parent_stash`` is the highest stash occupancy the PARENT commit's
#: per-path round reached after any round of the same campaign (same
#: seeds, same leaves; run once against the parent tree, PR 26): the
#: dense round makes every covered bucket an eviction target, so it
#: must never do worse. 0 (the stash empty after every round) unless a
#: case says otherwise.
LAYOUT_CASES = {
    # k < Ld < path_len: dense range, then per-path rows (the seed test)
    "mixed": dict(height=5, batch=12, k=0, ld=4),
    # the tree is smaller than the batch: Ld = path_len, no per-path row
    "tree_under_batch": dict(height=3, batch=16, k=1, ld=4),
    # the batch is smaller than the cache top: Ld = k, no dense HBM row
    "batch_under_cache": dict(height=5, batch=2, k=3, ld=3),
    "batch_of_one": dict(height=4, batch=1, k=0, ld=1, n_batches=24),
    "cipher_cached": dict(height=5, batch=12, k=2, ld=4, cipher=8),
    "recursive_posmap": dict(height=5, batch=12, k=2, ld=4, cipher=8,
                             recursive=True, n_batches=5),
    # density 6 and write-heavy, ~270 blocks live over 508 slots: the
    # one campaign here in which the parent's stash was ever non-empty
    "loaded": dict(height=6, batch=8, k=2, ld=4, blocks=384,
                   p_write=0.85, n_batches=70, parent_stash=2),
    "sharded": dict(height=5, batch=12, k=2, ld=4, cipher=8, shards=2,
                    n_batches=5),
}


def _on_own_path(cfg, state, leaf_of):
    """Every live tree block sits in a bucket on the path of its leaf:
    the Path-ORAM invariant, read off the decrypted planes."""
    from grapevine_tpu.oblivious.primitives import SENTINEL
    from grapevine_tpu.testing.compare import logical_tree_planes

    idx, _val, _leaf = logical_tree_planes(cfg, state)
    for hb, slot in zip(*np.nonzero(idx[:-1] != int(SENTINEL))):
        level = int(hb + 1).bit_length() - 1
        want = (1 << level) - 1 + (int(leaf_of[idx[hb, slot]])
                                   >> (cfg.height - level))
        assert hb == want, (
            f"block {idx[hb, slot]} (leaf {leaf_of[idx[hb, slot]]}) sits "
            f"in bucket {hb}, off its path (bucket {want} at level {level})"
        )


@pytest.mark.parametrize("case", list(LAYOUT_CASES))
def test_round_matches_sequential_oram(case):
    """Same op stream through oram_access_batch (the op-major oracle:
    per path, one access at a time) and oram_round (level-dense above,
    per-path below) gives the same logical outputs and the same final
    contents (leaves differ — the two paths draw different randomness;
    semantics must not), at every regime of the dense rule, with a
    recursive position map, sharded over a mesh, and at a batch of one.
    Placement differs by design, so the final states are compared as
    contents (``logical_block_map``) and held to the Path-ORAM
    invariant; stash overflow is 0 and the stash never holds more than
    the parent's per-path round left on the same seeds."""
    from grapevine_tpu.oram.posmap import derive_posmap_spec, read_table
    from grapevine_tpu.testing.compare import logical_block_map

    c = LAYOUT_CASES[case]
    blocks = c.get("blocks", 1 << c["height"])
    pm = (derive_posmap_spec(blocks, top_cache_levels=c["k"])
          if c.get("recursive") else None)
    cfg = OramConfig(height=c["height"], value_words=4, stash_size=96,
                     n_blocks=blocks, top_cache_levels=c["k"],
                     cipher_rounds=c.get("cipher", 0), posmap=pm)
    batch = c["batch"]
    assert cfg.dense_levels(batch) == c["ld"]
    key = jax.random.PRNGKey(0)
    st_seq = init_oram(cfg, key)
    st_rnd = init_oram(cfg, key)

    seq_step = jax.jit(
        lambda st, idxs, nl, ops, pm_l: oram_access_batch(
            cfg, st, idxs, nl, ops, kv_fn, pm_leaves=pm_l if pm else None),
    )

    def rnd_fn(st, idxs, nl, dl, codes, vals, pm_nl, pm_dl, axis_name=None):
        return oram_round(
            cfg, st, idxs, nl, dl, kv_apply_batch(cfg, idxs, codes, vals),
            axis_name=axis_name,
            pm_new_leaves=pm_nl if pm else None,
            pm_dummy_leaves=pm_dl if pm else None,
        )

    if c.get("shards"):
        import functools

        from jax.sharding import PartitionSpec as P

        from grapevine_tpu.parallel.mesh import (
            TREE_AXIS,
            _oram_specs,
            make_mesh,
        )

        if len(jax.devices()) < c["shards"]:
            pytest.skip("needs a multi-device mesh")
        mesh = make_mesh(jax.devices()[: c["shards"]])
        specs = _oram_specs()
        rnd_step = jax.jit(jax.shard_map(
            functools.partial(rnd_fn, axis_name=TREE_AXIS), mesh=mesh,
            in_specs=(specs,) + (P(),) * 7,
            out_specs=(specs, P(), P()), check_vma=False,
        ))
    else:
        rnd_step = jax.jit(rnd_fn)

    rkey = jax.random.PRNGKey(42)
    stash_max = 0
    n_batches = c.get("n_batches", 8)
    for bi, (idxs, codes, vals) in enumerate(
        _random_kv_batches(cfg, n_batches, batch, 7, c.get("p_write", 0.4))
    ):
        rkey, *ks = jax.random.split(rkey, 7)

        def draw(k, n):
            return jax.random.bits(k, (batch,), U32) & U32(n - 1)

        il = cfg.leaves if pm is None else 1 << pm.inner_height
        nl1, nl2, dl = (draw(k, cfg.leaves) for k in ks[:3])
        pm1, pm2, pm3 = (draw(k, il) for k in ks[3:])
        ops = (jnp.asarray(codes), jnp.asarray(vals))
        st_seq, out_s, _ = seq_step(st_seq, jnp.asarray(idxs), nl1, ops, pm1)
        st_rnd, out_r, leaves = rnd_step(
            st_rnd, jnp.asarray(idxs), nl2, dl, jnp.asarray(codes),
            jnp.asarray(vals), pm2, pm3,
        )
        np.testing.assert_array_equal(
            np.asarray(out_s["present"]), np.asarray(out_r["present"]), f"batch {bi}"
        )
        np.testing.assert_array_equal(
            np.asarray(out_s["value"]), np.asarray(out_r["value"]), f"batch {bi}"
        )
        assert np.asarray(leaves).shape == ((batch, 2) if pm else (batch,))
        assert np.all(np.asarray(leaves).reshape(batch, -1)[:, 0] < cfg.leaves)
        stash_max = max(stash_max, int(stash_occupancy(st_rnd)))

    assert int(st_seq.overflow) == 0 and int(st_rnd.overflow) == 0
    assert stash_max <= c.get("parent_stash", 0), (
        f"{case}: the dense round left {stash_max} blocks in the stash "
        f"where the parent's per-path round left {c.get('parent_stash', 0)}"
    )
    # identical logical content: the same live blocks with the same
    # values, wherever each state placed them
    assert logical_block_map(cfg, st_seq) == logical_block_map(cfg, st_rnd)
    _on_own_path(cfg, st_rnd, np.asarray(read_table(cfg, st_rnd.posmap)))
    # read back every index through the sequential path on both states
    all_idx = jnp.arange(blocks, dtype=U32)
    zeros = jnp.zeros((blocks, cfg.value_words), U32)
    ops = (jnp.full((blocks,), OP_READ, U32), zeros)
    nl = jax.random.bits(jax.random.PRNGKey(9), (blocks,), U32) & U32(
        cfg.leaves - 1
    )
    pml = nl & U32((1 << pm.inner_height) - 1) if pm else None
    _, back_s, _ = oram_access_batch(cfg, st_seq, all_idx, nl, ops, kv_fn,
                                     pm_leaves=pml)
    _, back_r, _ = oram_access_batch(cfg, st_rnd, all_idx, nl, ops, kv_fn,
                                     pm_leaves=pml)
    np.testing.assert_array_equal(np.asarray(back_s["present"]), np.asarray(back_r["present"]))
    np.testing.assert_array_equal(np.asarray(back_s["value"]), np.asarray(back_r["value"]))


def test_occurrence_masks():
    idxs = jnp.asarray([3, 5, 3, 9, 5, 3, 7], U32)
    first, last, chain = occurrence_masks(idxs, dummy_index=9)  # 9 = dummy here
    np.testing.assert_array_equal(
        np.asarray(first), [True, True, False, False, False, False, True]
    )
    np.testing.assert_array_equal(
        np.asarray(last), [False, False, False, False, True, True, True]
    )
    # [3,5,3,9,5,3,7]: same-key ops share the first occurrence's slot;
    # the dummy (9) keeps its own
    np.testing.assert_array_equal(np.asarray(chain), [0, 1, 0, 3, 1, 0, 6])


def test_occurrence_masks_equal_a_python_loop():
    """The [B,B] dedup against a plain loop on random index streams
    with duplicates and dummies, including B=1."""
    rng = np.random.default_rng(17)
    sizes = [1, 2, 5, 8, 16, 32]  # fixed shapes: bounded compile count
    for trial in range(24):
        b = sizes[trial % len(sizes)]
        dummy = 64
        idxs = rng.integers(0, 6, b).astype(np.uint32)
        idxs[rng.random(b) < 0.25] = dummy
        first, last, chain = map(
            np.asarray, occurrence_masks(jnp.asarray(idxs), dummy))
        for i in range(b):
            same = [j for j in range(b)
                    if idxs[i] != dummy and idxs[j] == idxs[i]]
            assert first[i] == (bool(same) and same[0] == i), trial
            assert last[i] == (bool(same) and same[-1] == i), trial
            assert chain[i] == (same[0] if same else i), trial


def _assign_evictions_by_gathers(cfg, valid, wleaf, bucket_map, n_rows,
                                 dense_levels):
    """The placement as it stood before PR 38, kept as the reference
    `_assign_evictions` is held to bit for bit: an argsort and two
    gathers through its permutation, per level the rank base read back
    by a gather at each row's segment start (``ecum[start]``), two
    inverse-permutation scatters. Same greedy pass, same result; the
    per-element gathers and the second scatter are what the round no
    longer pays for."""
    h, z = cfg.height, cfg.bucket_slots
    w = valid.shape[0]
    nslots = n_rows * z
    skey = jnp.where(valid, wleaf, U32(0xFFFFFFFF))
    eperm = jnp.argsort(skey)
    sleaf = skey[eperm]
    svalid = valid[eperm]
    iota_w = jnp.arange(w, dtype=jnp.int32)
    placed = jnp.zeros((w,), jnp.bool_)
    slot_tgt_s = jnp.full((w,), nslots, U32)
    bleaf = jnp.minimum(sleaf, U32(cfg.leaves - 1))
    for level in range(h, -1, -1):
        bid = bleaf >> U32(h - level)
        hb = (U32(1) << U32(level)) - U32(1) + bid
        elig = svalid & ~placed
        if level < dense_levels:
            tgt = hb
        else:
            tgt = bucket_map[jnp.minimum(hb, U32(cfg.n_buckets_padded - 1))]
            elig = elig & (tgt != U32(n_rows))
        bnd = jnp.concatenate([jnp.ones((1,), jnp.bool_), bid[1:] != bid[:-1]])
        ecum = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(elig.astype(jnp.int32))[:-1]]
        )
        start = jax.lax.cummax(jnp.where(bnd, iota_w, 0))
        rank = jnp.maximum(ecum - ecum[start], 0)
        chosen = elig & (rank < z)
        slot_tgt_s = jnp.where(chosen, tgt * U32(z) + rank.astype(U32), slot_tgt_s)
        placed = placed | chosen
    slot_tgt = jnp.full((w,), nslots, U32).at[eperm].set(
        slot_tgt_s, unique_indices=True
    )
    placed = jnp.zeros((w,), jnp.bool_).at[eperm].set(placed, unique_indices=True)
    return slot_tgt, placed


_EVICT_H = 4


def _eviction_case(z, dense_levels, rows):
    """(cfg, valid, wleaf, bucket_map, n_rows) of one placement: a
    height-4 tree, six fetched paths under ``dense_levels`` levels held
    whole (the owner map and output rows as `oram_round` lays them out
    with no cache), and a 160-row working set of the named kind."""
    cfg = OramConfig(height=_EVICT_H, value_words=1, bucket_slots=z)
    rng = np.random.default_rng(38)
    b, w, le = 6, 160, dense_levels
    nd, nsp = (1 << le) - 1, cfg.path_len - le
    n_rows = nd + b * nsp
    path_leaves = rng.integers(0, cfg.leaves, b)
    paths = jax.vmap(lambda lf: path_bucket_indices(cfg, lf))(
        jnp.asarray(path_leaves, U32)
    )
    bucket_map = _bucket_owner_map(
        cfg, paths[:, le:].reshape(-1),
        U32(nd) + jnp.arange(b * nsp, dtype=U32), n_rows,
    )
    wleaf = rng.integers(0, cfg.leaves, w)
    valid = rng.random(w) < 0.6
    if rows == "one_leaf":
        # duplicates of one leaf: one long run in sorted order, far more
        # candidates than the Z slots of any bucket on that path
        wleaf[: w // 2] = path_leaves[0]
        valid[: w // 2] = True
    elif rows == "crowded":
        # every row live on four leaves: more than Z candidates a bucket
        # at every level, the leaf level included
        wleaf = rng.choice(path_leaves[:4], w)
        valid[:] = True
    elif rows == "none_valid":
        valid[:] = False
    elif rows == "all_valid":
        valid[:] = True
    else:
        assert rows == "mixed", rows
    return cfg, jnp.asarray(valid), jnp.asarray(wleaf, U32), bucket_map, n_rows


@pytest.mark.parametrize(
    "rows", ["mixed", "one_leaf", "crowded", "none_valid", "all_valid"]
)
@pytest.mark.parametrize("z", [2, 4])
@pytest.mark.parametrize("dense_levels", [0, 2, _EVICT_H + 1])
def test_assign_evictions_bit_identical_to_gather_form(
    dense_levels, z, rows
):
    """PR 38: ranks from scans alone, the sorted keys from the sort's
    own payload and one back-scatter place every row exactly where the
    gather formulation did: ``slot_tgt`` and ``placed`` bit for bit."""
    cfg, valid, wleaf, bucket_map, n_rows = _eviction_case(z, dense_levels, rows)
    args = (cfg, valid, wleaf, bucket_map, n_rows, dense_levels)
    want_tgt, want_placed = _assign_evictions_by_gathers(*args)
    got_tgt, got_placed = _assign_evictions(*args)
    np.testing.assert_array_equal(np.asarray(got_tgt), np.asarray(want_tgt))
    np.testing.assert_array_equal(np.asarray(got_placed), np.asarray(want_placed))
    # the case is the one its name says, and the placement is a placement
    n_valid, n_placed = int(valid.sum()), int(want_placed.sum())
    tgt = np.asarray(want_tgt)[np.asarray(want_placed)]
    assert len(set(tgt.tolist())) == n_placed and (tgt < n_rows * z).all()
    if rows == "none_valid":
        assert n_placed == 0
    elif rows in ("crowded", "one_leaf"):
        assert 0 < n_placed < n_valid  # some bucket was over-subscribed
    if rows == "crowded" and dense_levels == _EVICT_H + 1:
        # every bucket on the four leaves' paths is full at every level
        assert n_placed % z == 0 and n_placed >= (cfg.path_len + 3) * z


@pytest.mark.parametrize("dense_levels", [0, 2, _EVICT_H + 1])
def test_assign_evictions_holds_no_gather_a_scan_can_replace(dense_levels):
    """The traced placement (xla sort) holds exactly one ``gather`` per
    per-path level, the ``bucket_map`` lookup, and none where every
    level is dense; one ``scatter``, the inverse permutation of the
    epilogue; one ``sort``. A per-element gather through the sort's
    permutation or at a segment start cannot come back unnoticed."""
    from grapevine_tpu.analysis.jaxpr_walk import census

    cfg, valid, wleaf, bucket_map, n_rows = _eviction_case(4, dense_levels, "mixed")
    counts = census(jax.make_jaxpr(
        lambda v, lf, m: _assign_evictions(
            cfg, v, lf, m, n_rows, dense_levels)
    )(valid, wleaf, bucket_map))
    assert counts["gather"] == cfg.path_len - dense_levels
    assert counts["scatter"] == 1
    assert counts["sort"] == 1
    assert counts["cumsum"] == counts["cummax"] == cfg.path_len
    assert not any(name.startswith("dynamic") for name in counts)


# ---- phase-major engine vs oracle -------------------------------------

SMALL = GrapevineConfig(bucket_cipher_rounds=0, 
    max_messages=64,
    max_recipients=8,
    mailbox_cap=4,
    batch_size=8,
    stash_size=96,
)


def key(n: int) -> bytes:
    return bytes([n & 0xFF, (n & 0xFF) ^ 0x5A, 1 + (n >> 8)]) + b"\x01" * 29


def req(rt, auth, msg_id=C.ZERO_MSG_ID, recipient=C.ZERO_PUBKEY, pl=None, tag=0):
    return QueryRequest(
        request_type=rt,
        auth_identity=auth,
        auth_signature=b"\x01" * C.SIGNATURE_SIZE,
        record=RequestRecord(
            msg_id=msg_id,
            recipient=recipient,
            payload=pl if pl is not None else bytes([tag & 0xFF]) * C.PAYLOAD_SIZE,
        ),
    )


def assert_responses_equal(dev, ora, ctx=""):
    assert dev.status_code == ora.status_code, f"{ctx}: status {dev.status_code} != {ora.status_code}"
    assert dev.record.msg_id == ora.record.msg_id, f"{ctx}: id"
    assert dev.record.sender == ora.record.sender, f"{ctx}: sender"
    assert dev.record.recipient == ora.record.recipient, f"{ctx}: recipient"
    assert dev.record.payload == ora.record.payload, f"{ctx}: payload"
    assert dev.record.timestamp == ora.record.timestamp, f"{ctx}: ts"


def test_round_engine_matches_batch_oracle():
    """Random multi-op batches (with same-key hazards): round engine must
    agree with the oracle's phase-major handle_batch on everything."""
    _run_engine_vs_oracle(SMALL, n_steps=30)


def test_round_engine_matches_batch_oracle_with_bucket_cipher():
    """Same harness with the at-rest bucket cipher enabled (the shipped
    default): randomized CRUD through encrypted trees must stay
    oracle-identical."""
    import dataclasses

    cfg = dataclasses.replace(SMALL, bucket_cipher_rounds=8)
    _run_engine_vs_oracle(cfg, n_steps=10)


def test_round_engine_matches_batch_oracle_density4():
    """tree_density=4 — the max-capacity-per-HBM-byte shape used by the
    2^22 bench sweep and the 2^24 pod config (tests/test_capacity.py):
    randomized CRUD, then a full expiry sweep, must stay
    oracle-identical at 4x blocks per leaf."""
    import dataclasses

    cfg = dataclasses.replace(SMALL, tree_density=4)
    engine, oracle, t = _run_engine_vs_oracle(cfg, n_steps=12)
    evicted_dev = engine.expire(t + 1000, period=10)
    evicted_ora = oracle.expire(t + 1000, period=10)
    assert evicted_dev == evicted_ora
    assert engine.message_count() == oracle.message_count() == 0
    assert engine.recipient_count() == oracle.recipient_count() == 0


#: the shape of ``chipshare-2p20-r2p16`` (benchmarks/configs) at a size
#: the CPU holds: a mailbox tree taller than the batch covers, so a
#: mailbox round keeps per-path levels under its dense ones
TALL_MAILBOX = GrapevineConfig(bucket_cipher_rounds=0,
    max_messages=1024,
    max_recipients=512,
    mailbox_cap=4,
    batch_size=8,
    stash_size=128,
)


def _run_tall_mailbox_campaign(cfg):
    from grapevine_tpu.engine.state import EngineConfig

    mb = EngineConfig.from_config(cfg).mb
    accesses = cfg.batch_size * cfg.resolved_mailbox_choices
    assert mb.path_len - mb.dense_levels(accesses) >= 2
    assert accesses < mb.leaves  # the round's paths do not all collide
    # hundreds of identities: the mailbox paths of a round differ, the
    # per-path levels evict for real and the mailbox stash takes spill
    engine, oracle, _ = _run_engine_vs_oracle(cfg, n_steps=24, n_idents=300)
    assert engine.recipient_count() == oracle.recipient_count() > 40


def test_round_engine_matches_batch_oracle_on_a_tall_mailbox_tree():
    """Per-path levels under dense ones in the MAILBOX tree (every other
    campaign here covers its mailbox tree whole): randomized CRUD over
    some hundreds of mailboxes must stay oracle-identical."""
    _run_tall_mailbox_campaign(TALL_MAILBOX)


def test_round_engine_matches_batch_oracle_on_a_tall_mailbox_tree_with_cipher():
    import dataclasses

    _run_tall_mailbox_campaign(
        dataclasses.replace(TALL_MAILBOX, bucket_cipher_rounds=8))


def _assert_mailbox_pad_words_are_zero(engine):
    """The stored mailbox row's words past its Z*V block words are
    zeros in plaintext, in the tree plane (decrypted) and in the
    tree-top cache plane."""
    from grapevine_tpu.testing.compare import logical_tree_planes

    mb = engine.ecfg.mb
    assert (mb.val_row_words, mb.stored_row_words) == (6080, 6144)
    _, val, _ = logical_tree_planes(mb, engine.state.mb)
    assert val.shape == (mb.n_buckets_padded, 6144)
    assert not val[:, 6080:].any()
    cache = np.asarray(engine.state.mb.cache_val)
    assert cache.shape == (mb.cache_buckets, 6144)
    assert not cache[:, 6080:].any()


@pytest.mark.parametrize(
    "cipher_rounds,shards", [(0, 1), (8, 1), (0, 4), (8, 4)]
)
def test_round_engine_matches_batch_oracle_at_the_padded_mailbox_row(
    cipher_rounds, shards
):
    """The tall mailbox tree at the real mailbox row (cap 62: 4 x 1,520
    = 6,080 block words, stored as 6,144 since PR 44), cipher off and
    on, on one device and on the CPU mesh (the local shard is
    ``[n/4, 6144]``, the cipher runs after the psum): oracle equality,
    and the pad words zero in plaintext after the rounds and after a
    sweep that expires every record."""
    import dataclasses

    from grapevine_tpu.testing.compare import logical_tree_planes

    cfg = dataclasses.replace(
        TALL_MAILBOX, mailbox_cap=62, bucket_cipher_rounds=cipher_rounds,
        shards=shards,
    )
    engine, oracle, t = _run_engine_vs_oracle(cfg, n_steps=16, n_idents=300)
    assert engine.recipient_count() == oracle.recipient_count() > 15
    _assert_mailbox_pad_words_are_zero(engine)
    _, blocks, _ = logical_tree_planes(engine.ecfg.mb, engine.state.mb)
    assert blocks[:, :6080].any()  # mailboxes rest in the tree
    assert engine.expire(t + 5, period=3) == oracle.expire(t + 5, period=3)
    assert engine.message_count() == oracle.message_count()
    _assert_mailbox_pad_words_are_zero(engine)
    engine.expire(t + 1000, period=10)
    assert engine.message_count() == 0 and engine.recipient_count() == 0


def _run_engine_vs_oracle(cfg, n_steps, n_idents=5):
    engine = GrapevineEngine(cfg, seed=3)
    oracle = ReferenceEngine(config=cfg, rng=random.Random(99))
    rng = random.Random(1234)
    idents = [key(i + 1) for i in range(n_idents)]
    live_ids: list[tuple[bytes, bytes, bytes]] = []

    t = NOW
    for step_no in range(n_steps):
        t += rng.randrange(3)
        n_ops = rng.randrange(1, cfg.batch_size + 1)
        reqs = []
        for _ in range(n_ops):
            c = rng.random()
            if c < 0.35 or not live_ids:
                sender, recip = rng.choice(idents), rng.choice(idents)
                reqs.append(req(C.REQUEST_TYPE_CREATE, sender, recipient=recip, tag=rng.randrange(256)))
            elif c < 0.55:
                mid, snd, rcp = rng.choice(live_ids)
                auth = rng.choice([snd, rcp, rng.choice(idents)])
                reqs.append(req(C.REQUEST_TYPE_READ, auth, msg_id=mid))
            elif c < 0.7:
                reqs.append(req(C.REQUEST_TYPE_READ, rng.choice(idents)))
            elif c < 0.8:
                mid, snd, rcp = rng.choice(live_ids)
                reqs.append(req(C.REQUEST_TYPE_UPDATE, rng.choice([snd, rcp]), msg_id=mid, recipient=rcp, tag=rng.randrange(256)))
            elif c < 0.9:
                mid, snd, rcp = rng.choice(live_ids)
                auth = rng.choice([snd, rcp, rng.choice(idents)])
                reqs.append(req(C.REQUEST_TYPE_DELETE, auth, msg_id=mid, recipient=rcp))
            else:
                reqs.append(req(C.REQUEST_TYPE_DELETE, rng.choice(idents)))

        dev_resps = engine.handle_queries(reqs, t)
        forced = [
            dev.record.msg_id
            if r.request_type == C.REQUEST_TYPE_CREATE
            and dev.status_code == C.STATUS_CODE_SUCCESS
            else None
            for r, dev in zip(reqs, dev_resps)
        ]
        ora_resps = oracle.handle_batch(reqs, t, forced)
        for j, (r, dev, ora) in enumerate(zip(reqs, dev_resps, ora_resps)):
            assert_responses_equal(dev, ora, f"step {step_no} slot {j} rt {r.request_type}")
            if ora.status_code == C.STATUS_CODE_SUCCESS:
                if r.request_type == C.REQUEST_TYPE_CREATE:
                    live_ids.append((ora.record.msg_id, ora.record.sender, ora.record.recipient))
                elif r.request_type == C.REQUEST_TYPE_DELETE:
                    live_ids = [e for e in live_ids if e[0] != ora.record.msg_id]

        assert engine.message_count() == oracle.message_count(), f"step {step_no}"
        assert engine.recipient_count() == oracle.recipient_count(), f"step {step_no}"
    assert engine.health()["stash_overflow"] == 0
    return engine, oracle, t


def test_round_engine_single_op_matches_per_op_oracle():
    """For single-op batches, phase-major ≡ per-op semantics — the oracle's
    plain handle_query is the yardstick."""
    cfg = GrapevineConfig(bucket_cipher_rounds=0, 
        max_messages=16, max_recipients=4, mailbox_cap=3, batch_size=1, stash_size=96
    )
    engine = GrapevineEngine(cfg, seed=8)
    oracle = ReferenceEngine(config=cfg, rng=random.Random(5))
    rng = random.Random(77)
    idents = [key(i + 1) for i in range(4)]
    live: list[tuple[bytes, bytes, bytes]] = []
    t = NOW
    for n in range(60):
        t += 1
        c = rng.random()
        if c < 0.45 or not live:
            r = req(C.REQUEST_TYPE_CREATE, rng.choice(idents), recipient=rng.choice(idents), tag=n)
        elif c < 0.65:
            mid, snd, rcp = rng.choice(live)
            r = req(C.REQUEST_TYPE_READ, rng.choice([snd, rcp]), msg_id=mid)
        elif c < 0.8:
            r = req(C.REQUEST_TYPE_READ, rng.choice(idents))
        else:
            r = req(C.REQUEST_TYPE_DELETE, rng.choice(idents))
        (dev,) = engine.handle_queries([r], t)
        forced = (
            dev.record.msg_id
            if r.request_type == C.REQUEST_TYPE_CREATE
            and dev.status_code == C.STATUS_CODE_SUCCESS
            else None
        )
        ora = oracle.handle_query(r, t, forced_msg_id=forced)
        assert_responses_equal(dev, ora, f"op {n}")
        if ora.status_code == C.STATUS_CODE_SUCCESS:
            if r.request_type == C.REQUEST_TYPE_CREATE:
                live.append((ora.record.msg_id, ora.record.sender, ora.record.recipient))
            elif r.request_type == C.REQUEST_TYPE_DELETE:
                live = [e for e in live if e[0] != ora.record.msg_id]


def test_round_engine_rud_transcripts_bit_identical():
    """grapevine.proto:120-122 for the phase-major engine: R/U/D of the
    same message from identically-seeded engines → identical transcripts."""
    a, b = key(7), key(8)

    def fresh():
        e = GrapevineEngine(SMALL, seed=11)
        (r,) = e.handle_queries([req(C.REQUEST_TYPE_CREATE, a, recipient=b)], NOW)
        assert r.status_code == C.STATUS_CODE_SUCCESS
        return e, r.record.msg_id

    transcripts = {}
    for rt in (C.REQUEST_TYPE_READ, C.REQUEST_TYPE_UPDATE, C.REQUEST_TYPE_DELETE):
        e, mid = fresh()
        _, tr = e.handle_queries_with_transcript(
            [req(rt, b, msg_id=mid, recipient=b)], NOW + 1
        )
        transcripts[rt] = tr
    assert np.array_equal(transcripts[C.REQUEST_TYPE_READ], transcripts[C.REQUEST_TYPE_UPDATE])
    assert np.array_equal(transcripts[C.REQUEST_TYPE_READ], transcripts[C.REQUEST_TYPE_DELETE])

    # failed ops indistinguishable from successful ones
    e, mid = fresh()
    _, tr_bad = e.handle_queries_with_transcript(
        [req(C.REQUEST_TYPE_DELETE, key(9), msg_id=mid, recipient=b)], NOW + 1
    )
    assert np.array_equal(transcripts[C.REQUEST_TYPE_DELETE], tr_bad)


def test_duplicate_key_ops_get_uncorrelated_leaves():
    """Two ops on the same message in one batch must not show the same
    records-ORAM leaf (the dedup dummy-fetch rule in oram_round)."""
    e = GrapevineEngine(SMALL, seed=13)
    a, b = key(1), key(2)
    (r,) = e.handle_queries([req(C.REQUEST_TYPE_CREATE, a, recipient=b)], NOW)
    mid = r.record.msg_id
    resps, tr = e.handle_queries_with_transcript(
        [req(C.REQUEST_TYPE_READ, b, msg_id=mid), req(C.REQUEST_TYPE_READ, b, msg_id=mid)],
        NOW + 1,
    )
    assert all(x.status_code == C.STATUS_CODE_SUCCESS for x in resps)
    assert resps[0].record.payload == resps[1].record.payload
    # same mailbox bucket(s) and same record block in one round: the
    # fetched leaves are an independent real draw + an independent dummy
    # draw per column ([a_0..a_{D-1}, b, c_0..c_{D-1}]). A full-row
    # collision has probability (1/leaves)^cols; seed 13 avoids it.
    assert not np.array_equal(tr[0], tr[1])


def test_phase_major_divergence_is_as_documented():
    """The one visible batch hazard: a CREATE cannot reuse a record slot
    freed by an explicit DELETE in the same batch (TOO_MANY_MESSAGES),
    but can in the next batch — and the oracle agrees."""
    cfg = GrapevineConfig(bucket_cipher_rounds=0, 
        max_messages=2, max_recipients=4, mailbox_cap=2, batch_size=4, stash_size=96
    )
    engine = GrapevineEngine(cfg, seed=2)
    oracle = ReferenceEngine(config=cfg, rng=random.Random(3))
    a, b = key(1), key(2)

    def run(reqs, t):
        dev = engine.handle_queries(reqs, t)
        forced = [
            d.record.msg_id
            if r.request_type == C.REQUEST_TYPE_CREATE and d.status_code == C.STATUS_CODE_SUCCESS
            else None
            for r, d in zip(reqs, dev)
        ]
        ora = oracle.handle_batch(reqs, t, forced)
        for i, (d, o) in enumerate(zip(dev, ora)):
            assert_responses_equal(d, o, f"slot {i}")
        return dev

    r1 = run([req(C.REQUEST_TYPE_CREATE, a, recipient=b), req(C.REQUEST_TYPE_CREATE, a, recipient=b)], NOW)
    assert [x.status_code for x in r1] == [C.STATUS_CODE_SUCCESS] * 2
    mid = r1[0].record.msg_id
    # delete + create in ONE batch: the create sees a full bus
    r2 = run(
        [req(C.REQUEST_TYPE_DELETE, b, msg_id=mid, recipient=b),
         req(C.REQUEST_TYPE_CREATE, a, recipient=b)],
        NOW + 1,
    )
    assert r2[0].status_code == C.STATUS_CODE_SUCCESS
    assert r2[1].status_code == C.STATUS_CODE_TOO_MANY_MESSAGES
    # next batch: the freed slot is available
    r3 = run([req(C.REQUEST_TYPE_CREATE, a, recipient=b)], NOW + 2)
    assert r3[0].status_code == C.STATUS_CODE_SUCCESS
