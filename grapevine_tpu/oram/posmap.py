"""Pluggable position maps: flat private table vs recursive position ORAM.

The position map is the recipient→leaf oracle every Path-ORAM access
starts from. Until PR 7 it was hard-coded as a flat u32[blocks+1] array
inside ``OramState`` — private working memory (the EPC analog, see the
threat model in path_oram.py) that must live resident, be sealed into
every checkpoint, and be replicated per shard. At 2^24 records that is
64 MiB (cheap); at 2^30 it is 4 GiB per replica, which caps capacity at
one HBM/host (ROADMAP open item 5).

This module makes the map a subsystem with two implementations behind
one constant-shape contract (``GrapevineConfig.posmap_impl``, the
PR-3/PR-5 selectable-impl playbook):

- **flat** — today's array, bit-for-bit: ``lookup`` is one private
  gather, ``remap`` one private scatter.
- **recursive** — the classic recursive construction (Path ORAM
  §"recursive construction", arXiv:1202.5150; the Pyramid scheme's
  hierarchical layout, arXiv:1712.07882) re-platformed as shape-static
  JAX, one level deep: ``k = entries_per_block`` position entries are
  packed per block of a smaller *internal* Path ORAM whose bucket tree
  lives in (encrypted, shardable) HBM like the payload tree. Only the
  internal ORAM's own flat map + stash stay resident — ``blocks/k``
  entries instead of ``blocks`` — so private position-handling memory
  shrinks by ``k`` (see :func:`posmap_private_bytes`; the 2^30 sizing
  table is OPERATIONS.md §13).

Obliviousness: a batch of B outer accesses resolves through EXACTLY B
internal-ORAM accesses every round — outer dummies become internal
dummies, and duplicate internal blocks are deduplicated by the internal
round's own occurrence machinery (dummy re-fetches of fresh uniform
paths), so every internal transcript entry is an independent uniform
internal leaf. Recursion depth and lookup batch shape are static
geometry; the access *count* per round is a constant, never a function
of which indices were queried (CI-audited in tests/test_posmap.py: the
traced lookup has a B-independent gather/scatter census and no control
flow). The internal leaves are returned to the caller and ride the
public transcript into the leak monitor (obs/leakmon.py ``*_pm``
streams).

Bit-identity contract with the flat map (tests/test_posmap_ab.py):
responses AND the final payload-tree state are bit-identical
flat↔recursive, because (a) the initial table is generated from the
same PRNG key by the same draw, (b) every lookup returns the
round-start entry and every remap commits the round's last write —
exactly the flat read/scatter semantics — and (c) the payload tree
additionally carries a per-slot leaf-metadata plane (recursive mode
only) so eviction resolves working-set leaves without consulting the
map, with values equal to the flat ``working_leaves`` gather by the
posmap↔metadata invariant (maintained at every insert/remap).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..obs.phases import device_phase

U32 = jnp.uint32

#: refuse recursion below this block count: the internal tree needs at
#: least 4 blocks for a height-1 two-per-leaf layout, and a map this
#: small is resident noise anyway
MIN_RECURSIVE_BLOCKS = 8

#: k cap: 2^10 entries = 4 KiB internal block values — the payload
#: bucket-row scale XLA layouts are already tuned for
MAX_ENTRIES_PER_BLOCK_LOG2 = 10


@dataclasses.dataclass(frozen=True)
class PosMapSpec:
    """Static geometry of a *recursive* position map.

    Hashable and embedded in ``OramConfig.posmap``, so it is covered by
    jit static arguments, ``repr``-based checkpoint geometry
    fingerprints (engine/checkpoint.py — a flat checkpoint can never
    silently restore into a recursive engine), and the journal AAD.
    """

    #: k: position entries packed per internal-ORAM block
    entries_per_block: int
    #: internal block space = outer blocks / k
    inner_blocks: int
    #: internal tree height (leaves = 2**inner_height; two blocks per
    #: leaf — the tree_density=2 shape the payload trees default to)
    inner_height: int
    inner_bucket_slots: int = 4
    inner_stash_size: int = 96
    #: at-rest cipher rounds for the internal bucket tree (inherits the
    #: outer tree's setting; the internal map holds future fetch paths,
    #: so it is at least as snapshot-sensitive as payload)
    inner_cipher_rounds: int = 0
    #: tree-top cache depth for the INTERNAL bucket tree (ROADMAP item
    #: 1 ∘ item 5 composition: the internal tree's top levels are
    #: touched every round too — path_oram.OramConfig.top_cache_levels,
    #: clamped to inner_height by derive_posmap_spec)
    inner_top_cache_levels: int = 0

    @property
    def inner_leaves(self) -> int:
        return 1 << self.inner_height


def derive_posmap_spec(
    blocks: int,
    stash_size: int = 96,
    cipher_rounds: int = 0,
    entries_per_block: int | None = None,
    top_cache_levels: int = 0,
) -> PosMapSpec:
    """Auto-derive recursion geometry from capacity.

    ``k`` targets ~sqrt(blocks) (capped at 2^10): private memory shrinks
    by k while internal block values stay bucket-row-sized. Explicit
    ``entries_per_block`` overrides (power of two, blocks/k >= 4).
    """
    if blocks < MIN_RECURSIVE_BLOCKS or blocks & (blocks - 1):
        raise ValueError(
            f"recursive posmap needs a power-of-two block space >= "
            f"{MIN_RECURSIVE_BLOCKS}, got {blocks} — use posmap_impl='flat' "
            "at this capacity"
        )
    if entries_per_block is None:
        k = 1 << max(1, min(MAX_ENTRIES_PER_BLOCK_LOG2,
                            (blocks.bit_length() - 1) // 2))
        while blocks // k < 4:
            k >>= 1
    else:
        k = entries_per_block
        if k < 2 or k & (k - 1) or blocks // k < 4 or blocks % k:
            raise ValueError(
                f"entries_per_block must be a power of two >= 2 with "
                f"blocks/k >= 4, got k={k} at blocks={blocks}"
            )
    inner_blocks = blocks // k
    ih = max(1, inner_blocks.bit_length() - 2)
    return PosMapSpec(
        entries_per_block=k,
        inner_blocks=inner_blocks,
        inner_height=ih,
        inner_stash_size=stash_size,
        inner_cipher_rounds=cipher_rounds,
        inner_top_cache_levels=min(top_cache_levels, ih),
    )


def inner_oram_config(spec: PosMapSpec):
    """The internal Path ORAM's OramConfig (always a flat-posmap ORAM —
    one level of recursion; cipher impl pinned to "jnp": internal rows
    are k words, far below the sizes the Pallas kernels pay off at)."""
    from .path_oram import OramConfig

    return OramConfig(
        height=spec.inner_height,
        value_words=spec.entries_per_block,
        bucket_slots=spec.inner_bucket_slots,
        stash_size=spec.inner_stash_size,
        cipher_rounds=spec.inner_cipher_rounds,
        cipher_impl="jnp",
        n_blocks=spec.inner_blocks,
        top_cache_levels=spec.inner_top_cache_levels,
    )


class RecursivePosMapState(NamedTuple):
    """Recursive position-map state pytree.

    ``inner``: the internal Path ORAM (an OramState whose block values
    are packed entry vectors). ``dummy_entry``: the throwaway slot flat
    keeps at ``table[blocks]`` — read/remapped by op-major dummy
    accesses, reproduced here so flat↔recursive stay bit-identical."""

    inner: object  # OramState
    dummy_entry: jax.Array  # u32 scalar


def _flat_table(cfg, key: jax.Array) -> jax.Array:
    """The flat table draw — THE one place the initial position values
    come from, under either impl (bit-identity anchor)."""
    return jax.random.randint(
        key, (cfg.blocks + 1,), 0, cfg.leaves, dtype=jnp.int32
    ).astype(U32)


def init_posmap(cfg, key: jax.Array):
    """Initial position-map pytree for an ``OramConfig``.

    Flat: the u32[blocks+1] table exactly as before. Recursive: the
    same table values packed k-per-block into an internal Path ORAM
    initialized FULL — every internal block placed at a secret uniformly
    random leaf-slot (a random permutation over two-per-leaf slots:
    marginally uniform, jointly exchangeable under index relabeling, so
    the first-fetch transcript stays data-independent), with the
    internal flat map set to match. With the internal cipher on, the
    pre-placed rows are encrypted under epoch 1 before they ever sit in
    HBM (epoch-0 plaintext would hand a snapshot the initial map)."""
    if cfg.posmap is None:
        return _flat_table(cfg, key)
    from .path_oram import cipher_rows, init_oram, stored_rows

    spec = cfg.posmap
    icfg = inner_oram_config(spec)
    k = spec.entries_per_block
    nb = spec.inner_blocks
    z = icfg.bucket_slots
    k_tab, k_inner, k_perm = (
        key, jax.random.fold_in(key, 1), jax.random.fold_in(key, 2)
    )
    table = _flat_table(cfg, k_tab)
    inner = init_oram(icfg, k_inner)

    vals = table[: cfg.blocks].reshape(nb, k)  # blocks = nb * k exactly
    perm = jax.random.permutation(k_perm, nb).astype(U32)  # slot s ↦ block
    density = nb // icfg.leaves  # 2 by construction (inner_height = lg nb - 1)
    slot_iota = jnp.arange(nb, dtype=U32)
    leaf_of_slot = slot_iota // U32(density)
    hb = (U32(1) << U32(icfg.height)) - U32(1) + leaf_of_slot  # leaf buckets
    flat_slot = hb * U32(z) + slot_iota % U32(density)

    tree_idx = inner.tree_idx.at[flat_slot].set(perm, unique_indices=True)
    val_slots = (
        jnp.zeros((icfg.n_buckets_padded * z, k), U32)
        .at[flat_slot]
        .set(vals[perm], unique_indices=True)
    )
    tree_val = stored_rows(
        icfg, val_slots.reshape(icfg.n_buckets_padded, z * k)
    )
    pm = inner.posmap.at[perm].set(leaf_of_slot)

    nonces, epoch = inner.nonces, inner.epoch
    if icfg.encrypted:
        ep1 = jnp.broadcast_to(
            jnp.array([1, 0], U32)[None, :], (icfg.n_buckets_padded, 2)
        )
        buckets = jnp.arange(icfg.n_buckets_padded, dtype=U32)
        enc_idx, enc_val = cipher_rows(
            icfg, inner.cipher_key, buckets, ep1,
            tree_idx.reshape(icfg.n_buckets_padded, z), tree_val,
        )
        tree_idx, tree_val = enc_idx.reshape(-1), enc_val
        nonces, epoch = ep1, jnp.array([2, 0], U32)
    tree_val = tree_val.reshape(inner.tree_val.shape)

    inner = inner._replace(
        tree_idx=tree_idx, tree_val=tree_val, posmap=pm,
        nonces=nonces, epoch=epoch,
    )
    return RecursivePosMapState(inner=inner, dummy_entry=table[cfg.blocks])


def _group_last_slot(idxs, dummy_index):
    """u32[B]: the slot of the round's LAST op on the same (real) index;
    dummies get their own slot — the mirror of ``occurrence_masks``'
    first-occurrence ``chain_slot``, in the same [B,B] form."""
    b = idxs.shape[0]
    slot_iota = jnp.arange(b, dtype=U32)
    is_real = idxs != U32(dummy_index)
    eq = (idxs[:, None] == idxs[None, :]) & is_real[:, None] & is_real[None, :]
    last = U32(b - 1) - jnp.argmax(eq[:, ::-1], axis=1).astype(U32)
    return jnp.where(is_real, last, slot_iota)


#: oblint taint anchors (analysis/oblint.py): the secret inputs of one
#: ``lookup_remap_round`` — the queried indices, every position the map
#: holds (flat table contents, or the whole recursive pytree: internal
#: tree plaintext via its cipher key, internal stash/posmap), the fresh
#: remap/dummy leaves (future fetch paths), and the occurrence masks
#: (functions of the secret indices).
OBLINT_SECRETS = (
    "idxs", "pm_state", "new_leaves", "dummy_leaves",
    "first_occ", "last_occ", "pm_new_leaves", "pm_dummy_leaves",
)


def RANGELINT_BOUNDS(cfg, prefix: str = "pm_state") -> dict:
    """Rangelint input-interval anchors (analysis/rangelint.py) for one
    ``lookup_remap_round`` / ``oram_round`` argument set at geometry
    ``cfg``: queried indices are block ids or the dummy, every leaf
    argument (remap targets, dummy fetches, internal-map remaps) is a
    fresh uniform draw below its tree's leaf count, and the map state
    itself carries the per-plane invariants of
    :func:`path_oram.RANGELINT_BOUNDS`.  The k-entry packing offsets
    (``idx >> lg k``, ``idx & (k-1)``, ``last_slot·k + off``) are then
    *derived* clean from these bounds — the packing-offset audit the
    satellite names."""
    lv = cfg.leaves - 1
    b = {
        "idxs": (0, cfg.dummy_index),
        "new_leaves": (0, lv),
        "dummy_leaves": (0, lv),
    }
    # the map-state pytree: flat = the bare table; recursive = the
    # RecursivePosMapState (inner OramState + dummy_entry)
    if cfg.posmap is None:
        b[prefix] = (0, lv)
    else:
        from .path_oram import OVERFLOW_ROUND_BUDGET

        icfg = inner_oram_config(cfg.posmap)
        il = icfg.leaves - 1
        b["pm_new_leaves"] = (0, il)
        b["pm_dummy_leaves"] = (0, il)
        b[f"{prefix}.inner.posmap"] = (0, il)
        b[f"{prefix}.inner.stash_val"] = (0, lv)
        b[f"{prefix}.inner.cache_val"] = (0, lv)
        b[f"{prefix}.inner.overflow"] = (0, 2**32 - OVERFLOW_ROUND_BUDGET)
        if not icfg.encrypted:
            b[f"{prefix}.inner.tree_val"] = (0, lv)
        b[f"{prefix}.dummy_entry"] = (0, lv)
    return b


def lookup_remap_round(
    cfg,
    pm_state,
    idxs: jax.Array,  # u32[B]; cfg.dummy_index = dummy op
    new_leaves: jax.Array,  # u32[B] remap targets
    dummy_leaves: jax.Array,  # u32[B] leaves for non-first-occurrence ops
    first_occ: jax.Array,  # bool[B] (this op performs the real fetch)
    last_occ: jax.Array,  # bool[B] (this op's remap wins)
    pm_new_leaves: jax.Array | None = None,  # u32[B] internal remaps
    pm_dummy_leaves: jax.Array | None = None,  # u32[B] internal dummies
):
    """Resolve B positions with a fixed access schedule.

    Returns ``(pm_state', leaves u32[B], inner_leaves u32[B] | None)``:
    ``leaves[i]`` is the round-start entry for first occurrences and
    ``dummy_leaves[i]`` otherwise; the last occurrence's ``new_leaves``
    wins each index's remap — exactly the flat semantics.
    ``inner_leaves`` is the internal ORAM's public transcript (None for
    flat)."""
    if cfg.posmap is None:
        with device_phase("posmap"):
            leaves = jnp.where(first_occ, pm_state[idxs], dummy_leaves)
            remap_tgt = jnp.where(last_occ, idxs, U32(cfg.blocks + 1))
            pm2 = pm_state.at[remap_tgt].set(
                new_leaves, mode="drop", unique_indices=True
            )
        return pm2, leaves, None
    if pm_new_leaves is None or pm_dummy_leaves is None:
        raise ValueError(
            "recursive posmap lookup needs pm_new_leaves/pm_dummy_leaves "
            "(fresh uniform internal leaves)"
        )
    from .round import oram_round

    spec = cfg.posmap
    icfg = inner_oram_config(spec)
    k = spec.entries_per_block
    lgk = k.bit_length() - 1
    b = idxs.shape[0]
    is_real = idxs != U32(cfg.dummy_index)
    inner_idxs = jnp.where(is_real, idxs >> lgk, U32(icfg.dummy_index))
    offs = idxs & U32(k - 1)  # garbage for dummies; never committed

    # the internal round commits each internal block's final value at
    # its LAST within-round occurrence — scatter every winning remap
    # onto that row so one committed row carries all of its block's
    # entry writes (distinct outer indices in one block have distinct
    # offsets, so in-bounds targets are unique)
    last_slot = _group_last_slot(inner_idxs, icfg.dummy_index)

    def apply_pm(vals0, present0):
        # vals0 u32[B, k]: each op's internal block at round start —
        # the lookup reads its own offset; remaps overlay the last rows
        looked = jnp.take_along_axis(
            vals0, offs[:, None].astype(jnp.int32), axis=1
        )[:, 0]
        tgt = jnp.where(
            last_occ & is_real, last_slot * U32(k) + offs, U32(b * k)
        )
        final = (
            vals0.reshape(b * k)
            .at[tgt]
            .set(new_leaves, mode="drop", unique_indices=True)
            .reshape(b, k)
        )
        # internal blocks are created full at init and never leave
        return looked, final, jnp.ones((b,), jnp.bool_)

    with device_phase("posmap"):
        inner2, looked, inner_leaves = oram_round(
            icfg, pm_state.inner, inner_idxs, pm_new_leaves,
            pm_dummy_leaves, apply_pm,
        )
    # looked-up entries come out of the (decrypted) internal tree, which
    # interval reasoning must treat as opaque; the mask re-establishes
    # the `< leaves` invariant the entries were stored under (identity
    # for honest state — leaves is a power of two — and defense in depth
    # against corrupt ciphertext steering a path fetch out of range)
    looked = looked & U32(cfg.leaves - 1)
    leaves = jnp.where(first_occ, looked, dummy_leaves)
    return pm_state._replace(inner=inner2), leaves, inner_leaves


def lookup_remap_one(cfg, pm_state, idx, new_leaf, pm_leaf=None):
    """Single-access lookup+remap (the op-major engine's path).

    Returns ``(pm_state', leaf, inner_leaf | None)``. Flat: the exact
    legacy gather/scatter pair. Recursive: ONE internal ORAM access per
    outer access, dummy-for-dummy (fixed schedule); the throwaway
    ``dummy_entry`` reproduces flat's ``table[blocks]`` read/remap."""
    if cfg.posmap is None:
        leaf = pm_state[idx]
        return pm_state.at[idx].set(new_leaf), leaf, None
    if pm_leaf is None:
        raise ValueError(
            "recursive posmap lookup needs pm_leaf (a fresh uniform "
            "internal leaf)"
        )
    from .path_oram import oram_access

    spec = cfg.posmap
    icfg = inner_oram_config(spec)
    k = spec.entries_per_block
    lgk = k.bit_length() - 1
    is_dummy = idx == U32(cfg.dummy_index)
    inner_idx = jnp.where(is_dummy, U32(icfg.dummy_index), idx >> lgk)
    off = (idx & U32(k - 1)).astype(jnp.int32)

    def fn(value, present, operand):
        looked = value[off]
        # remap the entry; keep the block, never insert (always present
        # for real indices — the internal tree is initialized full)
        return value.at[off].set(new_leaf), jnp.bool_(True), jnp.bool_(False), looked

    with device_phase("posmap"):
        inner2, looked, inner_leaf = oram_access(
            icfg, pm_state.inner, inner_idx, pm_leaf, None, fn
        )
    # same `< leaves` re-establishment as lookup_remap_round: decrypted
    # internal-tree entries are opaque to interval reasoning
    looked = looked & U32(cfg.leaves - 1)
    leaf = jnp.where(is_dummy, pm_state.dummy_entry, looked)
    dummy2 = jnp.where(is_dummy, new_leaf, pm_state.dummy_entry)
    return (
        pm_state._replace(inner=inner2, dummy_entry=dummy2),
        leaf,
        inner_leaf,
    )


# -- sizing + test/debug views ------------------------------------------


def posmap_private_bytes(cfg) -> int:
    """Resident/replicated position-handling bytes — the part that must
    live in private memory on every replica and shard (flat: the whole
    table; recursive: the internal ORAM's flat map, stash, and scalars
    — its bucket tree is encrypted, shardable HBM storage like the
    payload tree). The capacity acceptance (2^30 at <= 1/64 of flat)
    and the OPERATIONS.md §13 sizing table are computed from this."""
    if cfg.posmap is None:
        return 4 * (cfg.blocks + 1)
    spec = cfg.posmap
    icfg = inner_oram_config(spec)
    s, k = icfg.stash_size, spec.entries_per_block
    table = 4 * (icfg.blocks + 1)
    stash = 4 * s + 4 * s * k  # stash_idx + stash_val + stash_leaf(0)
    scalars = 4 * (1 + 1 + 8 + 2)  # dummy_entry, overflow, key, epoch
    # internal tree-top cache planes are decrypted-resident private
    # state (stash standing), so they count against the private budget
    z = icfg.bucket_slots
    cache = icfg.cache_buckets * (4 * z + 4 * z * k)
    return table + stash + scalars + cache


def posmap_hbm_bytes(cfg) -> int:
    """Shardable HBM bytes the map adds (recursive only): the internal
    bucket tree planes plus the payload tree's leaf-metadata plane."""
    if cfg.posmap is None:
        return 0
    icfg = inner_oram_config(cfg.posmap)
    z, k = icfg.bucket_slots, cfg.posmap.entries_per_block
    inner_tree = icfg.n_buckets_padded * (4 * z + 4 * z * k + 8)
    leaf_plane = 4 * cfg.n_buckets_padded * cfg.bucket_slots
    return inner_tree + leaf_plane


def read_table(cfg, pm_state):
    """TEST/DEBUG: materialize the full logical table u32[blocks] from
    either impl (decrypting the internal tree as needed). Host-side —
    never on the round path."""
    import numpy as np

    if cfg.posmap is None:
        return np.asarray(pm_state)[: cfg.blocks].copy()
    from ..oblivious.bucket_cipher import row_plane_keystreams
    from ..oblivious.primitives import SENTINEL

    spec = cfg.posmap
    icfg = inner_oram_config(spec)
    k, z = spec.entries_per_block, icfg.bucket_slots
    inner = pm_state.inner
    tidx = np.asarray(inner.tree_idx).reshape(-1, z)
    tval = np.asarray(inner.tree_val).reshape(icfg.n_buckets_padded, -1)
    if icfg.encrypted:
        buckets = jnp.arange(icfg.n_buckets_padded, dtype=U32)
        ks_idx, ks_val = row_plane_keystreams(
            inner.cipher_key, buckets, inner.nonces, z, icfg.row_words,
            icfg.cipher_rounds,
        )
        tidx = tidx ^ np.asarray(ks_idx)
        tval = tval ^ np.asarray(ks_val)
    out = np.zeros((cfg.blocks,), np.uint32)
    seen = np.zeros((spec.inner_blocks,), bool)
    rows = tval[:, : icfg.val_row_words].reshape(-1, k)
    flat_idx = tidx.reshape(-1)
    live = flat_idx != int(SENTINEL)
    # tree-top cache: cached buckets' HBM rows are stale (decrypt to
    # empty — never written while cached); the authoritative plaintext
    # rows live in the cache planes
    ncache = int(np.asarray(inner.cache_idx).size)
    if ncache:
        live[:ncache] = False
        crows = np.asarray(inner.cache_val)[:, : icfg.val_row_words].reshape(
            -1, k)
        cidx = np.asarray(inner.cache_idx)
        for slot in np.nonzero(cidx != int(SENTINEL))[0]:
            blk = int(cidx[slot])
            out[blk * k: (blk + 1) * k] = crows[slot]
            seen[blk] = True
    for slot in np.nonzero(live)[0]:
        blk = int(flat_idx[slot])
        out[blk * k: (blk + 1) * k] = rows[slot]
        seen[blk] = True
    sidx = np.asarray(inner.stash_idx)
    sval = np.asarray(inner.stash_val)
    for j in np.nonzero(sidx != int(SENTINEL))[0]:
        blk = int(sidx[j])
        out[blk * k: (blk + 1) * k] = sval[j]
        seen[blk] = True
    assert seen.all(), "recursive posmap lost internal blocks"
    return out
