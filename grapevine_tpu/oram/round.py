"""Batched Path-ORAM access rounds: one fetch, N ops, one eviction.

The sequential engine (`oram_access_batch`) commits each access as its own
path fetch → stash scan → evict → write-back, so a B-op batch costs 3·B
dependent HBM round trips — latency-bound on TPU. This module implements
the OPRAM-style *batched round* instead (cf. the batching discussion in
PAPERS.md and SURVEY.md §7 "hard parts" 6):

1. **Dedup + fetch**: each op's path is resolved up front. Duplicate
   logical indices within the round do a *dummy* fetch of a fresh random
   path after the first occurrence — the classic OPRAM conflict trick.
   This is also a security requirement, not just an optimization: if two
   ops on one key both fetched ``posmap[idx]`` the transcript would show
   two identical leaves, correlating ops on the same key. With dedup every
   transcript entry is an independent uniform leaf. The fetch is
   *level-dense above, per-path below*: a level with no more buckets
   than the round has accesses is read whole, once, as a fixed heap
   range (the tree-top cache planes are the first of these levels);
   under those, all B paths are fetched in one gather, and buckets
   shared by several paths are attributed to a single *owner* path slot
   and invalidated elsewhere, so each live block enters the working set
   once (`oram_round` docstring: the layout and why it is safe).
2. **Apply**: slot-order semantics (the documented within-batch commit
   order, SURVEY.md §7.6) are resolved by a fully **vectorized** batch
   callback — there is NO per-op `lax.scan` anywhere in the round. A
   sequential scan body costs ~30-130µs *per iteration* on TPU (profiled;
   it dominated the entire framework), so within-round read-after-write
   chains are instead computed in parallel: the round hands the callback
   each op's *initial* row value + presence (one static [B, W] compare +
   one B-row gather), and the callback resolves same-key chains with
   same-key matrices / segmented scans (see engine/vphases.py and
   oblivious/segmented.py) and returns each op's outputs plus the final
   per-key committed state. The [B, W] compare and row gathers are
   private-working-memory accesses — the same standing the flat position
   map already has (see the threat model in path_oram.py): obliviousness
   is claimed for the HBM bucket-tree transcript; the working set, like
   the stash and position map, is EPC-analog private state. The final
   (value, alive) of each key is scattered back to its working-set row —
   net inserts go to B reserved rows — and eviction proceeds.
3. **Evict**: one level-synchronous greedy pass assigns every working-set
   entry to the deepest fetched bucket on its own path — every bucket of
   a level the batch covers, the buckets the B paths meet below — jointly (an
   entry's path meets each level in exactly one bucket, so levels
   vectorize with no conflicts). Leftovers recompact into the stash; one
   scatter writes the dense range and all owned buckets back (write
   transcript ≡ read transcript).

Net effect per round: 2 large HBM transfers (gather + scatter) per tree
array instead of 2·B small dependent ones, with all decision logic in
O(log B)-depth parallel form.
"""

from __future__ import annotations

import jax

from ..config import on_tpu as _on_tpu
import jax.numpy as jnp

from ..oblivious.primitives import SENTINEL, rank_of
from ..oblivious.radix import radix_rank
from ..oblivious.bucket_cipher import epoch_next
from ..obs.phases import device_phase
from .path_oram import (
    OramConfig,
    OramState,
    _path_gather,
    _path_scatter,
    cipher_rows,
    path_bucket_indices,
    path_slot_indices,
    working_leaves,
)

U32 = jnp.uint32

#: oblint taint anchors (analysis/oblint.py): the secret inputs of one
#: ``oram_round(cfg, state, idxs, new_leaves, dummy_leaves, ...)`` —
#: block indices, every current/future position (posmap contents and the
#: fresh remap/dummy leaves are all future fetch paths), the private
#: stash/cache planes, and the at-rest cipher key (tainting the key is
#: what marks every *decrypted* tree row secret: plaintext is
#: key-derived, ciphertext is public). Argument-name/dotted-path
#: prefixes over the function's signature; tools/check_oblivious.py
#: resolves them against the flattened trace.
OBLINT_SECRETS = (
    "idxs", "new_leaves", "dummy_leaves",
    "pm_new_leaves", "pm_dummy_leaves",
    "state.posmap", "state.stash_idx", "state.stash_val",
    "state.stash_leaf", "state.cache_idx", "state.cache_val",
    "state.cache_leaf", "state.ebuf_idx", "state.ebuf_val",
    "state.ebuf_leaf", "state.cipher_key",
)
# Deliberately NOT secret: ebuf_paths / ebuf_rounds / ebuf_gen /
# fetch_tag — the flush-window bookkeeping is a pure function of the
# public transcript (the fetched leaves and the round counter), and the
# flush cadence must remain derivable from it alone (a flush that
# consulted buffer *contents* would be the leak the seeded
# flush_on_buffer_contents mutant pins).


def occurrence_masks(idxs: jax.Array, dummy_index: int):
    """(first_occ, last_occ, chain_slot) over real (non-dummy) indices.

    first_occ[i]: no earlier op in the round touches the same index —
    this op performs the real path fetch. last_occ[i]: no later op does —
    this op's fresh leaf wins the position-map remap. chain_slot[i]: the
    slot of the round's first op on the same index (dummies get their own
    slot) — the shared chain-buffer slot for within-round read-after-write.

    The classic [B,B]-mask form; `occurrence_masks_sorted` computes the
    identical masks in O(B log B) for the scan engine.
    """
    is_real = idxs != U32(dummy_index)
    eq = (idxs[:, None] == idxs[None, :]) & is_real[:, None] & is_real[None, :]
    b = idxs.shape[0]
    earlier = jnp.tril(jnp.ones((b, b), jnp.bool_), k=-1)
    first_occ = is_real & ~jnp.any(eq & earlier, axis=1)
    last_occ = is_real & ~jnp.any(eq & earlier.T, axis=1)
    slot_iota = jnp.arange(b, dtype=U32)
    chain_slot = jnp.where(is_real, jnp.argmax(eq, axis=1).astype(U32), slot_iota)
    return first_occ, last_occ, chain_slot


def occurrence_masks_sorted(idxs: jax.Array, dummy_index: int,
                            sort_impl: str = "xla",
                            key_bits: int | None = None):
    """`occurrence_masks` in O(B log B): one sort by (index, slot), then
    segment boundaries in sorted order mark first/last occurrences — no
    [B,B] intermediate (bit-identical outputs; tests/test_round.py).

    ``sort_impl="radix"`` with a declared ``key_bits`` bound (block
    indices are ≤ log2(blocks)+1 bits — oram_round passes the bound
    from its geometry) replaces the comparison sort with counting
    passes (oblivious/radix.py); identical masks either way."""
    from ..oblivious.segmented import multiword_group_sort, segment_bounds

    b = idxs.shape[0]
    is_real = idxs != U32(dummy_index)
    slot_iota = jnp.arange(b, dtype=U32)
    if sort_impl == "radix" and key_bits is not None:
        from ..oblivious.radix import radix_group_sort

        perm, inv, seg_start = radix_group_sort([idxs], key_bits)
    else:
        perm, inv, seg_start = multiword_group_sort([idxs])
    start, end = segment_bounds(seg_start)
    iota_i = jnp.arange(b, dtype=jnp.int32)
    first_occ = is_real & ((iota_i == start)[inv])
    last_occ = is_real & ((iota_i == end)[inv])
    chain_slot = jnp.where(is_real, perm[start][inv], slot_iota)
    return first_occ, last_occ, chain_slot


def _bucket_owner_map(
    cfg: OramConfig,
    flat_b: jax.Array,  # u32[R] heap ids of the per-path fetched buckets
    rows: jax.Array,  # u32[R] each copy's output row (ascending by column)
    n_rows: int,  # output rows; doubles as the "not fetched" sentinel
) -> jax.Array:
    """Heap-bucket → output-row map for this round's fetch.

    Buckets shared by several fetched paths must contribute their blocks
    to the working set exactly once and be written back exactly once;
    the owner is the lowest batch column touching the bucket, i.e. the
    lowest output row among its copies (a bucket sits at one level, and
    rows ascend with the column). One scatter-min over the heap bucket
    ids replaces the O((B·plen)²) all-pairs mask this supersedes, and
    doubles as the eviction-eligibility oracle: ``map[hb] != n_rows``
    iff a fetched path meets bucket ``hb`` this round.
    (searchsorted/sorted-neighbor alternatives lower to serial scalar
    loops on TPU — measured at ~0.17 ms per call — while scatter/gather
    stay vectorized.)
    """
    return jnp.full((cfg.n_buckets_padded,), U32(n_rows)).at[flat_b].min(rows)


def _assign_evictions(
    cfg: OramConfig,
    valid: jax.Array,  # bool[W] live working-set rows
    wleaf: jax.Array,  # u32[W] leaf assignment per row
    bucket_map: jax.Array,  # u32[n_buckets_padded] heap bucket -> output row
    n_rows: int,  # output bucket rows; doubles as the "not fetched" sentinel
    sort_impl: str,
    dense_levels: int = 0,  # levels whose buckets are their own output row
):
    """Joint level-synchronous greedy eviction assignment (module
    docstring step 3): one sort of the working set by leaf, then per
    level a segmented rank caps each bucket at Z — O(W) work per level
    with no [W, n_rows] masks. Returns ``(slot_tgt, placed)`` in
    working-set order; ``slot_tgt`` = ``row·Z + rank`` indexes a flat
    output of ``n_rows·Z`` slots (OOB = unplaced). ONE body serves both
    write layouts — the placement itself (which entry lands in which
    bucket) is the same greedy function either way, which the cross-E
    bit-identity contract depends on:

    - per-round eviction (oram_round): ``bucket_map`` sends a bucket
      that a fetched path meets to its output row (`_bucket_owner_map`);
      at the ``dense_levels`` — the levels the batch covers — every
      bucket is a target and its own output row, so those levels skip
      the lookup;
    - delayed flush (oram_flush): ``bucket_map`` = deduplicated target
      rows of the compacted window union.
    """
    h, z = cfg.height, cfg.bucket_slots
    w = valid.shape[0]
    nslots = n_rows * z
    skey = jnp.where(valid, wleaf, U32(0xFFFFFFFF))
    with device_phase("oram_evict_sort"):
        if sort_impl == "radix":
            # leaves are h bits; invalid rows sort last under the 2^h
            # sentinel exactly as they do under 0xFFFFFFFF (both stable
            # sorts keep equal keys in working-set order), so the
            # permutation is bit-identical to the argsort — at h+1
            # declared key bits instead of a 32-bit comparison sort
            eperm = radix_rank(
                jnp.where(valid, wleaf, U32(1) << U32(h)), h + 1
            )
        else:
            eperm = jnp.argsort(skey)
    sleaf = skey[eperm]
    svalid = valid[eperm]
    iota_w = jnp.arange(w, dtype=jnp.int32)
    placed = jnp.zeros((w,), jnp.bool_)  # sorted order
    slot_tgt_s = jnp.full((w,), nslots, U32)  # sorted order; OOB = unplaced
    # invalid rows carry the sort sentinel (0xFFFFFFFF / 2^h) in
    # sleaf; clamp to the real leaf range BEFORE the heap-id
    # arithmetic so `hb` provably fits u32 at every certified
    # geometry (the unclamped sentinel wrapped hb mod 2^32 —
    # harmless only because svalid masked those rows downstream;
    # rangelint flags exactly that kind of masked wraparound).
    # Clamped sentinel rows merge into the last real segment; they
    # are a sorted suffix and never eligible, so real rows' segment
    # starts and ranks are unchanged.
    bleaf = jnp.minimum(sleaf, U32(cfg.leaves - 1))
    for level in range(h, -1, -1):
        shift = U32(h - level)
        bid = bleaf >> shift  # bucket prefix per entry; sorted ⇒ contiguous
        hb = (U32(1) << U32(level)) - U32(1) + bid  # heap bucket index
        elig = svalid & ~placed
        if level < dense_levels:
            tgt = hb  # always fetched, and its own output row
        else:
            # one gather answers both "was my bucket fetched" (row !=
            # n_rows) and which output row holds it
            tgt = bucket_map[jnp.minimum(hb, U32(cfg.n_buckets_padded - 1))]
            elig = elig & (tgt != U32(n_rows))
        bnd = jnp.concatenate(
            [jnp.ones((1,), jnp.bool_), bid[1:] != bid[:-1]]
        )
        ei = elig.astype(jnp.int32)
        # exclusive count of eligibles, as the shifted inclusive
        # cumsum (interval-transparent, see primitives.rank_of)
        ecum = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(ei)[:-1]]
        )
        start = jax.lax.cummax(jnp.where(bnd, iota_w, 0))  # my segment start
        # exclusive rank within my bucket: >= 0 because ecum is
        # monotone and start[i] <= i; the max states that invariant
        # for interval reasoning (identity at runtime)
        rank = jnp.maximum(ecum - ecum[start], 0)
        chosen = elig & (rank < z)
        slot = tgt * U32(z) + rank.astype(U32)
        slot_tgt_s = jnp.where(chosen, slot, slot_tgt_s)
        placed = placed | chosen
    # back to working-set order (a [W] scatter, so values need no permute)
    slot_tgt = (
        jnp.full((w,), nslots, U32).at[eperm].set(slot_tgt_s, unique_indices=True)
    )
    placed = (
        jnp.zeros((w,), jnp.bool_).at[eperm].set(placed, unique_indices=True)
    )
    return slot_tgt, placed


def oram_round(
    cfg: OramConfig,
    state: OramState,
    idxs: jax.Array,  # u32[B] block indices (cfg.dummy_index = dummy op)
    new_leaves: jax.Array,  # u32[B] fresh uniform leaves (remap targets)
    dummy_leaves: jax.Array,  # u32[B] fresh uniform leaves (dummy fetches)
    apply_batch,
    axis_name: str | None = None,
    occ_impl: str = "dense",
    sort_impl: str = "xla",
    pm_new_leaves: jax.Array | None = None,  # u32[B] (recursive posmap)
    pm_dummy_leaves: jax.Array | None = None,  # u32[B] (recursive posmap)
):
    """One batched oblivious access round over this ORAM.

    ``apply_batch(vals0 u32[B,V], present0 bool[B]) ->
    (outs pytree, final_val u32[B,V], final_alive bool[B])``:

    - ``vals0[j]``/``present0[j]``: the pre-round value (zeros if absent)
      and presence of op j's key in the working set;
    - the callback resolves within-round slot-order chain semantics
      itself, **vectorized** (same-key matrices / segmented scans; it
      knows which ops share keys — typically via `occurrence_masks` on
      the same ``idxs``);
    - ``final_val[j]`` / ``final_alive[j]``: the key's state after the
      whole round. Only the values at each key's *last* occurrence are
      committed; the callback must put the final state there.

    Returns ``(state', outs, leaves)``; ``leaves`` u32[B] is the public
    transcript (every entry an independent uniform draw).

    **Layout: level-dense above, per-path below.** A level L has 2^L
    buckets; where 2^L <= B the B paths' copies of that level are no
    fewer rows than the level itself, so the round reads, evicts into
    and writes back the WHOLE level once, as a fixed heap range, instead
    of B owner-masked copies. ``Ld = cfg.dense_levels(B)`` levels are
    dense (a function of the shapes B, height and top_cache_levels —
    nothing to configure): the tree-top cache planes are the first
    ``kc`` of them and enter and leave the working set whole; heap
    buckets ``[2^kc − 1, 2^Ld − 1)`` are gathered, decrypted, encrypted
    and scattered at constant indices with an all-true owner mask;
    levels ``Ld … plen−1`` stay per-path under the owner map. The
    working set is ``[stash | (2^Ld − 1)·Z dense slots | B·(plen − Ld)·Z
    per-path slots | B insert rows]``, and the eviction output has one
    row per dense bucket (its heap id) followed by one per per-path
    copy. Every bucket of a level the batch covers (2^L <= B,
    ``cfg.covered_levels(B)`` of them) is an eviction target every
    round: a block may rest in any bucket on its own path, so the
    Path-ORAM invariant holds, and the access pattern at the dense
    levels is a constant — a fixed superset of the buckets the per-path
    round touched there; the public transcript, ``leaves``, is
    unchanged. Where the cache is taller than that (B < 2^kc), its
    deeper planes still come and go whole, but a bucket there is a
    target only if a fetched path meets it, exactly as without the
    cache: ``top_cache_levels`` decides which rows come from HBM, never
    where a block is placed (tests/test_tree_cache.py, contract 1).

    ``occ_impl``: "dense" = [B,B]-mask dedup, "scan" = sorted dedup with
    no quadratic intermediate (bit-identical; matches the engine's
    ``vphases_impl`` knob).

    ``sort_impl``: "xla" = the comparison sorts XLA lowers natively,
    "radix" = bounded-key counting passes (oblivious/radix.py) for the
    eviction leaf sort and the sorted dedup — bit-identical
    permutations, zero ``sort`` HLO in this round (matches the engine's
    ``GrapevineConfig.sort_impl`` knob; CI-audited in
    tests/test_radix.py).

    With a recursive position map (``cfg.posmap`` set; oram/posmap.py)
    ``pm_new_leaves``/``pm_dummy_leaves`` must supply fresh uniform
    *internal* leaves and the returned ``leaves`` is u32[B, 2]: column 0
    the payload-tree transcript, column 1 the internal posmap ORAM's —
    exactly B internal accesses per round regardless of the indices.
    """
    if cfg.delayed_eviction:
        # evict_window > 1 (config.py evict_every): this round is
        # fetch-only — gather+decrypt+stash/buffer update, ZERO tree
        # writes; oram_flush drains the accumulated window every
        # evict_window rounds on the round-counter cadence
        return _oram_fetch_round(
            cfg, state, idxs, new_leaves, dummy_leaves, apply_batch,
            axis_name=axis_name, occ_impl=occ_impl, sort_impl=sort_impl,
            pm_new_leaves=pm_new_leaves, pm_dummy_leaves=pm_dummy_leaves,
        )
    from .posmap import lookup_remap_round

    b = idxs.shape[0]
    z, v, plen = cfg.bucket_slots, cfg.value_words, cfg.path_len
    s = cfg.stash_size
    cb = cfg.cache_buckets  # heap ids [0, cb): the plaintext cache planes
    le = cfg.covered_levels(b)  # every bucket an eviction target
    ld = cfg.dense_levels(b)  # max(le, kc): levels held whole
    nd = (1 << ld) - 1  # heap ids [0, nd): the dense levels, cache included
    nc = min((1 << le) - 1, cb)  # cache buckets of the covered levels
    nsp = plen - ld  # per-path levels below them
    nbot = cfg.fetched_bucket_rows(b)  # rows of the encrypted HBM tree
    nrows = cb + nbot  # bucket rows of the working set = of the output
    nslots = nrows * z
    recursive = cfg.posmap is not None

    with device_phase("oram_fetch"):
        # --- 1. dedup, position-map read/remap, path fetch -----------------
        with device_phase("dedup"):
            if occ_impl == "scan":
                # block indices are bounded: real < blocks, dummy = blocks
                first_occ, last_occ, _ = occurrence_masks_sorted(
                    idxs, cfg.dummy_index, sort_impl=sort_impl,
                    key_bits=max(1, cfg.dummy_index.bit_length()),
                )
            else:
                first_occ, last_occ, _ = occurrence_masks(idxs, cfg.dummy_index)
        posmap, leaves, inner_leaves = lookup_remap_round(
            cfg, state.posmap, idxs, new_leaves, dummy_leaves,
            first_occ, last_occ,
            pm_new_leaves=pm_new_leaves, pm_dummy_leaves=pm_dummy_leaves,
            occ_impl=occ_impl, sort_impl=sort_impl,
        )

        with device_phase("path_index"):
            path_b = jax.vmap(lambda lf: path_bucket_indices(cfg, lf))(leaves)  # [B,plen]
            # below the covered levels a bucket is a target only where a
            # fetched path meets it. A cache level there (le <= level < ld,
            # a batch smaller than the cache top) keeps its bucket's own
            # row; a per-path copy at (column, level >= ld) has output row
            # nd + column·nsp + (level − ld), and the owner of a shared
            # bucket is its lowest row.
            path_rows = jnp.concatenate(
                [path_b[:, le:ld],
                 U32(nd) + jnp.arange(b * nsp, dtype=U32).reshape(b, nsp)],
                axis=1,
            )
            bmap = _bucket_owner_map(
                cfg, path_b[:, le:].reshape(-1), path_rows.reshape(-1), nrows
            )
            sparse_b = path_b[:, ld:].reshape(b * nsp)
            sparse_rows = path_rows[:, ld - le:].reshape(b * nsp)
            # HBM rows of the round, read and written at the same addresses:
            # the constant dense range below the cache, then the per-path
            # rows — ONLY these touch the encrypted tree arrays, so the
            # round's HBM traffic and cipher row count are
            # cfg.fetched_bucket_rows(B) (the jaxpr audit in
            # tools/check_tree_cache_oblivious.py pins this).
            # HBM slot planes are addressed on the bucket axis ([n, Z] reshape
            # views — free, layout-identical): flat slot ids (bucket·Z + slot)
            # escape u32/int32 one geometry doubling before bucket ids do, so
            # the certified u32 bound rides the bucket axis (rangelint;
            # OPERATIONS.md §18).
            bot_b = jnp.concatenate(
                [jnp.arange(cb, nd, dtype=U32), sparse_b]
            )
            owner_bot = jnp.concatenate(
                [jnp.ones((nd - cb,), jnp.bool_), bmap[sparse_b] == sparse_rows]
            )

    fused = cfg.cipher_impl == "pallas_fused"
    with device_phase("oram_fetch"):
        if axis_name is None and fused and cfg.encrypted:
            # single-chip fast path: gather + decrypt in ONE HBM pass
            # (oblivious/pallas_gather.py); the sharded path below keeps
            # decrypt-after-psum so tree plaintext never transits ICI
            from ..oblivious.pallas_gather import gather_decrypt_rows

            pidx, pval = gather_decrypt_rows(
                state.cipher_key, state.tree_idx, state.tree_val, state.nonces,
                bot_b, z=z, rounds=cfg.cipher_rounds,
                interpret=not _on_tpu(),
            )
        else:
            pidx = _path_gather(
                state.tree_idx.reshape(-1, z), bot_b, axis_name
            )  # [nbot, z]
            pval = _path_gather(state.tree_val, bot_b, axis_name)  # [nbot, z*v]
            pnonce = _path_gather(state.nonces, bot_b, axis_name)
            with device_phase("cipher_decrypt"):
                pidx, pval = cipher_rows(
                    cfg, state.cipher_key, bot_b, pnonce, pidx, pval
                )
        # non-owner copies of shared per-path buckets are invalidated
        pidx = jnp.where(owner_bot[:, None], pidx, SENTINEL)
        if recursive:
            # per-slot leaf metadata rides its own (jnp) cipher plane —
            # the fused kernels cover only the idx/val planes
            from .path_oram import leaf_plane_cipher

            pleaf = _path_gather(
                state.tree_leaf.reshape(-1, z), bot_b, axis_name
            )
            pnonce_l = _path_gather(state.nonces, bot_b, axis_name)
            with device_phase("cipher_decrypt"):
                pleaf = leaf_plane_cipher(
                    cfg, state.cipher_key, bot_b, pnonce_l, pleaf,
                )

    with device_phase("oram_fetch"):
        # the cache planes are plaintext working state like the stash and
        # the first cb dense rows: they join the working set whole. A cache
        # bucket under the covered levels that no path of this round meets
        # is no eviction target, so its blocks sit the round out and the
        # write-back keeps them (zero-length unless B < 2^kc): the cache
        # changes what is fetched from HBM, never where a block is placed
        with device_phase("cache_read"):
            cache_met = jnp.repeat(jnp.concatenate(
                [jnp.ones((nc,), jnp.bool_), bmap[nc:cb] != U32(nrows)]
            ), z)
            cidx = jnp.where(cache_met, state.cache_idx, SENTINEL)
            cval = state.cache_val.reshape(cb * z, v)
        w = s + nslots + b  # + b reserved rows for net inserts
        widx0 = jnp.concatenate(
            [state.stash_idx, cidx, pidx.reshape(-1),
             jnp.full((b,), SENTINEL, U32)]
        )
        wval0 = jnp.concatenate(
            [state.stash_val, cval, pval.reshape(-1, v),
             jnp.zeros((b, v), U32)], axis=0
        )

    with device_phase("oram_apply"):
        # --- 2. vectorized slot-order apply --------------------------------
        # Initial presence via a dense block-index → working-set-row map (one
        # scatter + one gather; block indices are unique among live blocks,
        # so at most one row writes each map slot). Replaces a [B, W] compare
        # that costs O(B·W) — ~3·10^8 bools per round at B=2048. The map is
        # private working memory, same standing as the posmap.
        iota_w = jnp.arange(w, dtype=U32)
        # non-real rows (SENTINEL, dummy) drop out of bounds: a live block
        # occupies exactly one working-set row, so in-bounds targets are
        # unique and the scatter can use the parallel lowering
        row_map = jnp.full((cfg.blocks + 2,), U32(w)).at[
            jnp.where(widx0 < U32(cfg.blocks), widx0, U32(cfg.blocks + 2))
        ].set(iota_w, mode="drop", unique_indices=True)
        pos0 = row_map[jnp.minimum(idxs, U32(cfg.blocks))]  # u32[B]; w = absent
        present0 = pos0 != U32(w)
        pos0 = jnp.minimum(pos0, U32(w - 1))
        vals0 = jnp.where(
            present0[:, None], wval0[pos0.astype(jnp.int32)], 0
        )  # u32[B, V]

    with device_phase("oram_apply"):
        outs, final_val, final_alive = apply_batch(vals0, present0)

    with device_phase("oram_apply"):
        # --- final per-key state → working-set rows ------------------------
        # the round's last op on each key commits the callback's final state:
        # updates rewrite (or kill) the existing row; net inserts land in the
        # b reserved trailing rows (row s + nslots + slot index)
        upd = last_occ & present0
        ins = last_occ & ~present0 & final_alive

        slot_iota = jnp.arange(b, dtype=U32)
        row_tgt = jnp.where(
            upd, pos0, jnp.where(ins, U32(s + nslots) + slot_iota, U32(w))
        )  # OOB = no write
        widx = widx0.at[row_tgt].set(
            jnp.where(final_alive, idxs, SENTINEL), mode="drop"
        )
        wval = wval0.at[row_tgt.astype(jnp.int32)].set(final_val, mode="drop")

        if recursive:
            # leaves ride the per-slot metadata plane (the map is its own
            # ORAM now — it cannot be gathered); rows committed this round
            # take their key's winning fresh leaf, the same value the map's
            # remap just recorded (the posmap↔metadata invariant)
            wleaf = jnp.concatenate(
                [state.stash_leaf, state.cache_leaf, pleaf.reshape(-1),
                 jnp.zeros((b,), U32)]
            ).at[row_tgt].set(new_leaves, mode="drop")
        else:
            # leaves for the whole working set come from the remapped private
            # posmap (the authoritative assignment — the tree stores no
            # leaves): rows touched this round already read back their op's
            # new leaf
            wleaf = working_leaves(posmap, cfg, widx)

    # --- 3. joint level-synchronous greedy eviction --------------------
    # One argsort of the working set by leaf, then per level: entries
    # destined to one bucket are contiguous in sorted order (a bucket at
    # level L is a leaf prefix, and sorting by leaf sorts by every
    # prefix), so within-bucket ranks are segmented cumsums — O(W) work
    # per level with no [W, B] masks (which at B=1024, plen=21 would be
    # ~10^8 bools per level).
    with device_phase("oram_evict"):
        valid = widx != SENTINEL
        slot_tgt, placed = _assign_evictions(
            cfg, valid, wleaf, bmap, nrows, sort_impl, dense_levels=le
        )

        # eviction slots are unique by construction (rank < z within a
        # bucket, disjoint slot ranges across buckets); unplaced rows drop
        new_pidx = jnp.full((nslots,), SENTINEL, U32).at[slot_tgt].set(
            widx, mode="drop", unique_indices=True
        )
        new_pval = jnp.zeros((nslots, v), U32).at[slot_tgt].set(
            wval, mode="drop", unique_indices=True
        )
        if recursive:
            new_pleaf = jnp.zeros((nslots,), U32).at[slot_tgt].set(
                wleaf, mode="drop", unique_indices=True
            )

        with device_phase("stash_compact"):
            # --- 4. stash recompaction -------------------------------------
            leftover = valid & ~placed
            srank = rank_of(leftover)
            starget = jnp.where(leftover, srank, s)  # OOB = dropped
            stash_idx = jnp.full((s,), SENTINEL, U32).at[starget].set(
                widx, mode="drop", unique_indices=True
            )
            stash_val = jnp.zeros((s, v), U32).at[starget].set(
                wval, mode="drop", unique_indices=True
            )
            stash_leaf = (
                jnp.zeros((s,), U32).at[starget].set(
                    wleaf, mode="drop", unique_indices=True
                )
                if recursive
                else state.stash_leaf
            )
            n_left = jnp.sum(leftover.astype(jnp.int32))
            # == n_left - min(n_left, s), in the interval-transparent form
            stash_dropped = jnp.maximum(n_left - s, 0).astype(U32)

    with device_phase("oram_writeback"):
        # the eviction output is ordered by output row, so the cache
        # planes are its first cb rows and the HBM rows (bot_b) the rest:
        # two contiguous slices; one owner bit per bucket row covers all
        # z slots on the bucket-axis scatters below
        bot_pidx = new_pidx[cb * z:].reshape(nbot, z)
        bot_pval = new_pval[cb * z:].reshape(nbot, z * v)
        epochs_w = jnp.broadcast_to(state.epoch[None, :], (nbot, 2))
    with device_phase("oram_writeback"):
        if axis_name is None and fused and cfg.encrypted:
            # single-chip fast path: encrypt + scatter in ONE HBM pass (the
            # write-back mirror of the fused fetch; pallas_gather.py) —
            # the nonce commit rides the same kernel, so this branch has no
            # XLA scatter at all
            from ..oblivious.pallas_gather import scatter_encrypt_rows

            tree_idx_new, tree_val_new, nonces = scatter_encrypt_rows(
                state.cipher_key, state.tree_idx, state.tree_val, state.nonces,
                bot_b, owner_bot, state.epoch,
                bot_pidx, bot_pval,
                z=z, rounds=cfg.cipher_rounds,
                interpret=not _on_tpu(),
            )
        else:
            with device_phase("cipher_encrypt"):
                enc_pidx, enc_pval = cipher_rows(
                    cfg,
                    state.cipher_key,
                    bot_b,
                    epochs_w,
                    bot_pidx,
                    bot_pval,
                )
            tree_idx_new = _path_scatter(
                state.tree_idx.reshape(-1, z), bot_b, enc_pidx, axis_name,
                owner_bot,
            ).reshape(-1)
            tree_val_new = _path_scatter(
                state.tree_val, bot_b, enc_pval, axis_name, owner_bot
            )
            nonces = (
                _path_scatter(
                    state.nonces, bot_b, epochs_w, axis_name, owner_bot
                )
                if cfg.encrypted
                else state.nonces
            )
        # cached levels leave as they came: whole planes, plaintext (a
        # bucket no path met keeps its rows);
        # replicated private state, so no collective even under sharding —
        # every chip computes the identical values (the stash-recompaction
        # standing). Zero-length at kc = 0.
        with device_phase("cache_write"):
            cache_idx_new = jnp.where(
                cache_met, new_pidx[: cb * z], state.cache_idx
            )
            cache_val_new = jnp.where(
                cache_met[:, None], new_pval[: cb * z], cval
            ).reshape(cb, z * v)
        if recursive:
            from .path_oram import leaf_plane_cipher

            with device_phase("cipher_encrypt"):
                enc_pleaf = leaf_plane_cipher(
                    cfg, state.cipher_key, bot_b, epochs_w,
                    new_pleaf[cb * z:].reshape(nbot, z),
                )
            tree_leaf_new = _path_scatter(
                state.tree_leaf.reshape(-1, z), bot_b, enc_pleaf, axis_name,
                owner_bot,
            ).reshape(-1)
            with device_phase("cache_write"):
                cache_leaf_new = jnp.where(
                    cache_met, new_pleaf[: cb * z], state.cache_leaf
                )
        else:
            tree_leaf_new = state.tree_leaf
            cache_leaf_new = state.cache_leaf
    with device_phase("oram_writeback"):
        new_state = OramState(
            tree_idx=tree_idx_new,
            tree_val=tree_val_new,
            cache_idx=cache_idx_new,
            cache_val=cache_val_new,
            cache_leaf=cache_leaf_new,
            tree_leaf=tree_leaf_new,
            stash_idx=stash_idx,
            stash_val=stash_val,
            stash_leaf=stash_leaf,
            # evict_window == 1: the buffer planes are zero-length and the
            # window bookkeeping never advances — bit-for-bit the pre-PR-15
            # per-round-eviction program
            ebuf_idx=state.ebuf_idx,
            ebuf_val=state.ebuf_val,
            ebuf_leaf=state.ebuf_leaf,
            ebuf_paths=state.ebuf_paths,
            ebuf_rounds=state.ebuf_rounds,
            ebuf_gen=state.ebuf_gen,
            fetch_tag=state.fetch_tag,
            posmap=posmap,
            overflow=state.overflow + stash_dropped,
            nonces=nonces,
            cipher_key=state.cipher_key,
            epoch=epoch_next(state.epoch),
        )
        if recursive:
            leaves = jnp.stack([leaves, inner_leaves], axis=1)
    return new_state, outs, leaves


def _oram_fetch_round(
    cfg: OramConfig,
    state: OramState,
    idxs: jax.Array,  # u32[B] block indices (cfg.dummy_index = dummy op)
    new_leaves: jax.Array,  # u32[B] fresh uniform leaves (remap targets)
    dummy_leaves: jax.Array,  # u32[B] fresh uniform leaves (dummy fetches)
    apply_batch,
    axis_name: str | None = None,
    occ_impl: str = "dense",
    sort_impl: str = "xla",
    pm_new_leaves: jax.Array | None = None,
    pm_dummy_leaves: jax.Array | None = None,
):
    """The delayed-eviction fetch round (``cfg.evict_window > 1``).

    Identical contract to :func:`oram_round` — same dedup, position
    resolution, gather+decrypt, and vectorized apply — but the
    scatter+encrypt half of the round is GONE: instead of evicting back
    into the fetched buckets, every live working-set row recompacts into
    the private eviction buffer (buffer-first; the stash catches the
    spill, keeping stash occupancy the pressure signal), the round's
    leaves are appended to the public window ledger (``ebuf_paths``),
    and the fetched buckets are tagged with the current flush
    generation. Tagged buckets' HBM/cache copies are *stale* — their
    live rows moved to the buffer at their fetch round — so re-fetches
    within one window invalidate them from the working set exactly like
    non-owner duplicates (each live block still enters the working set
    at most once, which the block→row map's uniqueness relies on). The
    tree arrays, cache planes, nonces, and the cipher epoch are
    untouched: the steady-state round performs ZERO HBM tree scatters
    and zero encrypt work (CI-audited row accounting,
    tools/check_tree_cache_oblivious.py:check_evict_round_accounting).
    :func:`oram_flush` drains the window.
    """
    from .posmap import lookup_remap_round

    b = idxs.shape[0]
    z, v, plen = cfg.bucket_slots, cfg.value_words, cfg.path_len
    s, c = cfg.stash_size, cfg.evict_buffer_slots
    nslots = b * plen * z
    recursive = cfg.posmap is not None

    with device_phase("oram_fetch"):
        # --- 1. dedup, position-map read/remap, path fetch (as E=1) --------
        with device_phase("dedup"):
            if occ_impl == "scan":
                first_occ, last_occ, _ = occurrence_masks_sorted(
                    idxs, cfg.dummy_index, sort_impl=sort_impl,
                    key_bits=max(1, cfg.dummy_index.bit_length()),
                )
            else:
                first_occ, last_occ, _ = occurrence_masks(idxs, cfg.dummy_index)
        posmap, leaves, inner_leaves = lookup_remap_round(
            cfg, state.posmap, idxs, new_leaves, dummy_leaves,
            first_occ, last_occ,
            pm_new_leaves=pm_new_leaves, pm_dummy_leaves=pm_dummy_leaves,
            occ_impl=occ_impl, sort_impl=sort_impl,
        )

        with device_phase("path_index"):
            path_b = jax.vmap(lambda lf: path_bucket_indices(cfg, lf))(leaves)
            flat_b = path_b.reshape(b * plen)
            cols_flat = jnp.repeat(jnp.arange(b, dtype=U32), plen)
            bmap = _bucket_owner_map(cfg, flat_b, cols_flat, b)
            # keep = this round's owner copy of a bucket that is NOT stale: a
            # bucket tagged earlier in this flush window already surrendered its
            # live rows to the buffer, so its HBM/cache bytes are dead copies
            fresh = state.fetch_tag[flat_b] != state.ebuf_gen
            keep = (bmap[flat_b] == cols_flat) & fresh

            # HBM slot planes are addressed on the bucket axis ([n, Z] reshape
            # views) exactly as in oram_round — flat slot ids escape u32/int32
            # one geometry doubling before bucket ids do (rangelint;
            # OPERATIONS.md §18). The tiny cache planes keep flat addressing.
            kc = cfg.top_cache_levels
            nbot = plen - kc
            bot_b = path_b[:, kc:].reshape(b * nbot)
            # level ℓ < kc heap ids are < 2^kc − 1 = cache_buckets by
            # construction; the min states that per-level invariant for
            # interval reasoning (runtime identity, see oram_round)
            top_b = jnp.minimum(
                path_b[:, :kc].reshape(b * kc),
                U32(max(cfg.cache_buckets, 1) - 1),
        )
        top_slots = path_slot_indices(cfg, top_b).reshape(-1)

    fused = cfg.cipher_impl == "pallas_fused"
    with device_phase("oram_fetch"):
        if axis_name is None and fused and cfg.encrypted:
            from ..oblivious.pallas_gather import gather_decrypt_rows

            pidx, pval = gather_decrypt_rows(
                state.cipher_key, state.tree_idx, state.tree_val, state.nonces,
                bot_b, z=z, rounds=cfg.cipher_rounds,
                interpret=not _on_tpu(),
            )
        else:
            pidx = _path_gather(
                state.tree_idx.reshape(-1, z), bot_b, axis_name
            )  # [B*nbot, z]
            pval = _path_gather(state.tree_val, bot_b, axis_name)
            pnonce = _path_gather(state.nonces, bot_b, axis_name)
            with device_phase("cipher_decrypt"):
                pidx, pval = cipher_rows(
                    cfg, state.cipher_key, bot_b, pnonce, pidx, pval
                )
        if kc:
            with device_phase("cache_read"):
                pidx = jnp.concatenate(
                    [state.cache_idx[top_slots].reshape(b, kc, z),
                     pidx.reshape(b, nbot, z)], axis=1,
                ).reshape(b * plen, z)
                pval = jnp.concatenate(
                    [state.cache_val[top_b].reshape(b, kc, z * v),
                     pval.reshape(b, nbot, z * v)], axis=1,
                ).reshape(b * plen, z * v)
        # non-owner copies AND stale copies are invalidated
        pidx = jnp.where(keep[:, None], pidx, SENTINEL)
        if recursive:
            from .path_oram import leaf_plane_cipher

            pleaf = _path_gather(
                state.tree_leaf.reshape(-1, z), bot_b, axis_name
            )
            pnonce_l = _path_gather(state.nonces, bot_b, axis_name)
            with device_phase("cipher_decrypt"):
                pleaf = leaf_plane_cipher(
                    cfg, state.cipher_key, bot_b, pnonce_l, pleaf,
                )
            if kc:
                with device_phase("cache_read"):
                    pleaf = jnp.concatenate(
                        [state.cache_leaf[top_slots].reshape(b, kc, z),
                         pleaf.reshape(b, nbot, z)], axis=1,
                    )
            pleaf = pleaf.reshape(-1)

    with device_phase("oram_fetch"):
        # working set = stash ∪ buffer ∪ fetched paths ∪ B insert rows
        w = s + c + nslots + b
        widx0 = jnp.concatenate(
            [state.stash_idx, state.ebuf_idx, pidx.reshape(-1),
             jnp.full((b,), SENTINEL, U32)]
        )
        wval0 = jnp.concatenate(
            [state.stash_val, state.ebuf_val, pval.reshape(-1, v),
             jnp.zeros((b, v), U32)], axis=0
        )

    with device_phase("oram_apply"):
        # --- 2. vectorized slot-order apply (as E=1; see oram_round) -------
        iota_w = jnp.arange(w, dtype=U32)
        row_map = jnp.full((cfg.blocks + 2,), U32(w)).at[
            jnp.where(widx0 < U32(cfg.blocks), widx0, U32(cfg.blocks + 2))
        ].set(iota_w, mode="drop", unique_indices=True)
        pos0 = row_map[jnp.minimum(idxs, U32(cfg.blocks))]
        present0 = pos0 != U32(w)
        pos0 = jnp.minimum(pos0, U32(w - 1))
        vals0 = jnp.where(
            present0[:, None], wval0[pos0.astype(jnp.int32)], 0
        )

    with device_phase("oram_apply"):
        outs, final_val, final_alive = apply_batch(vals0, present0)

    with device_phase("oram_apply"):
        upd = last_occ & present0
        ins = last_occ & ~present0 & final_alive

        slot_iota = jnp.arange(b, dtype=U32)
        row_tgt = jnp.where(
            upd, pos0, jnp.where(ins, U32(s + c + nslots) + slot_iota, U32(w))
        )
        widx = widx0.at[row_tgt].set(
            jnp.where(final_alive, idxs, SENTINEL), mode="drop"
        )
        wval = wval0.at[row_tgt.astype(jnp.int32)].set(final_val, mode="drop")

        if recursive:
            # the only consumer of leaf assignments in the fetch round is
            # the recursive per-row leaf plane below (flat maps resolve
            # leaves from the posmap at FLUSH time — no eviction happens
            # here, so tracing a working_leaves gather would add a dead
            # secret-indexed access for the analyzers to walk)
            wleaf = jnp.concatenate(
                [state.stash_leaf, state.ebuf_leaf, pleaf, jnp.zeros((b,), U32)]
            ).at[row_tgt].set(new_leaves, mode="drop")

    # --- 3. recompact EVERYTHING into buffer ∪ stash (no eviction) -----
    # buffer-first: the buffer is where window contents are expected to
    # live, the stash is the spill — so stash occupancy remains the
    # overflow-pressure signal the health fold watches. One rank + two
    # split scatters; total live past C+S drops into the shared sticky
    # overflow counter (the buffer-occupancy canary).
    with device_phase("oram_evict"):
        valid = widx != SENTINEL
        crank = rank_of(valid)
        ctarget = jnp.where(valid, crank, c + s)  # OOB = dropped
        comb_idx = jnp.full((c + s,), SENTINEL, U32).at[ctarget].set(
            widx, mode="drop", unique_indices=True
        )
        comb_val = jnp.zeros((c + s, v), U32).at[ctarget].set(
            wval, mode="drop", unique_indices=True
        )
        ebuf_idx, stash_idx = comb_idx[:c], comb_idx[c:]
        ebuf_val, stash_val = comb_val[:c], comb_val[c:]
        if recursive:
            comb_leaf = jnp.zeros((c + s,), U32).at[ctarget].set(
                wleaf, mode="drop", unique_indices=True
            )
            ebuf_leaf, stash_leaf = comb_leaf[:c], comb_leaf[c:]
        else:
            ebuf_leaf, stash_leaf = state.ebuf_leaf, state.stash_leaf
        n_live = jnp.sum(valid.astype(jnp.int32))
        # == n_live - min(n_live, c+s), in the interval-transparent
        # form (rangelint; the sticky counter's 2^16 budget absorbs it)
        dropped = jnp.maximum(n_live - (c + s), 0).astype(U32)

    with device_phase("oram_evict"):
        # --- 4. window bookkeeping; the tree/cache/nonces are UNTOUCHED ----
        # the append row: rounds < W whenever a fetch round runs (the
        # batcher flushes at W and resets the counter); the min states that
        # schedule invariant, which the declared [0, W] state bound cannot
        # carry by itself (runtime identity — without it the slice start
        # could reach the plane's end and XLA would clamp the write)
        ebuf_paths = jax.lax.dynamic_update_slice(
            state.ebuf_paths, leaves,
            ((jnp.minimum(state.ebuf_rounds, U32(cfg.evict_window - 1))
              * U32(b)).astype(jnp.int32),),
        )
        # monotone generations make scatter-max exact for duplicate buckets
        fetch_tag = state.fetch_tag.at[flat_b].max(state.ebuf_gen)

        new_state = OramState(
            tree_idx=state.tree_idx,
            tree_val=state.tree_val,
            cache_idx=state.cache_idx,
            cache_val=state.cache_val,
            cache_leaf=state.cache_leaf,
            tree_leaf=state.tree_leaf,
            stash_idx=stash_idx,
            stash_val=stash_val,
            stash_leaf=stash_leaf,
            ebuf_idx=ebuf_idx,
            ebuf_val=ebuf_val,
            ebuf_leaf=ebuf_leaf,
            ebuf_paths=ebuf_paths,
            ebuf_rounds=state.ebuf_rounds + U32(1),
            ebuf_gen=state.ebuf_gen,
            fetch_tag=fetch_tag,
            posmap=posmap,
            overflow=state.overflow + dropped,
            nonces=state.nonces,
            cipher_key=state.cipher_key,
            epoch=state.epoch,
        )
        if recursive:
            leaves = jnp.stack([leaves, inner_leaves], axis=1)
    return new_state, outs, leaves


def flush_target_slots(cfg: OramConfig) -> int:
    """Static write-target count of one flush: the window's fetched
    buckets deduplicated — at most ``window·fetch_count·path_len``
    path slots, and never more than the whole (padded) heap. The
    ``min`` is THE amortization: once ``E·F`` paths cover the tree,
    each extra window round adds fetch traffic but no write traffic,
    so the amortized scatter+encrypt cost per round falls as 1/E
    toward ``n_buckets/(E·F)`` rows (bench.py ``evict_ab`` measures
    the curve; the row-accounting gate pins the shape)."""
    return min(cfg.evict_window * cfg.evict_fetch_count * cfg.path_len,
               cfg.n_buckets_padded)


def oram_flush(
    cfg: OramConfig,
    state: OramState,
    axis_name: str | None = None,
    sort_impl: str = "xla",
) -> OramState:
    """Batched eviction + write-back of one accumulated flush window.

    Runs every ``evict_window`` fetch rounds on the round-counter
    cadence (never on buffer contents — the schedule must stay
    recipient-independent; the seeded flush_on_buffer_contents mutant
    pins the failure mode). One pass:

    1. the window's fetched paths (the public ``ebuf_paths`` ledger —
       ``window·fetch_count`` leaves, rounds beyond ``ebuf_rounds``
       masked inactive) expand to bucket ids and DEDUPLICATE into a
       static ``flush_target_slots`` array: every bucket fetched this
       window appears exactly once, so the window's shared buckets —
       the whole top of the tree, re-fetched every round — are written
       once per window instead of once per round. The dedup sort runs
       on PUBLIC data (bucket ids are the past transcript);
    2. the working set — eviction buffer ∪ stash — is greedily assigned
       to the deepest target bucket on each entry's own path
       (the SAME ``_assign_evictions`` body the per-round eviction
       runs, with the compacted [target, slot] output layout);
    3. one scatter+encrypt writes every target bucket back under the
       current epoch — ``flush_target_slots`` rows, cached top buckets
       peeled off to the plaintext cache planes by the heap-prefix
       mask;
    4. leftovers recompact into the stash, the buffer empties, and the
       flush generation bumps (re-validating every tagged bucket in
       O(1)).

    Every tagged bucket MUST be rewritten here: its HBM bytes are a
    stale copy of rows that moved to the buffer at fetch time, and a
    later window would re-fetch them as fresh after the generation
    bump. Deterministic given the state (no RNG), so journal replay
    re-executes it bit-identically (engine/journal.py KIND_FLUSH).
    Recursive position maps flush their internal tree in the same call.

    **Sharded (``axis_name`` set, inside shard_map).** The dedup, the
    eviction assignment, and the stash/buffer recompaction all run on
    the replicated private working set — identical on every chip, no
    collective — and only the final tree/nonce scatters change: the
    ``_path_scatter`` sharded branch ANDs the ``tree_tgt`` owner mask
    with each chip's contiguous heap range, so every chip writes
    exactly the target rows it owns and the union across the mesh is
    the single-chip flush bit for bit. The per-chip scatter still
    carries all ``t`` compacted rows (uniform static shape — row
    counts stay a pure function of geometry, never contents); non-owned
    rows drop out of bounds. Cache planes and the recursive inner tree
    are replicated private state and always take the axis-free path.
    """
    from .posmap import inner_oram_config

    z, v, plen = cfg.bucket_slots, cfg.value_words, cfg.path_len
    s, c = cfg.stash_size, cfg.evict_buffer_slots
    ncols = cfg.evict_window * cfg.evict_fetch_count
    f = cfg.evict_fetch_count
    pad = cfg.n_buckets_padded
    t = flush_target_slots(cfg)
    recursive = cfg.posmap is not None

    posmap = state.posmap
    if recursive:
        icfg = inner_oram_config(cfg.posmap)
        # the INNER tree is replicated private state (mesh.py P() specs),
        # never sharded — its flush must run the axis-free program on
        # every chip (the same convention oram_round uses for inner
        # accesses). Passing the outer axis_name here would owner-mask a
        # replicated plane against its FULL size: shard 0 would own
        # everything and every other replica nothing, silently diverging
        # the replicas on the first recursive flush.
        posmap = posmap._replace(
            inner=oram_flush(icfg, posmap.inner, None, sort_impl)
        )

    with device_phase("oram_flush"):
        leaves = state.ebuf_paths  # u32[ncols], public window ledger
        active = (jnp.arange(ncols, dtype=U32) // U32(f)) < state.ebuf_rounds
        path_b = jax.vmap(lambda lf: path_bucket_indices(cfg, lf))(leaves)
        flat_b = path_b.reshape(ncols * plen)
        active_flat = jnp.repeat(active, plen)
        # -- 1. public dedup: window bucket set → t compacted targets
        sb = jnp.sort(jnp.where(active_flat, flat_b, U32(pad)))
        first = jnp.concatenate(
            [jnp.ones((1,), jnp.bool_), sb[1:] != sb[:-1]]
        ) & (sb < U32(pad))
        fi = first.astype(U32)
        # compacted slot of each unique run: the exclusive count of
        # earlier firsts, as the shifted inclusive cumsum (the
        # interval-transparent form, see primitives.rank_of — cumsum−fi
        # reads as a full-lane u32 subtraction to interval reasoning)
        crank = jnp.concatenate(
            [jnp.zeros((1,), U32), jnp.cumsum(fi)[:-1]]
        )
        # target slot → bucket id (pad = unused slot, dropped on write)
        tgt_b = jnp.full((t,), U32(pad)).at[
            jnp.where(first, crank, U32(t))
        ].set(sb, mode="drop", unique_indices=True)
        # dense bucket id → target slot (t = not a target this window)
        dmap = jnp.full((pad,), U32(t)).at[
            jnp.where(first, sb, U32(pad))
        ].set(crank, mode="drop", unique_indices=True)

        # working set = buffer ∪ stash (buffer-first, the fetch-round
        # recompaction order)
        widx = jnp.concatenate([state.ebuf_idx, state.stash_idx])
        wval = jnp.concatenate([state.ebuf_val, state.stash_val], axis=0)
        if recursive:
            wleaf = jnp.concatenate([state.ebuf_leaf, state.stash_leaf])
        else:
            wleaf = working_leaves(posmap, cfg, widx)

        valid = widx != SENTINEL
        # [target, slot] layout over the compacted window union
        slot_tgt, placed = _assign_evictions(
            cfg, valid, wleaf, dmap, t, sort_impl
        )
        new_pidx = jnp.full((t * z,), SENTINEL, U32).at[slot_tgt].set(
            widx, mode="drop", unique_indices=True
        )
        new_pval = jnp.zeros((t * z, v), U32).at[slot_tgt].set(
            wval, mode="drop", unique_indices=True
        )
        if recursive:
            new_pleaf = jnp.zeros((t * z,), U32).at[slot_tgt].set(
                wleaf, mode="drop", unique_indices=True
            )

        # leftovers recompact into the stash; the buffer empties
        leftover = valid & ~placed
        srank = rank_of(leftover)
        starget = jnp.where(leftover, srank, s)  # OOB = dropped
        stash_idx = jnp.full((s,), SENTINEL, U32).at[starget].set(
            widx, mode="drop", unique_indices=True
        )
        stash_val = jnp.zeros((s, v), U32).at[starget].set(
            wval, mode="drop", unique_indices=True
        )
        stash_leaf = (
            jnp.zeros((s,), U32).at[starget].set(
                wleaf, mode="drop", unique_indices=True
            )
            if recursive
            else state.stash_leaf
        )
        n_left = jnp.sum(leftover.astype(jnp.int32))
        # == n_left - min(n_left, s), in the interval-transparent form
        stash_dropped = jnp.maximum(n_left - s, 0).astype(U32)

        # --- write-back: every target bucket once, cached top buckets
        # (a heap-id prefix) peeled off to the plaintext cache planes.
        # Shapes are t rows per plane; masked slots drop out of bounds.
        # HBM slot planes are addressed on the bucket axis ([n, Z]
        # reshape views) as in oram_round — flat slot ids escape
        # u32/int32 one geometry doubling before bucket ids (rangelint,
        # OPERATIONS.md §18); the tiny cache planes keep flat slot
        # addressing over CLAMPED bucket ids (cached targets are < cb
        # by the is_cached mask; the min states it for the intervals).
        kc = cfg.top_cache_levels
        cb = cfg.cache_buckets
        valid_tgt = tgt_b < U32(pad)
        is_cached = tgt_b < U32(cb)  # kc=0 → cb=0 → all False
        tree_tgt = valid_tgt & ~is_cached
        cache_tgt_slots = path_slot_indices(
            cfg, jnp.minimum(tgt_b, U32(max(cb, 1) - 1))
        ).reshape(-1)  # [t*z] flat cache-plane slots
        pidx2 = new_pidx.reshape(t, z)
        pval2 = new_pval.reshape(t, z * v)
        epochs_w = jnp.broadcast_to(state.epoch[None, :], (t, 2))
        fused = cfg.cipher_impl == "pallas_fused"
        if axis_name is None and fused and cfg.encrypted:
            from ..oblivious.pallas_gather import scatter_encrypt_rows

            tree_idx_new, tree_val_new, nonces = scatter_encrypt_rows(
                state.cipher_key, state.tree_idx, state.tree_val,
                state.nonces, tgt_b, tree_tgt, state.epoch,
                pidx2, pval2,
                z=z, rounds=cfg.cipher_rounds,
                interpret=not _on_tpu(),
            )
        else:
            with device_phase("cipher_encrypt"):
                enc_pidx, enc_pval = cipher_rows(
                    cfg, state.cipher_key, tgt_b, epochs_w, pidx2, pval2
                )
            tree_idx_new = _path_scatter(
                state.tree_idx.reshape(-1, z), tgt_b, enc_pidx, axis_name,
                tree_tgt,
            ).reshape(-1)
            tree_val_new = _path_scatter(
                state.tree_val, tgt_b, enc_pval, axis_name, tree_tgt
            )
            nonces = (
                _path_scatter(
                    state.nonces, tgt_b, epochs_w, axis_name, tree_tgt
                )
                if cfg.encrypted
                else state.nonces
            )
        if kc:
            # cache planes are indexed by heap id directly (a heap
            # prefix), so the clamped tgt_b slots address them; only
            # cached targets land, the rest drop out of bounds
            with device_phase("cache_write"):
                cache_idx_new = _path_scatter(
                    state.cache_idx, cache_tgt_slots, new_pidx, None,
                    jnp.repeat(is_cached, z),
                )
                cache_val_new = _path_scatter(
                    state.cache_val, tgt_b, pval2, None, is_cached
                )
        else:
            cache_idx_new = state.cache_idx
            cache_val_new = state.cache_val
        cache_leaf_new = state.cache_leaf
        if recursive:
            from .path_oram import leaf_plane_cipher

            pleaf2 = new_pleaf.reshape(t, z)
            with device_phase("cipher_encrypt"):
                enc_pleaf = leaf_plane_cipher(
                    cfg, state.cipher_key, tgt_b, epochs_w, pleaf2
                )
            tree_leaf_new = _path_scatter(
                state.tree_leaf.reshape(-1, z), tgt_b, enc_pleaf, axis_name,
                tree_tgt,
            ).reshape(-1)
            if kc:
                with device_phase("cache_write"):
                    cache_leaf_new = _path_scatter(
                        state.cache_leaf, cache_tgt_slots, new_pleaf, None,
                        jnp.repeat(is_cached, z),
                    )
        else:
            tree_leaf_new = state.tree_leaf

    with device_phase("oram_flush"):
        return OramState(
            tree_idx=tree_idx_new,
            tree_val=tree_val_new,
            cache_idx=cache_idx_new,
            cache_val=cache_val_new,
            cache_leaf=cache_leaf_new,
            tree_leaf=tree_leaf_new,
            stash_idx=stash_idx,
            stash_val=stash_val,
            stash_leaf=stash_leaf,
            ebuf_idx=jnp.full((c,), SENTINEL, U32),
            ebuf_val=jnp.zeros((c, v), U32),
            ebuf_leaf=jnp.zeros_like(state.ebuf_leaf),
            ebuf_paths=state.ebuf_paths,  # inactive at rounds=0; public
            ebuf_rounds=jnp.zeros((), U32),
            ebuf_gen=state.ebuf_gen + U32(1),
            fetch_tag=state.fetch_tag,  # generation bump re-validates all
            posmap=posmap,
            overflow=state.overflow + stash_dropped,
            nonces=nonces,
            cipher_key=state.cipher_key,
            epoch=epoch_next(state.epoch),
        )
