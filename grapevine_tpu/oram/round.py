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
   vectorize with no conflicts). The working set is sorted by leaf once
   (keys and permutation from the one sort), so at every level a
   bucket's candidates are a contiguous run, and an entry's rank within
   its bucket is formed by scans alone: the running count of eligible
   entries (a cumsum) less that count at the run's first row (a running
   max over the run boundaries — the count never falls). The first Z
   ranks of a bucket take its slots. The only per-entry read is a
   per-path level's lookup of its bucket's output row; one scatter takes
   the slots back to working-set order. Leftovers recompact into the
   stash; one scatter writes the dense range and all owned buckets back
   (write transcript ≡ read transcript).

Net effect per round: 2 large HBM transfers (gather + scatter) per tree
array instead of 2·B small dependent ones, with all decision logic in
O(log B)-depth parallel form.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..oblivious.primitives import SENTINEL, rank_of
from ..oblivious.bucket_cipher import epoch_next
from ..obs.phases import device_phase
from .path_oram import (
    OramConfig,
    OramState,
    _path_gather,
    _path_scatter,
    cipher_rows,
    logical_rows,
    path_bucket_indices,
    stored_rows,
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
    "state.cache_leaf", "state.cipher_key",
)


def occurrence_masks(idxs: jax.Array, dummy_index: int):
    """(first_occ, last_occ, chain_slot) over real (non-dummy) indices.

    first_occ[i]: no earlier op in the round touches the same index —
    this op performs the real path fetch. last_occ[i]: no later op does —
    this op's fresh leaf wins the position-map remap. chain_slot[i]: the
    slot of the round's first op on the same index (dummies get their own
    slot) — the shared chain-buffer slot for within-round read-after-write.

    The classic [B,B]-mask form.
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
    dense_levels: int,  # levels whose buckets are their own output row
):
    """Joint level-synchronous greedy eviction assignment (module
    docstring step 3): one sort of the working set by leaf, then per
    level a segmented rank caps each bucket at Z — O(W) work per level
    with no [W, n_rows] masks. Returns ``(slot_tgt, placed)`` in
    working-set order; ``slot_tgt`` = ``row·Z + rank`` indexes a flat
    output of ``n_rows·Z`` slots (OOB = unplaced). ``bucket_map`` sends
    a bucket that a fetched path meets to its output row
    (`_bucket_owner_map`); at the ``dense_levels`` — the levels the
    batch covers — every bucket is a target and its own output row, so
    those levels skip the lookup.

    No per-element gather that a scan or the sort can stand in for: on
    a v5e a [W] gather or scatter costs ~7 ns an element, serial — 0.56
    ms at W = 84,060, where a level's two scans and all its elementwise
    work are 2 us and the whole sort 0.09 ms (PERF.md §5, PR 38). So the
    sorted keys are the sort's own first operand, validity is read off
    them, a level's rank base is a running max, not a read at each
    row's segment start, and ``placed`` is read off the one array that
    is scattered back.
    """
    h, z = cfg.height, cfg.bucket_slots
    w = valid.shape[0]
    nslots = n_rows * z
    no_leaf = U32(0xFFFFFFFF)  # sorts after every leaf: invalid rows go last
    skey = jnp.where(valid, wleaf, no_leaf)
    with device_phase("oram_evict_sort"):
        # the keys and the permutation from one stable sort (what
        # jnp.argsort runs, which then drops the sorted keys)
        sleaf, eperm = jax.lax.sort(
            (skey, jnp.arange(w, dtype=jnp.int32)), num_keys=1
        )
    svalid = sleaf != no_leaf  # a live row's leaf is < cfg.leaves
    placed = jnp.zeros((w,), jnp.bool_)  # sorted order
    slot_tgt_s = jnp.full((w,), nslots, U32)  # sorted order; OOB = unplaced
    # invalid rows carry the sort sentinel 0xFFFFFFFF in sleaf; clamp
    # to the real leaf range BEFORE the heap-id arithmetic so `hb`
    # provably fits u32 at every certified geometry (the unclamped
    # sentinel wrapped hb mod 2^32 — harmless only because svalid
    # masked those rows downstream; rangelint flags exactly that kind
    # of masked wraparound). Clamped sentinel rows merge into the last
    # real segment; they are a sorted suffix and never eligible, so
    # real rows' ranks are unchanged.
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
            # n_rows) and which output row holds it — the one per-entry
            # read a level has
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
        # the count at my bucket's first row: ecum never falls and row 0
        # is a boundary, so the latest boundary's count is the largest
        # one so far — a running max, no read at a segment start
        base = jax.lax.cummax(jnp.where(bnd, ecum, 0))
        # exclusive rank within my bucket: >= 0 because base[i] is
        # ecum at a row <= i; the max states that invariant for
        # interval reasoning (identity at runtime)
        rank = jnp.maximum(ecum - base, 0)
        chosen = elig & (rank < z)
        slot = tgt * U32(z) + rank.astype(U32)
        slot_tgt_s = jnp.where(chosen, slot, slot_tgt_s)
        placed = placed | chosen
    # back to working-set order (a [W] scatter, so values need no
    # permute); a row is placed iff it left with a slot
    slot_tgt = (
        jnp.full((w,), nslots, U32).at[eperm].set(slot_tgt_s, unique_indices=True)
    )
    return slot_tgt, slot_tgt != U32(nslots)


def oram_round(
    cfg: OramConfig,
    state: OramState,
    idxs: jax.Array,  # u32[B] block indices (cfg.dummy_index = dummy op)
    new_leaves: jax.Array,  # u32[B] fresh uniform leaves (remap targets)
    dummy_leaves: jax.Array,  # u32[B] fresh uniform leaves (dummy fetches)
    apply_batch,
    axis_name: str | None = None,
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

    With a recursive position map (``cfg.posmap`` set; oram/posmap.py)
    ``pm_new_leaves``/``pm_dummy_leaves`` must supply fresh uniform
    *internal* leaves and the returned ``leaves`` is u32[B, 2]: column 0
    the payload-tree transcript, column 1 the internal posmap ORAM's —
    exactly B internal accesses per round regardless of the indices.
    """
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
            first_occ, last_occ, _ = occurrence_masks(idxs, cfg.dummy_index)
        posmap, leaves, inner_leaves = lookup_remap_round(
            cfg, state.posmap, idxs, new_leaves, dummy_leaves,
            first_occ, last_occ,
            pm_new_leaves=pm_new_leaves, pm_dummy_leaves=pm_dummy_leaves,
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

    with device_phase("oram_fetch"):
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
            # per-slot leaf metadata rides its own (jnp) cipher plane
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
            cval = logical_rows(cfg, state.cache_val).reshape(cb * z, v)
        w = s + nslots + b  # + b reserved rows for net inserts
        widx0 = jnp.concatenate(
            [state.stash_idx, cidx, pidx.reshape(-1),
             jnp.full((b,), SENTINEL, U32)]
        )
        wval0 = jnp.concatenate(
            [state.stash_val, cval, logical_rows(cfg, pval).reshape(-1, v),
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
    # One sort of the working set by leaf, then per level: entries
    # destined to one bucket are contiguous in sorted order (a bucket at
    # level L is a leaf prefix, and sorting by leaf sorts by every
    # prefix), so within-bucket ranks are segmented cumsums — O(W) work
    # per level with no [W, B] masks (which at B=1024, plen=21 would be
    # ~10^8 bools per level).
    with device_phase("oram_evict"):
        valid = widx != SENTINEL
        slot_tgt, placed = _assign_evictions(
            cfg, valid, wleaf, bmap, nrows, dense_levels=le
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
            cache_val_new = stored_rows(cfg, jnp.where(
                cache_met[:, None], new_pval[: cb * z], cval
            ).reshape(cb, z * v))
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
            posmap=posmap,
            overflow=state.overflow + stash_dropped,
            nonces=nonces,
            cipher_key=state.cipher_key,
            epoch=epoch_next(state.epoch),
        )
        if recursive:
            leaves = jnp.stack([leaves, inner_leaves], axis=1)
    return new_state, outs, leaves
