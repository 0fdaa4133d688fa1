"""Expiry sweep: full-tree timestamped eviction (reference README.md:86-98).

One jit'd data-independent pass over both ORAMs (the access pattern is
the whole tree — revealing nothing): records older than the expiry period
are invalidated, their mailbox entries cleared, emptied mailboxes release
their recipient slot, and the free-block list is rebuilt. The reference
MVP never finished hashmap eviction (README.md:98-99); this completes it.

Timestamps come from the untrusted host clock, as in the reference
(README.md:92-97); a tampered clock can evict early/late but the sweep
touches every bucket regardless, so it cannot reveal sender/recipient
linkage.

With the at-rest bucket cipher enabled, each tree is processed in row
chunks under ``lax.scan``: decrypt chunk → expire → re-encrypt under the
next epoch, all inside one scan body — at no point does more than one
chunk of plaintext exist in HBM (a mid-sweep memory snapshot exposes at
most ~8 M words, not the bus). Both cipher passes of a chunk go through
``path_oram.cipher_rows``, the one entry point of the at-rest cipher,
as the rounds' fetch and write-back do: on a TPU that is the Pallas
kernel of oblivious/pallas_cipher.py, which makes the keystream in VMEM,
reads the chunk's rows where they lie in the value plane and writes the
re-keyed rows back over them (the plane rides the scan's carry and is
aliased through the kernel: one copy of a tree, and the chunk of
plaintext between the two passes is all that is ever written beside
it); on the CPU it is the jnp keystream, bit for bit the same state
(tests/test_bucket_cipher.py). The recursive position map's leaf plane
(``Z`` words a row, under a domain-separated bucket word) keeps the jnp
``row_keystream``: 0.4 % of a tree's bytes.

On a mesh (``axis_name`` set, under ``parallel.make_sharded_sweep``'s
shard_map) each chip sweeps the contiguous heap range of buckets it
owns and the two cross-tree facts — which message ids survive, how many
recipients remain — are summed over the axis once per tree; everything
else is replicated private state that every chip sweeps identically.
Plain ``jit`` cannot do this: GSPMD will not partition a scan over the
sharded chunk axis, replicates the trees instead, and a 2^22 bus on
four v5e chips then fails to compile for lack of HBM.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..oblivious.bucket_cipher import epoch_next, row_keystream
from ..oblivious.primitives import (
    SENTINEL,
    is_zero_words,
    partition_rank,
    u64_le,
    u64_sub,
)
from ..obs.phases import device_phase
from ..oram.path_oram import (
    OramConfig,
    OramState,
    cipher_rows,
    logical_rows,
    stored_rows,
)
from .state import (
    ENT_SEQ,
    ENT_SEQH,
    ENT_TS,
    ENT_TSH,
    ENTRY_WORDS,
    EngineConfig,
    EngineState,
    KEY_WORDS,
    REC_TS,
    REC_TSH,
)

U32 = jnp.uint32

#: oblint taint anchors (analysis/oblint.py): the secret inputs of one
#: ``expiry_sweep(ecfg, state, now, period, now_hi)`` — THE SAME
#: private-plane/key/freelist anchors as the engine round, imported
#: from round_step so a new private plane cannot be tainted in one
#: audit and forgotten in the other; the sweep's chunk walk itself is
#: iota-driven and must stay untainted. ``now``/``period`` are the
#: untrusted host clock: public.
from .round_step import _tree_secrets as _rs_tree_secrets  # noqa: E402

OBLINT_SECRETS = (
    _rs_tree_secrets("state.rec")
    + _rs_tree_secrets("state.mb")
    + ("state.freelist", "state.hash_key", "state.id_key", "state.rng")
)


def RANGELINT_BOUNDS(ecfg: EngineConfig) -> dict:
    """Rangelint input-interval anchors for ``expiry_sweep(ecfg, state,
    now, period, now_hi)``: the same per-plane state invariants as the
    engine round (imported, so a new plane cannot be bounded in one
    audit and forgotten in the other). ``now``/``period`` are the
    untrusted host clock — full lane, never assumed. The sweep's own
    counters (chunk liveness, recipient recount) are *derived* bounded:
    the scan-carry fixpoint extrapolates their per-chunk budget over
    the chunk count, which tops out at total tree slots ≪ 2^32 at
    every certified geometry."""
    from .round_step import RANGELINT_BOUNDS as _rs_bounds

    return _rs_bounds(ecfg)


def _expired(ts_lo, ts_hi, now_lo, now_hi, period) -> jnp.ndarray:
    """Strict '>' age test over u64 lane pairs (now - ts > period).

    Guarded against wraparound: a record stamped *ahead* of the sweep
    clock (NTP step-back, caller-supplied smaller ``now``) must never be
    treated as ancient — the oracle's signed comparison keeps it, so we
    must too."""
    le = u64_le(ts_lo, ts_hi, now_lo, now_hi)
    d_lo, d_hi = u64_sub(now_lo, now_hi, ts_lo, ts_hi)
    return le & ((d_hi > 0) | (d_lo > period))


def _chunk_rows(cfg: OramConfig, n: int) -> int:
    """Rows per scan chunk of an ``n``-row (local) tree: power of two,
    ~8M words of keystream."""
    rpc = 1
    while rpc * 2 <= n and rpc * 2 * cfg.row_words <= (1 << 23):
        rpc *= 2
    return rpc


def _chunked_tree_sweep(cfg: OramConfig, oram: OramState, carry0, body,
                        axis_name=None):
    """Run ``body(carry, (plaintext idx [rpc, Z], plaintext val
    [rpc, Z*V])) -> (carry, (idx', val'))`` over the whole tree in
    chunks, with per-chunk decrypt/re-encrypt when the cipher is on.
    Returns (carry, OramState with new tree + nonces/epoch advanced).
    With ``axis_name`` the tree planes are this chip's rows only and
    the carry is this chip's partial result (the caller reduces it).

    A recursive position map (cfg.posmap set, oram/posmap.py) adds the
    per-slot leaf-metadata plane, encrypted under the same per-bucket
    nonces as the idx/val rows: the sweep re-keys every nonce, so the
    plane must be decrypt/re-encrypted in the same pass — its values
    never change (expiry only kills blocks; dead slots are masked by
    the SENTINEL idx), but its ciphertext epoch must follow the bucket.
    """
    z = cfg.bucket_slots
    n = oram.tree_val.shape[0]  # == n_buckets_padded off the mesh
    rpc = _chunk_rows(cfg, n)
    nch = n // rpc
    # global heap id of this chip's first row: the keystream is keyed
    # by heap ids
    base = (U32(0) if axis_name is None
            else jax.lax.axis_index(axis_name).astype(U32) * U32(n))
    recrypt_leaf = cfg.posmap is not None and cfg.encrypted

    # The planes ride the scan's carry and each chunk is read out of them
    # and written back over itself, so the program holds one copy of a
    # tree. Handed to the scan as stacked inputs and outputs they would
    # be two, and the records plane of 2^21 messages is 8 GiB: a second
    # copy does not fit the chip beside the state (PERF.md section 6,
    # PR 36). Slots are cut from the flat slot planes; the value plane's
    # rows are cipher_rows' to read and write in place (``chunk``).
    def cut(plane, i, width):
        return jax.lax.dynamic_slice_in_dim(plane, i * U32(width), width)

    def paste(plane, i, width, chunk):
        return jax.lax.dynamic_update_slice_in_dim(
            plane, chunk, i * U32(width), 0)

    def scan_body(carry, i):
        acc, idx_p, val_p, leaf_p = carry
        bid = base + i * U32(rpc) + jnp.arange(rpc, dtype=U32)
        ep = cut(oram.nonces, i, rpc)
        epn = jnp.broadcast_to(oram.epoch[None, :], (rpc, 2))
        # the chunk's rows go in as they lie in the plane and flat
        # plaintext comes back: cipher_rows' fetch direction, and the
        # identity where the cipher is off
        ix, vl = cipher_rows(
            cfg, oram.cipher_key, bid, ep,
            cut(idx_p, i, rpc * z).reshape(rpc, z), val_p, chunk=i,
        )
        # the body sees the blocks' Z*V words; the stored row's pad
        # words leave as the zeros they are in plaintext, and
        # cipher_rows' write-back direction stores their keystream,
        # over the rows the chunk was read from
        acc, (ix, vl) = body(acc, (ix, logical_rows(cfg, vl)))
        ix, val_p = cipher_rows(
            cfg, oram.cipher_key, bid, epn, ix, vl, chunk=i, plane=val_p)
        if recrypt_leaf:
            # leaf-plane stream: same (bucket, epoch), bucket word
            # offset by n_buckets_padded (path_oram.leaf_plane_cipher
            # domain separation)
            boff = bid + U32(cfg.n_buckets_padded)
            lf = cut(leaf_p, i, rpc * z).reshape(rpc, z)
            lf = lf ^ row_keystream(
                oram.cipher_key, boff, ep, z, cfg.cipher_rounds
            )
            lf = lf ^ row_keystream(
                oram.cipher_key, boff, epn, z, cfg.cipher_rounds
            )
            leaf_p = paste(leaf_p, i, rpc * z, lf.reshape(-1))
        idx_p = paste(idx_p, i, rpc * z, ix.reshape(-1))
        return (acc, idx_p, val_p, leaf_p), None

    # the leaf plane rides along only where it is re-keyed
    leaf0 = oram.tree_leaf if recrypt_leaf else jnp.zeros((0,), U32)
    (carry, idx_o, val_o, leaf_o), _ = jax.lax.scan(
        scan_body, (carry0, oram.tree_idx, oram.tree_val, leaf0),
        jnp.arange(nch, dtype=U32),
    )
    new = oram._replace(tree_idx=idx_o, tree_val=val_o)
    if recrypt_leaf:
        new = new._replace(tree_leaf=leaf_o)
    if cfg.encrypted:
        new = new._replace(
            nonces=jnp.broadcast_to(oram.epoch[None, :], oram.nonces.shape),
            epoch=epoch_next(oram.epoch),
        )
    return carry, new


def expiry_sweep(
    ecfg: EngineConfig, state: EngineState, now, period, now_hi=0,
    axis_name=None,
) -> EngineState:
    now = U32(now)
    now_hi = U32(now_hi)
    period = U32(period)

    # --- records ORAM: invalidate expired blocks, gather liveness ------
    rcfg = ecfg.rec
    v = rcfg.value_words
    n_msgs = ecfg.max_messages

    def rec_body(present, xs):
        ix, vl = xs  # [rpc, Z], [rpc, Z*V] plaintext
        ts_lo = vl[:, REC_TS::v][:, : rcfg.bucket_slots]
        ts_hi = vl[:, REC_TSH::v][:, : rcfg.bucket_slots]
        live = ix != SENTINEL
        dead = live & _expired(ts_lo, ts_hi, now, now_hi, period)
        ix = jnp.where(dead, SENTINEL, ix)
        # decrypted slot ids are opaque to interval reasoning; the min
        # keeps the liveness index inside the int32 scatter lane
        # (garbage >= n_msgs still drops — same OOB row as the sentinel)
        safe = jnp.minimum(
            jnp.where(ix != SENTINEL, ix, U32(n_msgs)), U32(n_msgs)
        ).reshape(-1)
        present = present.at[safe].set(True, mode="drop")
        return present, (ix, vl)

    present0 = jnp.zeros((n_msgs,), jnp.bool_)
    with device_phase("sweep_records"):
        present, rec = _chunked_tree_sweep(
            rcfg, state.rec, present0, rec_body, axis_name
        )
        if axis_name is not None:
            # a message id survives if any chip's rows still hold it
            present = jax.lax.psum(present.astype(U32), axis_name) > 0

        # tree-top cache planes (cfg.top_cache_levels > 0): the cached
        # top buckets' live blocks exist ONLY here — their HBM rows are
        # stale empty ciphertext, which the chunked pass above decrypts
        # to empty rows and re-keys harmlessly. The cache is plaintext
        # private state (stash standing), so it sweeps exactly like the
        # stash: no cipher, no re-key, same expire body.
        if rcfg.top_cache_levels:
            zc = rcfg.bucket_slots
            present, (cix, cvl) = rec_body(
                present,
                (rec.cache_idx.reshape(-1, zc),
                 logical_rows(rcfg, rec.cache_val)),
            )
            rec = rec._replace(
                cache_idx=cix.reshape(-1), cache_val=stored_rows(rcfg, cvl)
            )

    # stash rows are plaintext private state
    st_live = state.rec.stash_idx != SENTINEL
    st_dead = st_live & _expired(
        state.rec.stash_val[:, REC_TS],
        state.rec.stash_val[:, REC_TSH],
        now, now_hi, period,
    )
    rec_stash_idx = jnp.where(st_dead, SENTINEL, state.rec.stash_idx)
    safe = jnp.minimum(
        jnp.where(rec_stash_idx != SENTINEL, rec_stash_idx, U32(n_msgs)),
        U32(n_msgs),
    )
    present = present.at[safe].set(True, mode="drop")
    rec = rec._replace(stash_idx=rec_stash_idx)

    # --- mailbox ORAM: clear expired entries, drop empty mailboxes -----
    k, cap = ecfg.mb_slots, ecfg.mailbox_cap

    def sweep_mb(idx, val):
        # idx: [...]; val: blocks of V words — one block per idx entry
        lead = idx.shape
        ew = ENTRY_WORDS
        mw = KEY_WORDS + ew * cap
        flat = val.reshape((-1, k * mw))
        keys = flat.reshape(-1, k, mw)[:, :, :KEY_WORDS]
        entries = flat.reshape(-1, k, mw)[:, :, KEY_WORDS:].reshape(-1, k, cap, ew)
        valid = (entries[..., ENT_SEQ] | entries[..., ENT_SEQH]) != 0
        dead = valid & _expired(
            entries[..., ENT_TS], entries[..., ENT_TSH], now, now_hi, period
        )
        entries = jnp.where(dead[..., None], jnp.zeros((ew,), U32), entries)
        mbox_live = jnp.any(
            (entries[..., ENT_SEQ] | entries[..., ENT_SEQH]) != 0, axis=-1
        )  # [n, k]
        keys = jnp.where(mbox_live[..., None], keys, jnp.zeros((8,), U32))
        out = jnp.concatenate(
            [keys, entries.reshape(-1, k, cap * ew)], axis=-1
        ).reshape(flat.shape)
        # blocks with no live mailbox leave the ORAM entirely
        any_key = jnp.any(
            ~is_zero_words(keys.reshape(-1, k, 8)).reshape(-1, k), axis=-1
        ).reshape(lead)
        new_idx = jnp.where(idx != SENTINEL, jnp.where(any_key, idx, SENTINEL), idx)
        return new_idx, out.reshape(val.shape), keys.reshape(lead + (k, 8))

    def live_keys(keys, idx):
        lead_live = idx != SENTINEL
        kv = ~is_zero_words(keys)
        return jnp.sum(kv & lead_live[..., None]).astype(U32)

    def mb_body(cnt, xs):
        ix, vl = xs  # [rpc, Zm], [rpc, Zm*Vm] plaintext
        new_idx, out_val, keys = sweep_mb(ix, vl)
        return cnt + live_keys(keys, new_idx), (new_idx, out_val)

    with device_phase("sweep_mailbox"):
        recips, mb = _chunked_tree_sweep(
            ecfg.mb, state.mb, jnp.zeros((), U32), mb_body, axis_name
        )
        if axis_name is not None:
            recips = jax.lax.psum(recips, axis_name)
        # mailbox tree-top cache: plaintext pass, stash standing (see
        # the records cache sweep above)
        if ecfg.mb.top_cache_levels:
            zc = ecfg.mb.bucket_slots
            mc_idx, mc_val, mc_keys = sweep_mb(
                mb.cache_idx.reshape(-1, zc),
                logical_rows(ecfg.mb, mb.cache_val),
            )
            recips = recips + live_keys(mc_keys, mc_idx)
            mb = mb._replace(
                cache_idx=mc_idx.reshape(-1),
                cache_val=stored_rows(ecfg.mb, mc_val),
            )
    mb_stash_idx, mb_stash_val, stash_keys = sweep_mb(
        state.mb.stash_idx, state.mb.stash_val
    )
    recipients = recips + live_keys(stash_keys, mb_stash_idx)
    mb = mb._replace(stash_idx=mb_stash_idx, stash_val=mb_stash_val)

    # --- rebuild the free-block list from surviving record liveness ----
    # stable partition (free indices first, each side in index order):
    # two exclusive ranks + one unique scatter, O(n), no sort. pos is
    # exactly where a stable free-first partition puts each index.
    pos = partition_rank(present).astype(U32)
    freelist = (
        jnp.zeros((n_msgs,), U32)
        .at[pos]
        .set(jnp.arange(n_msgs, dtype=U32), unique_indices=True)
    )
    free_top = (U32(n_msgs) - jnp.sum(present.astype(U32))).astype(U32)

    return state._replace(
        rec=rec,
        mb=mb,
        freelist=freelist,
        free_top=free_top,
        recipients=recipients,
    )
