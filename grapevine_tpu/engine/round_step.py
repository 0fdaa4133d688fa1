"""Phase-major batched engine step: three vectorized ORAM rounds per batch.

`engine/step.py` commits each op's three phases before the next op starts
(op-major), which serializes 3·B dependent path fetches. This module runs
the same three phases *phase-major* over the batched round primitive
(oram/round.py): one mailbox round applying phase A for every op in slot
order, one records round applying phase B, one mailbox round applying
phase C. Within each round the slot-order semantics are resolved fully in
parallel (engine/vphases.py) — there is no per-op loop anywhere on the
device hot path.

**Phase-major commit semantics** (the documented batch-hazard behavior of
this engine; the reference never faced batches, SURVEY.md §7.6). Within
one batch, in slot order:

- phase-A effects (mailbox capacity checks and appends for CREATE,
  zero-id selection, zero-id DELETE's mailbox pop, record-slot
  reservation) are visible to later ops' phase A;
- phase-B effects (record insert/mutate/remove) are visible to later
  ops' phase B;
- phase-C effects (explicit DELETE's mailbox removal, UPDATE's mailbox
  timestamp refresh) are visible only to the *next* batch — as are
  record slots freed by any DELETE.

Consequences, all mirrored bit-for-bit by the CPU oracle's
``handle_batch`` (testing/reference.py): a CREATE cannot reuse capacity
freed by a DELETE in the same batch; a zero-id op whose mailbox-selected
message was explicitly deleted earlier in the batch reports NOT_FOUND
(the record is already gone in phase B) rather than selecting the next
message. For single-op batches phase-major and op-major semantics are
identical (no cross-op window), which tests assert.

Obliviousness: the public transcript is one uniform leaf per op per
round, [mailbox, records, mailbox] — identical in distribution for every
op type including padding dummies; duplicate-index dedup inside
oram_round keeps same-key ops uncorrelated in the transcript. Quota
admission may branch on *aggregate* saturation (bus or recipient table
within B of full) — see the leak analysis in engine/vphases.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..oblivious.primitives import is_zero_words, rank_of
from ..oblivious.prp import prp2_decrypt
from ..obs.phases import device_phase
from ..wire import constants as C
from ..oram.round import oram_round
from .responses import assemble_responses
from ..oblivious.primitives import u64_add_u32
from .state import EngineConfig, EngineState, mb_bucket_hash
from .vphases import phase_a_batch, phase_b_batch, phase_c_batch

U32 = jnp.uint32


def _tree_secrets(prefix: str) -> tuple:
    """The private planes of one OramState under ``prefix``: positions
    (posmap — recursively, the whole pytree under a recursive map),
    stash and cache contents, and the at-rest cipher key (key-taint is
    what marks decrypted tree rows secret; ciphertext stays public)."""
    return tuple(
        f"{prefix}.{p}"
        for p in (
            "posmap", "stash_idx", "stash_val", "stash_leaf",
            "cache_idx", "cache_val", "cache_leaf",
            "cipher_key",
        )
    )


#: oblint taint anchors (analysis/oblint.py): the secret inputs of one
#: full engine round ``engine_round_step(ecfg, state, batch)`` — every
#: per-op column of the batch (who, which message, what type, what
#: payload), both trees' private planes, and the engine's key material
#: (hash/PRP keys mix secrets; the rng's draws become future positions).
#: The freelist is secret too: its *contents* are freed block ids in
#: deletion order (private EPC-analog state per the threat model in
#: engine/state.py), even though its *height* (free_top) is the public
#: aggregate the quota-admission standing branches on (vphases.py).
#: Deliberately NOT secret: free_top/recipients/seq (aggregate
#: saturation counters), nonces/epoch (public write-epoch counters),
#: and the HBM tree ciphertext planes.
OBLINT_SECRETS = (
    ("batch.req_type", "batch.auth", "batch.msg_id", "batch.recipient",
     "batch.payload", "state.freelist", "state.hash_key",
     "state.id_key", "state.rng")
    + _tree_secrets("state.rec")
    + _tree_secrets("state.mb")
)


def RANGELINT_BOUNDS(ecfg: EngineConfig) -> dict:
    """Rangelint input-interval anchors (analysis/rangelint.py) for one
    full engine round ``engine_round_step(ecfg, state, batch)`` — the
    geometry-derived invariants where values enter the compiled round:

    - both trees' private planes carry the per-plane bounds of
      :func:`oram.path_oram.RANGELINT_BOUNDS` (position values below
      their leaf counts; ciphertext opaque);
    - the freelist holds block ids, ``free_top`` counts at most
      ``max_messages`` of them (stack invariant: pushes are exactly the
      oracle-pinned deletes), ``recipients`` is capped by admission at
      ``max_recipients`` — the counters' per-run increment budget is one
      batch (≤ B), which the u32 lane absorbs with 2^31 of margin;
    - batch columns, the u64 clock lanes, and the seq counter stay at
      the full lane (untrusted inputs / two-lane counters whose wrap is
      the allowlisted carry idiom).
    """
    from ..oram.path_oram import RANGELINT_BOUNDS as tree_bounds

    return {
        **tree_bounds(ecfg.rec, prefix="state.rec"),
        **tree_bounds(ecfg.mb, prefix="state.mb"),
        "state.freelist": (0, ecfg.max_messages - 1),
        "state.free_top": (0, ecfg.max_messages),
        "state.recipients": (0, ecfg.max_recipients),
    }


def _first_appearance_ids(rows: np.ndarray, mask: np.ndarray) -> np.ndarray:
    """i64[B] group ids of the rows of ``rows`` under ``mask``: equal
    rows share an id, ids count up in order of first appearance (what a
    dictionary filled in slot order would hand out), ``-1`` outside the
    mask. One ``np.unique`` over the rows seen as single items of the
    row's width in bytes."""
    ids = np.full(mask.shape, -1, np.int64)
    idx = np.flatnonzero(mask)
    if idx.size:
        picked = np.ascontiguousarray(rows[idx])
        items = picked.view(f"V{picked.shape[1] * picked.itemsize}")
        _, first, inverse = np.unique(
            items.ravel(), return_index=True, return_inverse=True)
        rank = np.empty(first.size, np.int64)
        rank[np.argsort(first)] = np.arange(first.size)
        ids[idx] = rank[inverse]
    return ids


def transcript_key_groups(batch: dict, mb_choices: int):
    """Host-side mirror of this step's key selection, for the leak
    monitor (obs/leakmon.py).

    Returns ``((mb_keys, mb_stable), (rec_keys, rec_stable))`` aligned
    to the transcript columns ``[a_0..a_{D-1}, b, c_0..c_{D-1}]``:

    - ``mb_keys`` i64[B·D]: within-round group ids over the flattened
      mailbox fetch slots — two slots share a group iff they fetch the
      same candidate bucket on the device, i.e. same ``ka`` (the
      recipient for CREATE/explicit-id ops, else the auth identity —
      the ``ka`` select above) and same choice column. ``-1`` = padding
      dummy (no key). Grouping by ``ka`` rather than the keyed bucket
      hash (device-resident ``hash_key``) can only *miss* accidental
      hash collisions between distinct ``ka`` — an undercount of
      same-key pairs, never a false SUSPECT.
    - ``rec_keys`` i64[B]: records-round groups; explicit-id non-CREATE
      ops group by ``msg_id`` (one msg_id = one PRP-resolved block).
      CREATE (allocates a fresh block) and zero-id ops (block selected
      inside the oblivious round) are not host-resolvable → ``-1``.
    - ``*_stable``: per-slot cross-round-stable ids for the repeat
      tracker, one row of u32 words a slot: ``mb_stable`` u32[B·D, 9]
      (the eight ``ka`` words, then the choice column), ``rec_stable``
      u32[B, 4] (the ``msg_id`` words). A keyless slot's row is read by
      nobody.

    Array work over the batch's columns: no statement runs once per op
    (the loop this replaced is the oracle in tests/test_leakmon.py).
    The key material stays in process memory (the monitor's standing —
    same as the position map); only windowed aggregates are exported.
    """
    rt = np.asarray(batch["req_type"]).astype(np.uint32)
    auth = np.asarray(batch["auth"], dtype=np.uint32)
    recipient = np.asarray(batch["recipient"], dtype=np.uint32)
    msg_id = np.asarray(batch["msg_id"], dtype=np.uint32)
    is_real = (rt >= C.REQUEST_TYPE_CREATE) & (rt <= C.REQUEST_TYPE_DELETE)
    is_create = rt == C.REQUEST_TYPE_CREATE
    id_zero = ~msg_id.any(axis=1)
    ka = np.where((is_create | ~id_zero)[:, None], recipient, auth)

    d = mb_choices
    choice = np.arange(d, dtype=np.int64)
    group = _first_appearance_ids(ka, is_real)
    mb_keys = np.where(
        group[:, None] >= 0, group[:, None] * d + choice, -1
    ).ravel()
    mb_stable = np.concatenate(
        [np.repeat(ka, d, axis=0),
         np.tile(choice.astype(np.uint32), ka.shape[0])[:, None]],
        axis=1,
    )
    rec_keys = _first_appearance_ids(msg_id, is_real & ~is_create & ~id_zero)
    return (mb_keys, mb_stable), (rec_keys, msg_id)


def engine_round_step(
    ecfg: EngineConfig,
    state: EngineState,
    batch: dict,
    axis_name: str | None = None,
):
    """Process one batch as three phase-major ORAM rounds.

    Same signature and return shape as `engine_step`: ``(state',
    responses, transcripts u32[B, 3])``.
    """
    with device_phase("request_unpack"):
        b = batch["req_type"].shape[0]
        now = batch["now"].astype(U32)
        # u64 clock: low lane in "now", optional high lane in "now_hi"
        # (absent in pre-widening batch dicts — membership is trace-static)
        now_hi = (
            batch["now_hi"].astype(U32) if "now_hi" in batch else jnp.zeros((), U32)
        )
        now2 = jnp.stack([now, now_hi])
        rt = batch["req_type"].astype(U32)
        auth = batch["auth"]
        msg_id = batch["msg_id"]
        recipient = batch["recipient"]
        payload = batch["payload"]

        d = ecfg.mb_choices  # candidate buckets fetched per op (mailbox tier)
        keys = jax.random.split(state.rng, 8)
        k_next = keys[7]
        nl_a, nl_b, nl_c = (
            jax.random.bits(keys[0], (b * d,), U32) & U32(ecfg.mb.leaves - 1),
            jax.random.bits(keys[1], (b,), U32) & U32(ecfg.rec.leaves - 1),
            jax.random.bits(keys[2], (b * d,), U32) & U32(ecfg.mb.leaves - 1),
        )
        dl_a, dl_b, dl_c = (
            jax.random.bits(keys[3], (b * d,), U32) & U32(ecfg.mb.leaves - 1),
            jax.random.bits(keys[4], (b,), U32) & U32(ecfg.rec.leaves - 1),
            jax.random.bits(keys[5], (b * d,), U32) & U32(ecfg.mb.leaves - 1),
        )
        id_rand = jax.random.bits(keys[6], (b, 3), U32)

        # recursive position map (oram/posmap.py): each round additionally
        # needs fresh uniform *internal* leaves — drawn from a fold_in side
        # stream so the flat engine's draws above are untouched bit-for-bit
        # (the flat↔recursive response/state identity contract)
        recursive = ecfg.rec.posmap is not None
        pm = {"a": (None, None), "b": (None, None), "c": (None, None)}
        if recursive:
            mb_il = ecfg.mb.posmap.inner_leaves
            rec_il = ecfg.rec.posmap.inner_leaves
            kpm = jax.random.split(jax.random.fold_in(state.rng, 0x504D), 6)
            pm = {
                "a": (jax.random.bits(kpm[0], (b * d,), U32) & U32(mb_il - 1),
                      jax.random.bits(kpm[1], (b * d,), U32) & U32(mb_il - 1)),
                "b": (jax.random.bits(kpm[2], (b,), U32) & U32(rec_il - 1),
                      jax.random.bits(kpm[3], (b,), U32) & U32(rec_il - 1)),
                "c": (jax.random.bits(kpm[4], (b * d,), U32) & U32(mb_il - 1),
                      jax.random.bits(kpm[5], (b * d,), U32) & U32(mb_il - 1)),
            }

        is_create = rt == C.REQUEST_TYPE_CREATE
        is_read = rt == C.REQUEST_TYPE_READ
        is_update = rt == C.REQUEST_TYPE_UPDATE
        is_delete = rt == C.REQUEST_TYPE_DELETE
        is_real = is_create | is_read | is_update | is_delete
        id_zero = is_zero_words(msg_id)
        zero_recip = is_zero_words(recipient)

        ka = jnp.where((is_create | ~id_zero)[:, None], recipient, auth)
        # D candidate buckets per op (salted independent keyed hashes);
        # every op fetches ALL candidates so the transcript hides which one
        # holds the recipient (vphases.phase_a_batch chooses with masks)
        bucket2 = jnp.stack(
            [
                jax.vmap(
                    lambda k, c=c: mb_bucket_hash(
                        state.hash_key, k, ecfg.mb_table_buckets, salt=c
                    )
                )(ka)
                for c in range(d)
            ],
            axis=1,
        )  # u32[B,D]
        idxs_mb2 = jnp.where(is_real[:, None], bucket2, U32(ecfg.mb.dummy_index))
        idxs_mb_flat = idxs_mb2.reshape(b * d)

        # allocation candidates: the top B free blocks, pre-gathered so the
        # freelist array never enters device decision logic (vphases assigns
        # the n-th successful create candidate n). The rank arithmetic uses
        # the +max_messages modular bias so lanes past the stack top never
        # wrap below zero in u32 (free_top + mm - 1 <= 2^31 - 1 at the
        # certified blocks <= 2^30 bound; the & mask is mod mm) — bit-
        # identical to free_top-1-ks on every selected lane, and interval-
        # transparent to rangelint instead of a masked wraparound.
        ks = jnp.arange(b, dtype=U32)
        mm_mask = U32(ecfg.max_messages - 1)
        cand_pos = jnp.where(
            ks < state.free_top,
            (state.free_top + mm_mask - ks) & mm_mask,
            U32(0),
        )
        cand_idx = state.freelist[cand_pos]

        # ---- round A: mailbox (capacity, append, zero-id select/pop) ------
        ctx = {
            "is_real": is_real,
            "is_create": is_create,
            "is_read": is_read,
            "is_update": is_update,
            "is_delete": is_delete,
            "id_zero": id_zero,
            "zero_recip": zero_recip,
            "ka": ka,
            "idxs_mb2": idxs_mb2,
            "cand_idx": cand_idx,
            "id_key": state.id_key,
            "id_rand": id_rand,
            "free_top0": state.free_top,
            "recipients0": state.recipients,
            "seq0": state.seq,
            "now": now,
            "now_hi": now_hi,
            "auth": auth,
            "recipient": recipient,
            "msg_id": msg_id,
            "payload": payload,
        }
    with device_phase("round_a_mailbox"):
        # the callback's own precomputation (recipient groups) counts
        # as the round's apply stage
        with device_phase("oram_apply"):
            apply_a = phase_a_batch(ecfg, ctx)
        mb1, out_a, leaf_a = oram_round(
            ecfg.mb, state.mb, idxs_mb_flat, nl_a, dl_a,
            apply_a, axis_name,
            pm_new_leaves=pm["a"][0], pm_dummy_leaves=pm["a"][1],
        )
    with device_phase("freelist_counters"):
        # n_allocs <= free_top by phase-A admission (the quota invariant the
        # oracle-equality suites pin), so the subtraction cannot wrap; that
        # argument lives in RANGE_ALLOWLIST, and the min re-establishes the
        # stack bound for interval reasoning downstream (identity at runtime)
        free_top = jnp.minimum(
            state.free_top - out_a["n_allocs"], U32(ecfg.max_messages)
        )
        recipients = state.recipients + out_a["n_claims"]
        seq_lo, seq_hi = u64_add_u32(state.seq[0], state.seq[1], U32(b))
        seq = jnp.stack([seq_lo, seq_hi])

    # ---- round B: records (verify, insert, mutate, remove) ------------
    with device_phase("request_unpack"):
        # id words 0-1 are the PRP-encrypted (nonce, block index)
        # (oblivious/prp.py); mailbox entries store the same encrypted form,
        # so one decrypt covers explicit-id and zero-id-selected lookups
        create_ok = out_a["create_ok"]
        enc_w0 = jnp.where(id_zero, out_a["sel_blk"], msg_id[:, 0])
        enc_w1 = jnp.where(id_zero, out_a["sel_idw"], msg_id[:, 1])
        dec_blk = prp2_decrypt(state.id_key, enc_w0, enc_w1, ecfg.id_bits)
        lookup_blk = jnp.where(create_ok, out_a["alloc_idx"], dec_blk)
        real_b = is_real & (
            create_ok | (~is_create & (~id_zero | out_a["sel_found"]))
        )
        idx_b = jnp.where(
            real_b, lookup_blk & U32(ecfg.rec.blocks - 1), U32(ecfg.rec.dummy_index)
        )
        ctx_b = {
            **ctx,
            "idx_b": idx_b,
            "real_b": real_b,
            "create_ok": create_ok,
            "new_id": out_a["new_id"],
            "sel_blk": out_a["sel_blk"],
            "sel_idw": out_a["sel_idw"],
        }
    with device_phase("round_b_records"):
        with device_phase("oram_apply"):
            apply_b = phase_b_batch(ecfg, ctx_b)
        rec1, out_b, leaf_b = oram_round(
            ecfg.rec, state.rec, idx_b, nl_b, dl_b,
            apply_b, axis_name,
            pm_new_leaves=pm["b"][0], pm_dummy_leaves=pm["b"][1],
        )

    with device_phase("freelist_counters"):
        # freed blocks return to the freelist in slot order — one vectorized
        # scatter, visible only to the next batch (phase-major commit rule)
        dels = out_b["del_ok"]
        push_pos = jnp.where(
            dels, free_top + rank_of(dels).astype(U32), U32(ecfg.max_messages)
        )
        freelist = state.freelist.at[push_pos].set(idx_b, mode="drop")
        free_top = free_top + jnp.sum(dels.astype(U32))

    # ---- round C: mailbox finalization --------------------------------
    with device_phase("request_unpack"):
        ctx_c = {
            **ctx,
            "del_ok": out_b["del_ok"],
            "upd_ok": out_b["upd_ok"],
            "rm_a": out_a["rm_a"],
        }
    with device_phase("round_c_mailbox"):
        with device_phase("oram_apply"):
            apply_c = phase_c_batch(ecfg, ctx_c)
        mb2, _out_c, leaf_c = oram_round(
            ecfg.mb, mb1, idxs_mb_flat, nl_c, dl_c,
            apply_c, axis_name,
            pm_new_leaves=pm["c"][0], pm_dummy_leaves=pm["c"][1],
        )

    # ---- response assembly (shared with the op-major engine) ----------
    responses = assemble_responses(
        is_real=is_real,
        is_create=is_create,
        is_update=is_update,
        is_delete=is_delete,
        id_zero=id_zero,
        status_a=out_a["status_a"],
        create_ok=create_ok,
        out_b=out_b,
        new_id=out_a["new_id"],
        auth=auth,
        recipient=recipient,
        payload=payload,
        now2=now2,
    )
    with device_phase("transcript"):
        # transcript: D leaves per mailbox round + 1 records leaf per op —
        # [B, 2D+1] columns (a_0..a_{D-1}, b, c_0..c_{D-1}); every entry an
        # independent uniform draw either way. Recursive posmap: the
        # internal ORAM's accesses are public transcript too — the same
        # layout is appended as columns [2D+1, 2(2D+1)) so the leak monitor
        # audits the position-resolution traffic alongside the payload's
        # (obs/leakmon.py mb_pm/rec_pm streams)
        if recursive:
            transcripts = jnp.concatenate(
                [
                    leaf_a[:, 0].reshape(b, d), leaf_b[:, 0:1],
                    leaf_c[:, 0].reshape(b, d),
                    leaf_a[:, 1].reshape(b, d), leaf_b[:, 1:2],
                    leaf_c[:, 1].reshape(b, d),
                ],
                axis=1,
            )
        else:
            transcripts = jnp.concatenate(
                [leaf_a.reshape(b, d), leaf_b[:, None], leaf_c.reshape(b, d)],
                axis=1,
            )

    new_state = EngineState(
        rec=rec1,
        mb=mb2,
        freelist=freelist,
        free_top=free_top,
        recipients=recipients,
        seq=seq,
        hash_key=state.hash_key,
        id_key=state.id_key,
        rng=k_next,
    )
    return new_state, responses, transcripts
