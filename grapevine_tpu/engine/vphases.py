"""Vectorized phase semantics: the whole batch resolved in parallel.

The engine's three phases (mailbox, records, mailbox — engine/step.py
documents the semantics per phase) were originally applied op-by-op under
``lax.scan``. On TPU a scan body costs ~30-130µs *per iteration* (each
tiny op in the body pays fixed sequencer overhead), which made the scans
>99% of round latency. This module computes identical slot-order
semantics with **no per-op loop at all**: same-key chains (ops on one
record / one mailbox in one round) become [B,B] masked matrices — "did
any earlier op of my group do X" — with OR-aggregates as one-hot
bool-matmuls on the MXU. O(B²) compute and intermediate memory, but
every op is a wide matrix/reduction the MXU/VPU eat at the batch sizes
the engine runs (every op with a [B,B] or [B·D,B] operand together is
0.31 ms of a device round at B = 2048, ROADMAP Speed 6).
tests/test_vphases.py holds each group query to a plain Python loop and
the whole engine to the CPU oracle.

Beside the masks:

- the mailbox occupancy walk (CREATE = min(count+1, cap), zero-id DELETE
  pop = max(count-1, 0)) is a *saturating-counter* walk, computed exactly
  with a segmented associative scan in O(log B) depth
  (oblivious/segmented.py);
- entry selection ("pop the oldest") becomes a per-mailbox sort by seq +
  a rank gather;
- final block values are rebuilt once per touched bucket with shifts and
  conflict-free scatters.

Admission quotas (bus capacity, recipient-table capacity) couple ops
*across* groups. When headroom covers the whole batch — the steady
state — admission decouples and everything above is exact. When the bus
or recipient table is within B of saturation, a fallback ``lax.scan``
over [B] resolves just the admission bits sequentially (tiny body —
counters only, no values). The branch predicate reveals only "bus or
recipient table nearly full", an aggregate the reference's own error
responses already expose to clients (and Create is permitted to be
distinguishable, reference grapevine.proto:120-122); per-op secrets
never influence the branch.

Obliviousness note: the admission walk gathers at its ``group_sort``
permutation, a function of the batch's same-key structure, like the
working-set row maps in oram/round.py: these are private-working-memory
accesses, the EPC analog, not the HBM bucket-tree transcript
obliviousness is claimed for. Dedup inside oram_round keeps same-key ops
uncorrelated in the public transcript.

Semantics notes vs the original chain engine (mirrored by the oracle):

- **Sticky mailbox slots**: a recipient's hash-table slot persists when
  its mailbox drains to empty; only the expiry sweep reclaims slots and
  decrements the recipient count. (Freeing mid-round would couple every
  recipient's walk to every other's through bucket-slot occupancy; the
  reference never specifies reclamation timing.)
- **Seq numbering by slot**: a created entry's order stamp is
  ``seq0 + slot`` and ``seq`` advances by B per round, preserving
  slot-order semantics with gaps. The counter is u64 (two u32 lanes) —
  no realistic wraparound.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..oblivious.primitives import (
    is_zero_words,
    rank_of,
    shift_down,
    sort_rows_by_u64,
    u64_add_u32,
    words_equal,
)
from ..oblivious.prp import prp2_encrypt
from ..oblivious.segmented import (
    group_sort,
    sat_apply,
    segmented_exclusive_sat_scan,
)
from ..wire import constants as C
from .state import (
    ENT_BLK,
    ENT_IDW,
    ENT_SEQ,
    ENT_SEQH,
    ENT_TS,
    ENT_TSH,
    ENTRY_WORDS,
    EngineConfig,
    KEY_WORDS,
    REC_ID,
    REC_PAYLOAD,
    REC_RECIPIENT,
    REC_SENDER,
    REC_TS,
    REC_TSH,
)

U32 = jnp.uint32
I32 = jnp.int32


def _tril(b: int, strict: bool = True):
    return jnp.tril(jnp.ones((b, b), jnp.bool_), k=-1 if strict else 0)


def _counts_before(same: jax.Array, flags: jax.Array) -> jax.Array:
    """#flagged earlier ops of my group, per op: i32[B]."""
    b = same.shape[0]
    return jnp.sum(same & _tril(b) & flags[None, :], axis=1).astype(I32)


def _any_before(same: jax.Array, flags: jax.Array) -> jax.Array:
    b = same.shape[0]
    return jnp.any(same & _tril(b) & flags[None, :], axis=1)


def _bool_matmul(m: jax.Array, u: jax.Array) -> jax.Array:
    """OR-aggregate u's rows over m's True columns: bool[B,B] x bool[B,N]
    → bool[B,N], computed on the MXU (sums < 2^24 are exact in f32)."""
    return (
        jnp.matmul(
            m.astype(jnp.float32),
            u.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        > 0.5
    )


# ----------------------------------------------------------------------
# group aggregation engine
# ----------------------------------------------------------------------
#
# Every within-round chain question the three phases ask is one of a
# small set of group aggregations ("my group" = ops sharing a recipient
# key / effective bucket / record block; dummies are singleton groups):
#
#   counts_before(f)       #flagged strictly-earlier ops of my group
#   any_before(f)          counts_before > 0
#   total_sum(f)/total_or  sum / OR over my whole group (self included)
#   *_rows(u)              the same, aggregating bool[B,N] row vectors
#   group_first/group_last smallest / largest slot index in my group
#   first_flag_index(f)    slot of my group's first flagged op (+ found)
#   last_flag_index[_upto] slot of my group's last flagged op
#                          (optionally restricted to at-or-before me)
#   select_by_rank(f,v,q)  v-row of my group's q-th flagged op (0 if none)
#
# _DenseGroups answers them with [B,B] masks and one-hot matmuls, and
# is the one place that knows that formulation. Dummy ops never raise
# flags (all flags are masked by is_real).


class _DenseGroups:
    """The group queries over a [B,B] same-group mask."""

    def __init__(self, same: jax.Array):
        b = same.shape[0]
        self.b = b
        # real ops already include themselves in `same`; adding the
        # diagonal only turns dummy rows into singleton groups, which
        # never changes a flagged result (dummies raise no flags)
        self.m = same | jnp.eye(b, dtype=jnp.bool_)
        self._same = same

    def counts_before(self, flags):
        return _counts_before(self._same, flags)

    def any_before(self, flags):
        return _any_before(self._same, flags)

    def total_sum(self, flags):
        return jnp.sum(self.m & flags[None, :], axis=1).astype(I32)

    def total_or(self, flags):
        return jnp.any(self.m & flags[None, :], axis=1)

    def total_sum_rows(self, u):
        return jnp.matmul(
            self.m.astype(jnp.float32),
            u.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        ).astype(I32)

    def total_or_rows(self, u):
        return _bool_matmul(self.m, u)

    def group_first(self):
        return jnp.argmax(self.m, axis=1).astype(U32)

    def group_last(self):
        iota = jnp.arange(self.b, dtype=U32)
        return jnp.max(jnp.where(self.m, iota[None, :], 0), axis=1)

    def first_flag_index(self, flags):
        oh = self.m & flags[None, :]
        return jnp.argmax(oh, axis=1).astype(I32), jnp.any(oh, axis=1)

    def last_flag_index_upto(self, flags):
        iota = jnp.arange(self.b, dtype=I32)
        wm = self.m & flags[None, :] & _tril(self.b, strict=False)
        return jnp.max(jnp.where(wm, iota[None, :], -1), axis=1)

    def last_flag_index(self, flags):
        iota = jnp.arange(self.b, dtype=I32)
        wm = self.m & flags[None, :]
        return jnp.max(jnp.where(wm, iota[None, :], -1), axis=1)

    def select_by_rank(self, flags, vals, q):
        rank = self.counts_before(flags)
        oh = self.m & flags[None, :] & (rank[None, :] == q[:, None])
        return jnp.sum(oh[:, :, None] * vals[None, :, :], axis=1).astype(
            vals.dtype
        )


def _recipient_groups(ka: jax.Array, is_real: jax.Array):
    """Groups over the recipient key ka (dummies singleton)."""
    requal = (
        words_equal(ka[:, None, :], ka[None, :, :])
        & is_real[:, None]
        & is_real[None, :]
    )
    return _DenseGroups(requal)


def _index_groups(idx: jax.Array, is_real: jax.Array):
    """Groups over a single u32 index column (bucket / record block)."""
    eq = (
        (idx[:, None] == idx[None, :])
        & is_real[:, None]
        & is_real[None, :]
    )
    return _DenseGroups(eq)


def _mb_parse_batch(ecfg: EngineConfig, vals: jax.Array):
    """[B, Vmb] → keys [B,K,8], entries [B,K,cap,ENTRY_WORDS]."""
    b = vals.shape[0]
    k, cap, ew = ecfg.mb_slots, ecfg.mailbox_cap, ENTRY_WORDS
    kw = KEY_WORDS
    v = vals.reshape(b, k, kw + ew * cap)
    return v[:, :, :kw], v[:, :, kw:].reshape(b, k, cap, ew)


def _mb_pack_batch(ecfg: EngineConfig, keys: jax.Array, entries: jax.Array):
    b = keys.shape[0]
    k, cap, ew = ecfg.mb_slots, ecfg.mailbox_cap, ENTRY_WORDS
    flat = jnp.concatenate([keys, entries.reshape(b, k, cap * ew)], axis=2)
    return flat.reshape(b, k * (KEY_WORDS + ew * cap))


# ----------------------------------------------------------------------
# order along a mailbox's cap axis: one sort that carries the entries,
# masked reductions and a barrel shift — never a take_along_axis by a
# per-element index, which a TPU executes one element at a time (12 ns
# an element on a v5e: 6 ms for one [B,K,cap] index array at B = 2048)
# ----------------------------------------------------------------------


def _oldest_first(entries, slot_match):
    """Every slot's entries by ascending 64-bit sequence number, holes
    (sequence 0, whatever else they hold) last in their stored order.

    entries u32[B,K,cap,ENTRY_WORDS], slot_match bool[B,K] → (valid
    count i32[B,K], sorted entries u32[B,K,cap,W], the matched slot's
    sorted entries u32[B,cap,W]). At most one slot of a bucket holds a
    recipient key, and sorting each slot commutes with selecting one,
    so the recipient's view needs no sort of its own: it is the masked
    sum over K, all zeros when no slot matches."""
    valid = (entries[..., ENT_SEQ] | entries[..., ENT_SEQH]) != 0
    inf = U32(0xFFFFFFFF)
    sorted_all = sort_rows_by_u64(
        jnp.where(valid, entries[..., ENT_SEQ], inf),
        jnp.where(valid, entries[..., ENT_SEQH], inf),
        entries,
        axis=2,
    )
    sorted_ent = jnp.sum(
        sorted_all * slot_match[:, :, None, None].astype(U32), axis=1
    )
    return jnp.sum(valid, axis=2).astype(I32), sorted_all, sorted_ent


def _pth_entry(sorted_ent, p):
    """Entry ``clip(p, 0, cap-1)`` of each row: u32[B,cap,W], i32[B] →
    u32[B,W], as a masked reduction over cap."""
    cap = sorted_ent.shape[1]
    hit = jnp.arange(cap, dtype=I32) == jnp.clip(p, 0, cap - 1)[:, None]
    return jnp.sum(sorted_ent * hit[:, :, None].astype(U32), axis=1)


def _drop_oldest(sorted_all, count, popped):
    """Each slot's survivors after its ``popped`` oldest entries go:
    shifted down to position 0, everything past the survivors zeroed.
    sorted_all u32[B,K,cap,W]; count, popped i32[B,K], popped <= count."""
    cap = sorted_all.shape[2]
    keep = jnp.arange(cap, dtype=I32) + popped[:, :, None] < count[:, :, None]
    moved = shift_down(sorted_all, popped[:, :, None, None], axis=2)
    return jnp.where(keep[:, :, :, None], moved, U32(0))


# ----------------------------------------------------------------------
# admission: who gets to create / claim / pop, exactly, in slot order
# ----------------------------------------------------------------------


def _admission_fast(
    ecfg,
    *,
    is_create_cand,
    is_pop_cand,
    found0,
    first_create,
    free_slots0,
    init_count,
    groups_r,
    groups_g,
    rslot,
):
    """Quota-decoupled admission (bus + recipient headroom ≥ B)."""
    b = rslot.shape[0]
    cap = ecfg.mailbox_cap

    claim_cand = first_create & ~found0
    claim_rank = groups_g.counts_before(claim_cand)
    claim_ok = claim_cand & (claim_rank < free_slots0)
    # my recipient's claim, if any (claims live at the first-create op)
    claimed_r = groups_r.total_or(claim_ok)
    active = found0 | claimed_r

    # saturating occupancy walk per recipient, segmented by first-occ slot
    create_elem = is_create_cand & active
    pop_elem = is_pop_cand & active
    add = jnp.where(create_elem, 1, jnp.where(pop_elem, -1, 0)).astype(I32)
    lo = jnp.zeros((b,), I32)
    hi = jnp.full((b,), cap, I32)
    perm, inv, seg = group_sort(rslot)
    pre = segmented_exclusive_sat_scan((add[perm], lo[perm], hi[perm]), seg)
    count_before = sat_apply(pre, init_count[perm])[inv]

    create_ok = create_elem & (count_before < cap)
    pop_ok = pop_elem & (count_before > 0)
    can_alloc = jnp.ones((b,), jnp.bool_)
    return dict(
        create_ok=create_ok,
        pop_ok=pop_ok,
        claim_ok=claim_ok,
        count_before=count_before,
        can_alloc=can_alloc,
        active=active,
    )


def _admission_slow(
    ecfg,
    *,
    is_create_cand,
    is_pop_cand,
    found0,
    first_create,
    free_slots0,
    init_count,
    rslot,
    gslot,
    free_top0,
    recipients0,
):
    """Exact sequential admission for the near-saturation regime.

    A tiny scan over counters only — no block values — so its per-op cost
    is bounded by a dozen scalar/[B]-element ops. Runs only when the bus
    or recipient table is within B of full (see module docstring for the
    leak analysis of the branch)."""
    b = rslot.shape[0]
    cap = ecfg.mailbox_cap
    iota = jnp.arange(b, dtype=U32)
    first_r = rslot == iota  # first op of each recipient group
    first_g = gslot == iota
    counts0 = jnp.where(first_r, init_count, 0)
    frees0 = jnp.where(first_g, free_slots0, 0)

    def step(carry, xs):
        n_alloc, recips, counts, frees, claimed = carry
        j, crt, pop, fnd, fc, r, g = xs
        cnt = counts[r]
        fs = frees[g]
        can_alloc = n_alloc < free_top0
        room = recips < U32(ecfg.max_recipients)
        claim = fc & ~fnd
        claim_ok = claim & (fs > 0) & room & can_alloc
        active = fnd | claimed[r] | claim_ok
        create_ok = crt & can_alloc & active & (cnt < cap)
        pop_ok = pop & active & (cnt > 0)
        counts = counts.at[r].set(cnt + create_ok.astype(I32) - pop_ok.astype(I32))
        frees = frees.at[g].set(fs - claim_ok.astype(I32))
        claimed = claimed.at[r].set(claimed[r] | claim_ok)
        n_alloc = n_alloc + create_ok.astype(U32)
        recips = recips + claim_ok.astype(U32)
        out = (create_ok, pop_ok, claim_ok, cnt, can_alloc, active)
        return (n_alloc, recips, counts, frees, claimed), out

    (_, _, _, _, _), outs = jax.lax.scan(
        step,
        (
            jnp.zeros((), U32),
            jnp.asarray(recipients0, U32),
            counts0.astype(I32),
            frees0.astype(I32),
            jnp.zeros((b,), jnp.bool_),
        ),
        (iota, is_create_cand, is_pop_cand, found0, first_create, rslot, gslot),
    )
    create_ok, pop_ok, claim_ok, count_before, can_alloc, active = outs
    return dict(
        create_ok=create_ok,
        pop_ok=pop_ok,
        claim_ok=claim_ok,
        count_before=count_before,
        can_alloc=can_alloc,
        active=active,
    )


# ----------------------------------------------------------------------
# phase A: mailbox round (capacity, append, zero-id select/pop)
# ----------------------------------------------------------------------


def phase_a_batch(ecfg: EngineConfig, ctx: dict):
    """Build the round-A ``apply_batch`` callback.

    ``ctx``: is_real/is_create/is_read/is_update/is_delete bool[B],
    id_zero, zero_recip bool[B]; ka u32[B,8]; idxs_mb2 u32[B,D] (the
    D=ecfg.mb_choices candidate table buckets per op; the round fetches
    all of them, flattened row-major); cand_idx u32[B]; id_rand u32[B,3];
    free_top0, recipients0, seq0 u32; now u32. The callback receives
    [B*D] rows and returns (out_a, final_val [B*D,V], final_alive [B*D]).

    Two-choice (D=2) semantics: an op's *effective* bucket is the
    candidate containing its recipient key, else — for a fresh claim —
    the candidate with more free key slots **at round start** (ties →
    candidate 0). The choice is resolved with masks over both fetched
    candidates, and both candidate rows are always written back, so the
    transcript never shows which candidate holds a recipient. Choosing
    by round-start occupancy (not claim-by-claim) keeps the admission
    walk vectorized; a claim can still fail if earlier in-round claims
    fill its chosen bucket — same order-sensitivity class as the
    existing claim_rank < free_slots0 rule, invisible to the oracle
    (placement never surfaces in responses)."""

    b = ctx["ka"].shape[0]
    d = ecfg.mb_choices
    k, cap = ecfg.mb_slots, ecfg.mailbox_cap
    is_real = ctx["is_real"]
    is_create_cand = ctx["is_create"] & is_real & ~ctx["zero_recip"]
    is_pop_cand = ctx["is_delete"] & ctx["id_zero"] & is_real
    is_zsel = (ctx["is_read"] | ctx["is_delete"]) & ctx["id_zero"] & is_real
    ka = ctx["ka"]
    idxs_mb2 = ctx["idxs_mb2"]  # u32[B,D]
    now = ctx["now"]
    m_sentinel = U32(ecfg.mb_table_buckets)
    iota = jnp.arange(b, dtype=U32)

    # recipient groups (ka equality); bucket groups move inside the
    # callback — the effective bucket depends on fetched occupancy
    groups_r = _recipient_groups(ka, is_real)
    rslot = groups_r.group_first()

    def apply_batch(vals0, present0):
        # --- candidate choice: [B*D] rows → per-op chosen views -------
        keys_c, entries_c = _mb_parse_batch(ecfg, vals0)  # [B*D,K,..]
        keys_c = keys_c.reshape(b, d, k, 8)
        entries_c = entries_c.reshape(b, d, k, cap, ENTRY_WORDS)
        key_valid_c = ~is_zero_words(keys_c)  # [B,D,K]
        match_c = key_valid_c & words_equal(keys_c, ka[:, None, None, :])
        found_c = jnp.any(match_c, axis=2)  # [B,D]
        free_c = (k - jnp.sum(key_valid_c, axis=2)).astype(I32)  # [B,D]
        if d == 1:
            chosen = jnp.zeros((b,), I32)
        else:
            emptier = jnp.argmax(free_c, axis=1).astype(I32)  # ties → 0
            chosen = jnp.where(
                jnp.any(found_c, axis=1),
                jnp.argmax(found_c, axis=1).astype(I32),
                emptier,
            )
        ch = chosen[:, None, None, None]
        keys0 = jnp.take_along_axis(keys_c, ch.astype(I32), axis=1)[:, 0]
        entries0 = jnp.take_along_axis(
            entries_c, ch[..., None].astype(I32), axis=1
        )[:, 0]
        eff_idx = jnp.take_along_axis(idxs_mb2, chosen[:, None], axis=1)[:, 0]
        eff_idx = jnp.where(is_real, eff_idx, m_sentinel + U32(1) + iota)

        # bucket groups over the effective bucket (dummies unique)
        groups_g = _index_groups(eff_idx, is_real)
        gslot = groups_g.group_first()
        glast = groups_g.group_last()

        key_valid0 = ~is_zero_words(keys0)  # [B,K]
        slot_match0 = key_valid0 & words_equal(keys0, ka[:, None, :])  # [B,K]
        found0 = jnp.any(slot_match0, axis=1) & is_real
        free_slots0 = (k - jnp.sum(key_valid0, axis=1)).astype(I32)
        # sorted_ent: my recipient's entries (zeros when mailbox absent)
        icount_sl, sorted_all, sorted_ent = _oldest_first(
            entries0, slot_match0
        )
        init_count = jnp.sum(
            (sorted_ent[:, :, ENT_SEQ] | sorted_ent[:, :, ENT_SEQH]) != 0,
            axis=1,
        ).astype(I32)

        first_create = is_create_cand & ~groups_r.any_before(is_create_cand)

        common = dict(
            is_create_cand=is_create_cand,
            is_pop_cand=is_pop_cand,
            found0=found0,
            first_create=first_create,
            free_slots0=free_slots0,
            init_count=init_count,
            rslot=rslot,
        )
        fast_ok = (ctx["free_top0"] >= U32(b)) & (
            ctx["recipients0"] + U32(b) <= U32(ecfg.max_recipients)
        )
        adm = jax.lax.cond(
            fast_ok,
            lambda: _admission_fast(
                ecfg, **common, groups_r=groups_r, groups_g=groups_g
            ),
            lambda: _admission_slow(
                ecfg,
                **common,
                gslot=gslot,
                free_top0=ctx["free_top0"],
                recipients0=ctx["recipients0"],
            ),
        )
        create_ok = adm["create_ok"]
        pop_ok = adm["pop_ok"]
        claim_ok = adm["claim_ok"]
        count_before = adm["count_before"]
        can_alloc = adm["can_alloc"]
        active = adm["active"]

        # --- allocation + ids (n-th successful create takes candidate n)
        grank = rank_of(create_ok)
        # clamp to the CANDIDATE array's extent, not this round's lane
        # count: under mailbox_choices=2 the lanes are B·D wide while
        # cand_idx is B wide, so `b - 1` let non-create lanes index past
        # the array (formally UB under PROMISE_IN_BOUNDS; XLA happened
        # to clamp). Create lanes always rank < B — the quota caps
        # successful creates at the batch size (rangelint finding).
        cand_cap = ctx["cand_idx"].shape[0] - 1
        alloc_idx = ctx["cand_idx"][jnp.minimum(grank, cand_cap)]
        # id words 0-1 = PRP-encrypted (nonce, block index): decodable
        # on-device, fresh random-looking values on every create even
        # when the LIFO freelist reuses a block (oblivious/prp.py; the
        # reference's random-id requirement, grapevine.proto:66-79).
        # Word 3 is forced odd so a real id is never all-zeroes.
        idr = ctx["id_rand"]
        w0, w1 = prp2_encrypt(
            ctx["id_key"], alloc_idx, idr[:, 0], ecfg.id_bits
        )
        new_id = jnp.stack([w0, w1, idr[:, 1], idr[:, 2] | U32(1)], axis=1)

        # --- zero-id selection: p-th oldest of [initial sorted ++ creates]
        pops_before = groups_r.counts_before(pop_ok)
        crank = groups_r.counts_before(create_ok)
        p = pops_before
        sel_from_init = p < init_count
        init_sel = _pth_entry(sorted_ent, p)  # [B, ENTRY_WORDS]
        q = p - init_count
        created = groups_r.select_by_rank(create_ok, new_id[:, :2], q)
        created_blk = created[:, 0]
        created_idw = created[:, 1]
        sel_blk = jnp.where(sel_from_init, init_sel[:, ENT_BLK], created_blk)
        sel_idw = jnp.where(sel_from_init, init_sel[:, ENT_IDW], created_idw)
        sel_found = is_zsel & active & (count_before > 0)
        rm_a = pop_ok

        # --- status (precedence documented in testing/reference.py) ----
        status_a = jnp.where(
            ctx["zero_recip"],
            U32(C.STATUS_CODE_INVALID_RECIPIENT),
            jnp.where(
                ~can_alloc,
                U32(C.STATUS_CODE_TOO_MANY_MESSAGES),
                jnp.where(
                    ~active,
                    U32(C.STATUS_CODE_TOO_MANY_RECIPIENTS),
                    jnp.where(
                        count_before >= cap,
                        U32(C.STATUS_CODE_TOO_MANY_MESSAGES_FOR_RECIPIENT),
                        U32(C.STATUS_CODE_SUCCESS),
                    ),
                ),
            ),
        )

        # --- final block assembly (committed at each group's last op) --
        # claimed key slot per claim op: the claim_rank-th free slot
        free_rank = jnp.cumsum(~key_valid0, axis=1) - (~key_valid0)  # [B,K]
        claim_rank = groups_g.counts_before(claim_ok)
        claim_slot_oh = (
            (~key_valid0) & (free_rank == claim_rank[:, None]) & claim_ok[:, None]
        )  # [B,K]
        # my recipient's key slot (original or claimed). The claim lives
        # at the group's first-*create* op, which need not be the group's
        # first op (a zero-id R/D by the same recipient may precede it in
        # slot order), so OR-aggregate over the whole group — at most one
        # op per group has claim_ok.
        claim_slot_r = groups_r.total_or_rows(claim_slot_oh)  # [B,K]
        mslot_oh = jnp.where(found0[:, None], slot_match0, claim_slot_r)
        mslot_idx = jnp.argmax(mslot_oh, axis=1).astype(U32)
        has_mslot = jnp.any(mslot_oh, axis=1)

        # keys: scatter claims into their group-representative rows
        ctgt = (
            jnp.where(claim_ok, glast, U32(b)),
            jnp.where(claim_ok, jnp.argmax(claim_slot_oh, axis=1).astype(U32), U32(k)),
        )
        # at most one claim per group (claim_ok), and claims target
        # their group-representative row — in-bounds targets unique
        keys_fin = keys0.at[ctgt].set(ka, mode="drop", unique_indices=True)

        # initial entries: survivors shift down by popped_init per slot
        # T[r,s]: total pops in r's group landing on slot s
        pop_sl = mslot_oh & pop_ok[:, None]  # [B,K]
        T = groups_g.total_sum_rows(pop_sl)  # [B,K] i32
        popped_init_sl = jnp.minimum(T, icount_sl)  # [B,K]
        ents_fin = _drop_oldest(sorted_all, icount_sl, popped_init_sl)

        # created entries: survivors append after the surviving initials
        T_r = groups_r.total_sum(pop_ok)  # total pops in my group
        popped_init_r = jnp.minimum(T_r, init_count)
        popped_created_r = T_r - popped_init_r
        surv = create_ok & (crank >= popped_created_r) & has_mslot
        # pos >= 0 on every lane etgt consumes: surv requires
        # crank >= popped_created_r, and popped_init_r = min(T, init) <=
        # init_count always; the max states that invariant for interval
        # reasoning (non-surv lanes carry masked garbage either way)
        pos = jnp.maximum(
            (init_count - popped_init_r) + (crank - popped_created_r), 0
        )
        etgt = (
            jnp.where(surv, glast, U32(b)),
            jnp.where(surv, mslot_idx, U32(k)),
            jnp.where(surv, pos.astype(U32), U32(cap)),
        )
        sq_lo, sq_hi = u64_add_u32(ctx["seq0"][0], ctx["seq0"][1], iota)
        new_entry = jnp.stack(
            [
                new_id[:, 0],
                new_id[:, 1],
                sq_lo,
                sq_hi,
                jnp.full((b,), now, U32),
                jnp.full((b,), ctx["now_hi"], U32),
            ],
            axis=1,
        )
        # distinct (group row, slot, rank) per surviving create — unique
        ents_fin = ents_fin.at[etgt].set(
            new_entry, mode="drop", unique_indices=True
        )

        assembled = _mb_pack_batch(ecfg, keys_fin, ents_fin)  # [B,V]
        assembled_alive = jnp.any(~is_zero_words(keys_fin), axis=1)  # [B]

        # --- row commit: every fetched row of a bucket carries the
        # bucket's final state (oram_round commits whichever row is the
        # bucket's LAST occurrence in the flattened [B*D] order — which
        # may be another op's *unchosen* candidate, so pass-through rows
        # must hold the committed value too). Dense bucket → last-
        # choosing-op map: one scatter-max + one gather.
        op_map = (
            jnp.full((ecfg.mb_table_buckets + 1,), -1, I32)
            .at[jnp.where(is_real, eff_idx, m_sentinel + U32(1))]
            .max(iota.astype(I32), mode="drop")
        )
        rows_idx = idxs_mb2.reshape(b * d)
        g = op_map[jnp.minimum(rows_idx, m_sentinel)]  # [B*D]; -1 = none
        has_g = (g >= 0) & (rows_idx < m_sentinel)
        gc = jnp.clip(g, 0, b - 1)
        final_val = jnp.where(has_g[:, None], assembled[gc], vals0)
        final_alive = jnp.where(has_g, assembled_alive[gc], present0)

        out_a = {
            "create_ok": create_ok,
            "status_a": status_a,
            "sel_blk": sel_blk,
            "sel_idw": sel_idw,
            "sel_found": sel_found,
            "rm_a": rm_a,
            "alloc_idx": alloc_idx,
            "new_id": new_id,
            "n_claims": jnp.sum(claim_ok.astype(U32)),
            "n_allocs": jnp.sum(create_ok.astype(U32)),
        }
        return out_a, final_val, final_alive

    return apply_batch


# ----------------------------------------------------------------------
# phase B: records round (verify, insert, mutate, remove)
# ----------------------------------------------------------------------


def phase_b_batch(ecfg: EngineConfig, ctx: dict):
    """Round-B callback. ``ctx`` adds: idx_b u32[B] (dummy-routed block
    keys), real_b bool[B], create_ok, new_id u32[B,4], sel_blk, sel_idw,
    auth/recipient u32[B,8], msg_id u32[B,4], payload u32[B,234],
    plus the request-type masks and now."""

    b = ctx["idx_b"].shape[0]
    realb = ctx["real_b"]
    # record-block groups; dummies (idx_b = rec.dummy_index, shared)
    # must stay singletons, exactly as the realb-masked dense equality
    groups_k = _index_groups(ctx["idx_b"], realb)
    now = ctx["now"]
    create_ev = ctx["is_create"] & ctx["create_ok"] & realb

    def apply_batch(vals0, present0):
        init_id = vals0[:, REC_ID]
        init_sender = vals0[:, REC_SENDER]
        init_recip = vals0[:, REC_RECIPIENT]
        init_ts = vals0[:, REC_TS : REC_TSH + 1]  # u32[B,2] (lo, hi)
        init_payload = vals0[:, REC_PAYLOAD]

        # identity fields are fixed per key: creation (in-round) or initial
        c_idx, has_c = groups_k.first_flag_index(create_ev)
        sid = jnp.where(has_c[:, None], ctx["new_id"][c_idx], init_id)
        ssender = jnp.where(has_c[:, None], ctx["auth"][c_idx], init_sender)
        srecip = jnp.where(has_c[:, None], ctx["recipient"][c_idx], init_recip)

        match4 = words_equal(sid, ctx["msg_id"])
        match2 = (sid[:, 0] == ctx["sel_blk"]) & (sid[:, 1] == ctx["sel_idw"])
        mtc = jnp.where(ctx["id_zero"], match2, match4) & ~ctx["is_create"] & realb
        auth_ok = words_equal(ctx["auth"], ssender) | words_equal(
            ctx["auth"], srecip
        )
        recip_match = words_equal(ctx["recipient"], srecip)

        del_pred = (
            ctx["is_delete"] & mtc & auth_ok & (ctx["id_zero"] | recip_match)
        )
        created_before = groups_k.any_before(create_ev)
        base_alive = (present0 & realb) | created_before
        killed_before = groups_k.any_before(del_pred & base_alive)
        alive = base_alive & ~killed_before

        match_ok = alive & mtc
        read_ok = ctx["is_read"] & match_ok & auth_ok
        upd_ok = ctx["is_update"] & match_ok & auth_ok & recip_match
        del_ok = del_pred & alive

        # last payload/ts writer at-or-before me (me included for my own
        # update/create); reads see the state before themselves
        W = create_ev | upd_ok
        lw = groups_k.last_flag_index_upto(W)
        has_w = lw >= 0
        lwc = jnp.clip(lw, 0, b - 1)
        resp_payload = jnp.where(
            has_w[:, None], ctx["payload"][lwc], init_payload
        )
        now2 = jnp.stack([now, ctx["now_hi"]]).astype(U32)
        resp_ts = jnp.where(has_w[:, None], now2[None, :], init_ts)

        out_b = {
            "read_ok": read_ok,
            "upd_ok": upd_ok,
            "del_ok": del_ok,
            "match_ok": mtc & alive,
            "auth_ok": auth_ok,
            "recip_match": recip_match,
            "resp_id": sid,
            "resp_sender": ssender,
            "resp_recipient": srecip,
            "resp_ts": resp_ts,
            "resp_payload": resp_payload,
        }

        # final per-key state
        any_create = groups_k.total_or(create_ev)
        any_del = groups_k.total_or(del_ok)
        final_alive = ((present0 & realb) | any_create) & ~any_del
        lwf = groups_k.last_flag_index(W)
        has_wf = lwf >= 0
        lwfc = jnp.clip(lwf, 0, b - 1)
        fin_payload = jnp.where(
            has_wf[:, None], ctx["payload"][lwfc], init_payload
        )
        fin_ts = jnp.where(has_wf[:, None], now2[None, :], init_ts)
        final_val = jnp.concatenate(
            [sid, ssender, srecip, fin_ts, fin_payload], axis=1
        )
        return out_b, final_val, final_alive

    return apply_batch


# ----------------------------------------------------------------------
# phase C: mailbox finalization (explicit-delete removal, update refresh)
# ----------------------------------------------------------------------


def phase_c_batch(ecfg: EngineConfig, ctx: dict):
    """Round-C callback. ``ctx`` adds: del_ok, upd_ok, rm_a bool[B] (from
    rounds A/B), msg_id u32[B,4], ka u32[B,8], idxs_mb2 u32[B,D].

    Like round A the callback sees all D candidate rows per op; an op's
    mutations (explicit-delete clear, update timestamp refresh) land in
    whichever candidate holds its recipient key, and are aggregated onto
    EVERY fetched row of that bucket so the round's last-occurrence
    commit (oram_round) writes them regardless of which op's row wins.
    The dense impl aggregates with a [B·D,B] one-hot matmul; the scan
    impl scatter-adds per-bucket mutation vectors into a
    [table_buckets, K·cap] table and gathers per row — the same dense
    bucket-table idiom phase A's op_map already uses."""

    b = ctx["ka"].shape[0]
    d = ecfg.mb_choices
    k, cap = ecfg.mb_slots, ecfg.mailbox_cap
    is_real = ctx["is_real"]
    idxs_mb2 = ctx["idxs_mb2"]
    m_sentinel = U32(ecfg.mb_table_buckets)
    rm_c = ctx["del_ok"] & ~ctx["rm_a"] & is_real
    refresh = ctx["upd_ok"] & is_real
    now = ctx["now"]

    def apply_batch(vals0, present0):
        keys_c, entries_c = _mb_parse_batch(ecfg, vals0)
        keys_c = keys_c.reshape(b, d, k, 8)
        entries_c = entries_c.reshape(b, d, k, cap, ENTRY_WORDS)
        key_valid_c = ~is_zero_words(keys_c)
        match_c = key_valid_c & words_equal(
            keys_c, ctx["ka"][:, None, None, :]
        )  # [B,D,K]
        found_c = jnp.any(match_c, axis=2)  # [B,D]
        chosen = (
            jnp.zeros((b,), I32)
            if d == 1
            else jnp.argmax(found_c, axis=1).astype(I32)
        )
        ch = chosen[:, None, None, None]
        slot_match = jnp.take_along_axis(match_c, ch[:, :, :, 0], axis=1)[:, 0]
        entries0 = jnp.take_along_axis(
            entries_c, ch[..., None].astype(I32), axis=1
        )[:, 0]  # [B,K,cap,4]
        eff_idx = jnp.take_along_axis(idxs_mb2, chosen[:, None], axis=1)[:, 0]
        mutating = (rm_c | refresh) & jnp.any(found_c, axis=1)
        eff_idx = jnp.where(mutating, eff_idx, m_sentinel)

        # my (slot, entry) matches: entry holds my msg_id's (blk, idw)
        ent_valid = (
            entries0[:, :, :, ENT_SEQ] | entries0[:, :, :, ENT_SEQH]
        ) != 0
        em = (
            ent_valid
            & (entries0[:, :, :, ENT_BLK] == ctx["msg_id"][:, 0, None, None])
            & (entries0[:, :, :, ENT_IDW] == ctx["msg_id"][:, 1, None, None])
            & slot_match[:, :, None]
        )  # [B,K,cap]
        u_clear = (em & rm_c[:, None, None]).reshape(b, k * cap)
        u_refresh = (em & refresh[:, None, None]).reshape(b, k * cap)

        # aggregate op mutations onto every row of the op's bucket
        rows_idx = idxs_mb2.reshape(b * d)  # [B*D]
        row_op = (rows_idx[:, None] == eff_idx[None, :]) & mutating[None, :]
        clear = _bool_matmul(row_op, u_clear).reshape(b * d, k, cap)
        refr = _bool_matmul(row_op, u_refresh).reshape(b * d, k, cap)

        rows_entries = entries_c.reshape(b * d, k, cap, ENTRY_WORDS)
        rows_keys = keys_c.reshape(b * d, k, 8)
        ents = jnp.where(
            refr[:, :, :, None],
            rows_entries.at[:, :, :, ENT_TS]
            .set(now)
            .at[:, :, :, ENT_TSH]
            .set(ctx["now_hi"]),
            rows_entries,
        )
        ents = jnp.where(clear[:, :, :, None], U32(0), ents)
        final_val = _mb_pack_batch(ecfg, rows_keys, ents)
        final_alive = present0  # sticky slots: blocks persist until sweep
        return {}, final_val, final_alive

    return apply_batch
