"""Dense-vs-scan vphases equivalence: bit-identical engines, no [B,B].

The tentpole contract of the scan slot-order machinery
(engine/vphases.py, ``vphases_impl="scan"``):

1. responses AND final engine state bit-identical to the dense impl —
   randomized oracle campaigns over op mixes heavy in same-key chains,
   zero-id pops, saturation-fallback rounds, and single-op batches
   (the same contract the cipher impls carry, testing/compare.py);
2. the scan impl's jaxpr materializes NO [B,B]-shaped intermediate at
   B=256 (asserted on the traced jaxpr, with the dense impl as the
   positive control proving the checker sees such intermediates).

The fast campaign count keeps tier-1 within budget; the full ≥200-
campaign sweep runs under ``-m slow`` (and was run at PR time — see
PERF.md Round 6). Set $GRAPEVINE_VPHASES_CAMPAIGNS to override.
"""

import functools
import os
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from grapevine_tpu.config import GrapevineConfig
from grapevine_tpu.engine.batcher import GrapevineEngine
from grapevine_tpu.engine.round_step import engine_round_step
from grapevine_tpu.engine.state import (
    ENT_SEQ,
    ENT_SEQH,
    ENTRY_WORDS,
    EngineConfig,
    ID_WORDS,
    KEY_WORDS,
    PAYLOAD_WORDS,
    init_engine,
)
from grapevine_tpu.engine.vphases import (
    _drop_oldest,
    _oldest_first,
    _pth_entry,
    phase_a_batch,
)
from grapevine_tpu.oblivious.primitives import shift_down
from grapevine_tpu.testing.reference import ReferenceEngine
from grapevine_tpu.wire import constants as C
from grapevine_tpu.wire.records import QueryRequest, RequestRecord

NOW = 1_700_000_000

BASE = dict(
    bucket_cipher_rounds=0,
    max_messages=64,
    max_recipients=8,
    mailbox_cap=4,
    batch_size=8,
    stash_size=96,
)
#: bus within B of full from the start (free_top < B after one round of
#: creates) — every later round takes the _admission_slow lax.scan
#: branch; mailbox_cap raised so the bus quota binds before the
#: per-recipient cap
SAT_BUS = dict(BASE, max_messages=16, mailbox_cap=16)
#: recipient table can never cover a full batch (recipients0 + B > max)
#: — the slow branch runs from round one
SAT_RECIP = dict(BASE, max_recipients=4)


def key(n: int) -> bytes:
    return bytes([n & 0xFF, (n >> 8) ^ 0x5A]) + b"\x01" * 30


def req(rt, auth, msg_id=C.ZERO_MSG_ID, recipient=C.ZERO_PUBKEY, tag=0):
    return QueryRequest(
        request_type=rt,
        auth_identity=auth,
        auth_signature=b"\x01" * C.SIGNATURE_SIZE,
        record=RequestRecord(
            msg_id=msg_id,
            recipient=recipient,
            payload=bytes([tag & 0xFF]) * C.PAYLOAD_SIZE,
        ),
    )


def _mk_pair(cfg_kwargs, seed):
    dense = GrapevineEngine(
        GrapevineConfig(vphases_impl="dense", **cfg_kwargs), seed=seed
    )
    scan = GrapevineEngine(
        GrapevineConfig(vphases_impl="scan", **cfg_kwargs), seed=seed
    )
    return dense, scan


def _assert_responses_bitequal(rd, rs, ctx=""):
    for j, (d, s) in enumerate(zip(rd, rs)):
        assert d.status_code == s.status_code, f"{ctx} slot {j}: status"
        assert d.record.msg_id == s.record.msg_id, f"{ctx} slot {j}: id"
        assert d.record.sender == s.record.sender, f"{ctx} slot {j}: sender"
        assert d.record.recipient == s.record.recipient, f"{ctx} slot {j}"
        assert d.record.timestamp == s.record.timestamp, f"{ctx} slot {j}: ts"
        assert d.record.payload == s.record.payload, f"{ctx} slot {j}: payload"


def _assert_states_bitequal(ea, eb, ctx=""):
    la = jax.tree_util.tree_leaves_with_path(ea.state)
    lb = jax.tree_util.tree_leaves(eb.state)
    for (path, x), y in zip(la, lb):
        assert np.array_equal(np.asarray(x), np.asarray(y)), (
            f"{ctx}: state diverges at {jax.tree_util.keystr(path)}"
        )


def _gen_batch(rng, idents, live_ids, n):
    """Op mix heavy in same-key chains and zero-id pops; explicit-id
    R/U/D drawn from live ids (stale ids → NOT_FOUND, also exercised)."""
    reqs = []
    for _ in range(n):
        r = rng.random()
        a = idents[rng.integers(len(idents))]
        x = idents[rng.integers(len(idents))]
        if r < 0.30:
            reqs.append(
                req(C.REQUEST_TYPE_CREATE, a, recipient=x,
                    tag=int(rng.integers(256)))
            )
        elif r < 0.34:  # zero recipient → INVALID_RECIPIENT
            reqs.append(req(C.REQUEST_TYPE_CREATE, a))
        elif r < 0.55:
            reqs.append(req(C.REQUEST_TYPE_READ, a))  # zero-id pop-read
        elif r < 0.72:
            reqs.append(req(C.REQUEST_TYPE_DELETE, a))  # zero-id pop
        elif live_ids and r < 0.82:
            mid, owner = live_ids[rng.integers(len(live_ids))]
            reqs.append(req(C.REQUEST_TYPE_READ, a, msg_id=mid))
        elif live_ids and r < 0.92:
            mid, owner = live_ids[rng.integers(len(live_ids))]
            rcp = owner if rng.random() < 0.7 else x
            reqs.append(
                req(C.REQUEST_TYPE_UPDATE, owner, msg_id=mid, recipient=rcp,
                    tag=int(rng.integers(256)))
            )
        elif live_ids:
            mid, owner = live_ids[rng.integers(len(live_ids))]
            reqs.append(
                req(C.REQUEST_TYPE_DELETE, owner, msg_id=mid, recipient=owner)
            )
        else:
            reqs.append(req(C.REQUEST_TYPE_READ, x))
    return reqs


def _run_campaign(cfg_kwargs, seed, n_batches=3, batch_fill=None,
                  mk_pair=None):
    """One campaign: a fresh engine A/B pair + oracle, mixed batches.

    Asserts pair ≡ bitwise (responses, then final state) and both
    ≡ oracle semantics (forced-id comparison, counts included).
    ``mk_pair`` builds the (a, b) engines under test — default the
    dense/scan vphases pair; tests/test_sort_radix.py reuses the whole
    campaign with an xla/radix sort pair instead.
    """
    rng = np.random.default_rng(seed)
    dense, scan = (mk_pair or _mk_pair)(
        cfg_kwargs, seed=int(rng.integers(1 << 30))
    )
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
        rd = dense.handle_queries(reqs, t)
        rs = scan.handle_queries(reqs, t)
        _assert_responses_bitequal(rd, rs, f"seed {seed} batch {bi}")
        forced = [
            d.record.msg_id
            if r.request_type == C.REQUEST_TYPE_CREATE
            and d.status_code == C.STATUS_CODE_SUCCESS
            else None
            for r, d in zip(reqs, rd)
        ]
        ro = oracle.handle_batch(reqs, t, forced)
        for j, (r, d, o) in enumerate(zip(reqs, rd, ro)):
            assert d.status_code == o.status_code, (
                f"seed {seed} batch {bi} slot {j}: engine "
                f"{d.status_code} != oracle {o.status_code}"
            )
            assert d.record.msg_id == o.record.msg_id
            assert d.record.payload == o.record.payload
            assert d.record.timestamp == o.record.timestamp
        assert dense.message_count() == oracle.message_count()
        assert dense.recipient_count() == oracle.recipient_count()
        for r, d in zip(reqs, rd):
            if (
                r.request_type == C.REQUEST_TYPE_CREATE
                and d.status_code == C.STATUS_CODE_SUCCESS
            ):
                live_ids.append((d.record.msg_id, r.record.recipient))
            elif (
                r.request_type == C.REQUEST_TYPE_DELETE
                and d.status_code == C.STATUS_CODE_SUCCESS
            ):
                live_ids = [
                    (m, o_) for m, o_ in live_ids if m != d.record.msg_id
                ]
    _assert_states_bitequal(dense, scan, f"seed {seed}")


def _campaign_plan(n_total):
    """Distribute campaigns over the regimes; every regime represented."""
    plans = []
    for i in range(n_total):
        r = i % 10
        if r < 5:
            plans.append((BASE, None))  # steady-state fast path
        elif r < 7:
            plans.append((SAT_BUS, None))  # bus saturation fallback
        elif r < 9:
            plans.append((SAT_RECIP, None))  # recipient-table fallback
        else:
            plans.append((BASE, 1))  # single-op batches (dummy-padded)
    return plans


_FAST_N = int(os.environ.get("GRAPEVINE_VPHASES_CAMPAIGNS", "8"))


def test_randomized_ab_campaigns():
    """Budget-shaped fast set: the cost is ~all jit compiles (one per
    distinct geometry × impl), so the fast plan spans two geometries —
    steady-state and bus-saturation. Both saturation regimes resolve
    through the same _admission_slow scan (only the tripping guard
    differs), so bus-saturation keeps the fallback branch covered; the
    recipient-table geometry runs in the -m slow full sweep."""
    for i, (cfg, fill) in enumerate(_campaign_plan(_FAST_N)):
        if cfg is SAT_RECIP:
            cfg = SAT_BUS
        _run_campaign(cfg, seed=1000 + i, batch_fill=fill)


@pytest.mark.slow
def test_randomized_ab_campaigns_full():
    """The full ≥200-campaign acceptance sweep (run at PR time; kept
    under -m slow so tier-1 stays within its budget)."""
    for i, (cfg, fill) in enumerate(_campaign_plan(220)):
        _run_campaign(cfg, seed=5000 + i, batch_fill=fill)


@pytest.mark.slow  # two extra engine compiles (~15 s); the B=1 segment
# edge cases are covered always-on by the segmented property tests and
# the fill=1 campaigns in the fast plan
def test_single_op_batch_engine_ab():
    """batch_size=1 end to end: the sort/scan machinery at B=1 (segment
    logic degenerate cases) stays bit-identical and oracle-true."""
    cfg = dict(BASE, batch_size=1)
    for i in range(6):
        _run_campaign(cfg, seed=300 + i, n_batches=6, batch_fill=1)


def test_saturation_fallback_engaged_and_bitequal():
    """Drive the bus to saturation so fast_ok is False (free_top < B):
    rounds resolve through _admission_slow under both impls and must
    stay bit-identical, including TOO_MANY_MESSAGES admission order."""
    dense, scan = _mk_pair(SAT_BUS, seed=9)
    a, x = key(1), key(2)
    t = NOW
    # 3 full batches of creates against max_messages=16: round 2 onward
    # runs with free_top < B=8 → the lax.scan branch
    for bi in range(3):
        reqs = [
            req(C.REQUEST_TYPE_CREATE, a, recipient=x, tag=bi * 8 + j)
            for j in range(8)
        ]
        rd = dense.handle_queries(reqs, t + bi)
        rs = scan.handle_queries(reqs, t + bi)
        _assert_responses_bitequal(rd, rs, f"sat batch {bi}")
    assert dense.message_count() <= 16
    codes = {r.status_code for r in rd}
    assert C.STATUS_CODE_TOO_MANY_MESSAGES in codes  # quota actually hit
    _assert_states_bitequal(dense, scan, "saturation")


# ----------------------------------------------------------------------
# jaxpr shape audit: the scan impl materializes no [B,B] intermediate
# ----------------------------------------------------------------------

JAXPR_B = 256


def _iter_jaxprs(jaxpr):
    yield jaxpr
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            vs = v if isinstance(v, (list, tuple)) else (v,)
            for x in vs:
                inner = getattr(x, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    yield from _iter_jaxprs(inner)
                elif hasattr(x, "eqns"):
                    yield from _iter_jaxprs(x)


def _quadratic_avals(jaxpr, b):
    """All bool/f32 avals in the jaxpr with ≥2 axes of extent ≥ b.

    Record values are u32[B, 256] at the 1KB record size — exactly B
    words wide at B=256 — so a ``jnp.where(mask[:, None], rows, ...)``
    over record rows carries a broadcast bool predicate of shape
    (B, 256) that is batch×value-width, not a same-key matrix. Those
    two representational primitives (the predicate broadcast and the
    select it feeds) are excluded for bools; every *computational* use
    of a genuine [B,B] mask (and/or/reduce/convert, and the f32 one-hot
    matmul operands) remains audited, which the dense positive-control
    test proves is sufficient to detect the dense impl.
    """
    bad = []
    skip_bool = ("select_n", "broadcast_in_dim")
    for jx in _iter_jaxprs(jaxpr):
        for eqn in jx.eqns:
            for var in list(eqn.invars) + list(eqn.outvars):
                aval = getattr(var, "aval", None)
                shape = getattr(aval, "shape", ())
                dtype = getattr(aval, "dtype", None)
                if dtype is None:
                    continue
                if dtype not in (jnp.bool_, jnp.float32):
                    continue
                if dtype == jnp.bool_ and eqn.primitive.name in skip_bool:
                    continue
                if sum(1 for dim in shape if dim >= b) >= 2:
                    bad.append((eqn.primitive.name, str(dtype), tuple(shape)))
    return bad


def _trace_engine_jaxpr(impl):
    cfg = GrapevineConfig(
        max_messages=1 << 12,
        max_recipients=1 << 8,
        mailbox_cap=4,
        batch_size=JAXPR_B,
        bucket_cipher_rounds=0,
        stash_size=512,
        vphases_impl=impl,
    )
    ecfg = EngineConfig.from_config(cfg)
    state = jax.eval_shape(lambda: init_engine(ecfg, 0))
    b = JAXPR_B
    u32 = jnp.uint32
    batch = {
        "req_type": jax.ShapeDtypeStruct((b,), u32),
        "auth": jax.ShapeDtypeStruct((b, KEY_WORDS), u32),
        "msg_id": jax.ShapeDtypeStruct((b, ID_WORDS), u32),
        "recipient": jax.ShapeDtypeStruct((b, KEY_WORDS), u32),
        "payload": jax.ShapeDtypeStruct((b, PAYLOAD_WORDS), u32),
        "now": jax.ShapeDtypeStruct((), u32),
        "now_hi": jax.ShapeDtypeStruct((), u32),
    }
    return jax.make_jaxpr(functools.partial(engine_round_step, ecfg))(
        state, batch
    ).jaxpr


def test_scan_jaxpr_has_no_quadratic_intermediate():
    bad = _quadratic_avals(_trace_engine_jaxpr("scan"), JAXPR_B)
    assert not bad, (
        f"scan impl materializes quadratic mask intermediates at "
        f"B={JAXPR_B}: {sorted(set(bad))[:8]}"
    )


def test_dense_jaxpr_audit_positive_control():
    """The dense impl DOES materialize [B,B] masks — proving the audit
    actually detects the intermediates the scan test asserts away."""
    bad = _quadratic_avals(_trace_engine_jaxpr("dense"), JAXPR_B)
    assert bad, "audit found no [B,B] intermediates even in the dense impl"


# ----------------------------------------------------------------------
# order along a mailbox's cap axis (PR 31): one sort that carries the
# entries, masked reductions and a barrel shift, against the gathers
# they replaced — kept here, in the parent's words, as the reference
# ----------------------------------------------------------------------


def _ref_lex_argsort(lo, hi, axis):
    p1 = jnp.argsort(lo, axis=axis, stable=True)
    hi_p = jnp.take_along_axis(hi, p1, axis=axis)
    p2 = jnp.argsort(hi_p, axis=axis, stable=True)
    return jnp.take_along_axis(p1, p2, axis=axis)


def _ref_mailbox_order(entries0, slot_match0, p, popped):
    """Round A's ordering as the parent of PR 31 wrote it: two-pass
    argsorts composed by gathers, rows moved by ``take_along_axis``."""
    u32, i32 = jnp.uint32, jnp.int32
    cap = entries0.shape[2]
    inf = u32(0xFFFFFFFF)
    ent_r = jnp.sum(
        entries0 * slot_match0[:, :, None, None].astype(u32), axis=1
    )
    ent_valid = (ent_r[:, :, ENT_SEQ] | ent_r[:, :, ENT_SEQH]) != 0
    sk_lo = jnp.where(ent_valid, ent_r[:, :, ENT_SEQ], inf)
    sk_hi = jnp.where(ent_valid, ent_r[:, :, ENT_SEQH], inf)
    order = _ref_lex_argsort(sk_lo, sk_hi, axis=1)
    sorted_ent = jnp.take_along_axis(ent_r, order[:, :, None], axis=1)
    pi = jnp.clip(p, 0, cap - 1)
    init_sel = jnp.take_along_axis(
        sorted_ent, pi[:, None, None], axis=1
    )[:, 0, :]
    valid_all = (
        entries0[:, :, :, ENT_SEQ] | entries0[:, :, :, ENT_SEQH]
    ) != 0
    icount_sl = jnp.sum(valid_all, axis=2).astype(i32)
    sk_lo_all = jnp.where(valid_all, entries0[:, :, :, ENT_SEQ], inf)
    sk_hi_all = jnp.where(valid_all, entries0[:, :, :, ENT_SEQH], inf)
    order_all = _ref_lex_argsort(sk_lo_all, sk_hi_all, axis=2)
    sorted_all = jnp.take_along_axis(
        entries0, order_all[:, :, :, None], axis=2
    )
    e_iota = jnp.arange(cap, dtype=i32)[None, None, :]
    src = e_iota + popped[:, :, None]
    keepm = src < icount_sl[:, :, None]
    ents_fin = jnp.where(
        keepm[:, :, :, None],
        jnp.take_along_axis(
            sorted_all, jnp.clip(src, 0, cap - 1)[:, :, :, None], axis=2
        ),
        u32(0),
    )
    return icount_sl, sorted_all, sorted_ent, init_sel, ents_fin


def _new_mailbox_order(entries0, slot_match0, p, popped):
    icount_sl, sorted_all, sorted_ent = _oldest_first(entries0, slot_match0)
    return (
        icount_sl,
        sorted_all,
        sorted_ent,
        _pth_entry(sorted_ent, p),
        _drop_oldest(sorted_all, icount_sl, popped),
    )


def _mailbox_cases(cap, seed):
    """entries u32[b,K,cap,W], slot_match bool[b,K], p i32[b], popped
    i32[b,K]: rows 0..cap shift slot 0 by their own index (every shift
    0..cap, beside random ones in the other slots); mailboxes random
    with holes, all holes and full; holes carry sequence 0 and
    duplicate garbage in their other words; sequence numbers distinct
    per mailbox, some with only the high lane set, some only the low."""
    rng = np.random.default_rng(seed)
    k, w = 4, ENTRY_WORDS
    b = cap + 1 + 7
    entries = rng.integers(1, 1 << 32, (b, k, cap, w), dtype=np.uint64)
    garbage = rng.integers(0, 3, (b, k, cap, w), dtype=np.uint64) * 0xABCD
    seq = np.stack(
        [rng.permutation(cap) + 1 for _ in range(b * k)]
    ).reshape(b, k, cap).astype(np.uint64)
    lane = rng.integers(0, 3, (b, k, 1))  # 0: low only, 1: high only, 2: both
    entries[..., ENT_SEQ] = np.where(
        lane == 1, 0, seq * np.uint64(0x01000193) % (1 << 32) + 1
    )
    entries[..., ENT_SEQH] = np.where(lane == 0, 0, seq)
    fill = rng.integers(0, 3, (b, k, 1))  # 0: all holes, 1: random, 2: full
    valid = np.where(
        fill == 1, rng.random((b, k, cap)) < 0.6, fill == 2
    )
    entries = np.where(valid[..., None], entries, garbage)
    entries[..., ENT_SEQ] *= valid
    entries[..., ENT_SEQH] *= valid
    match = np.zeros((b, k), bool)
    hit = rng.integers(0, k + 1, b)  # k: no slot holds my recipient
    match[np.arange(b)[hit < k], hit[hit < k]] = True
    popped = rng.integers(0, cap + 1, (b, k))
    popped[: cap + 1, 0] = np.arange(cap + 1)
    p = rng.integers(0, cap + 3, b)
    return (
        jnp.asarray(entries, jnp.uint32),
        jnp.asarray(match),
        jnp.asarray(p, jnp.int32),
        jnp.asarray(popped, jnp.int32),
    )


@pytest.mark.parametrize("cap", [8, 62])
def test_mailbox_order_equals_the_gather_formulation(cap):
    for seed in range(3):
        case = _mailbox_cases(cap, seed)
        want = jax.jit(_ref_mailbox_order)(*case)
        got = jax.jit(_new_mailbox_order)(*case)
        names = "icount_sl sorted_all sorted_ent init_sel ents_fin".split()
        for name, g, r in zip(names, got, want):
            assert g.dtype == r.dtype and g.shape == r.shape, name
            assert np.array_equal(np.asarray(g), np.asarray(r)), (
                f"{name} differs from the gather formulation "
                f"(cap={cap}, seed={seed})"
            )


def test_shift_down_every_shift_and_width():
    for n in (1, 2, 7, 8, 62, 64):
        x = jnp.arange(1, n + 1, dtype=jnp.uint32)[None, :] + jnp.zeros(
            (n + 1, 1), jnp.uint32
        )
        s = jnp.arange(n + 1, dtype=jnp.int32)[:, None]
        got = np.asarray(shift_down(x, s, axis=1))
        want = np.zeros((n + 1, n), np.uint32)
        for r in range(n + 1):
            want[r, : n - r] = np.arange(r + 1, n + 1)
        assert np.array_equal(got, want), n


def _per_element_gathers(jaxpr, min_slices, max_slice_words):
    """(result shape, slice sizes) of every ``gather`` that fetches
    ``min_slices`` or more slices of at most ``max_slice_words`` words
    each: the form a TPU runs one element at a time."""
    bad = []
    for jx in _iter_jaxprs(jaxpr):
        for eqn in jx.eqns:
            if eqn.primitive.name != "gather":
                continue
            words = int(np.prod(eqn.params["slice_sizes"]))
            shape = tuple(eqn.outvars[0].aval.shape)
            if (
                words <= max_slice_words
                and int(np.prod(shape)) // words >= min_slices
            ):
                bad.append((shape, tuple(eqn.params["slice_sizes"])))
    return bad


def _trace_round_a_apply(impl):
    """The jaxpr of round A's callback alone: ``phase_a_batch``'s
    precomputation and its ``apply_batch`` over [B*D] fetched rows."""
    ecfg = EngineConfig.from_config(
        GrapevineConfig(**{**BASE, "batch_size": JAXPR_B, "mailbox_cap": 8},
                        vphases_impl=impl)
    )
    state = jax.eval_shape(lambda: init_engine(ecfg, 0))
    b, d = JAXPR_B, ecfg.mb_choices
    s = jax.ShapeDtypeStruct
    u32 = jnp.uint32
    flag = s((b,), jnp.bool_)
    ctx = dict(
        {n: flag for n in ("is_real", "is_create", "is_read", "is_update",
                           "is_delete", "id_zero", "zero_recip")},
        ka=s((b, KEY_WORDS), u32), idxs_mb2=s((b, d), u32),
        cand_idx=s((b,), u32), id_rand=s((b, 3), u32),
        id_key=state.id_key, free_top0=state.free_top,
        recipients0=state.recipients, seq0=state.seq,
        now=s((), u32), now_hi=s((), u32),
    )
    jaxpr = jax.make_jaxpr(
        lambda ctx, vals0, present0: phase_a_batch(ecfg, ctx)(vals0, present0)
    )(ctx, s((b * d, ecfg.mb.value_words), u32), s((b * d,), jnp.bool_)).jaxpr
    return ecfg, jaxpr


@pytest.mark.parametrize("impl", ["dense", "scan"])
def test_round_a_apply_has_no_per_element_gather(impl):
    """Both vphases impls share the ordering body; on a CPU-only PR a
    ``take_along_axis`` over [B,K,cap] or [B,cap] would cost nothing
    here and 12 ns an element on the chip. B*cap slices is the smallest
    of the gathers PR 31 removed; what the callback still gathers by
    index is B*D slices or fewer, or whole rows of V words."""
    ecfg, jaxpr = _trace_round_a_apply(impl)
    assert ecfg.mailbox_cap > ecfg.mb_choices
    bad = _per_element_gathers(
        jaxpr, JAXPR_B * ecfg.mailbox_cap, ENTRY_WORDS
    )
    assert not bad, f"{impl}: per-element gathers in round A: {bad[:6]}"


def test_per_element_gather_audit_positive_control():
    """The audit finds the seven gathers of the parent's formulation:
    two of indices and one of rows for each sorted view, one of rows
    for the shift (the p-th entry's is B slices: under the threshold)
    — and none at all in what replaced them."""
    case = _mailbox_cases(8, 0)
    b, k, cap = case[0].shape[:3]
    jaxpr = jax.make_jaxpr(_ref_mailbox_order)(*case).jaxpr
    bad = _per_element_gathers(jaxpr, b * cap, ENTRY_WORDS)
    shapes = sorted(s for s, _ in bad)
    assert shapes == sorted(
        [(b, cap)] * 2 + [(b, cap, ENTRY_WORDS)]
        + [(b, k, cap)] * 2 + [(b, k, cap, ENTRY_WORDS)] * 2
    ), shapes
    clean = jax.make_jaxpr(_new_mailbox_order)(*case).jaxpr
    assert not _per_element_gathers(clean, 1, 1 << 30)


def test_vphases_impl_knob_validation():
    with pytest.raises(ValueError):
        GrapevineConfig(vphases_impl="bogus")
    # None resolves per backend at engine-config time; tests force CPU
    ecfg = EngineConfig.from_config(GrapevineConfig())
    assert ecfg.vphases_impl == "scan"
    assert (
        EngineConfig.from_config(
            GrapevineConfig(vphases_impl="dense")
        ).vphases_impl
        == "dense"
    )
