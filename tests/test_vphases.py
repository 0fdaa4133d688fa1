"""The slot-order machinery (engine/vphases.py) against independent models.

1. every group query of ``_DenseGroups`` against a plain Python loop,
   under duplicate-heavy keys, dummies, one group and all-distinct keys:
   the [B,B] formulation every cell runs, checked below the level of the
   whole engine;
2. randomized campaigns of the whole engine against the oracle
   (testing/reference.py) over op mixes heavy in same-key chains,
   zero-id pops, saturation-fallback rounds and single-op batches;
3. order along a mailbox's cap axis (PR 31) against the gather
   formulation it replaced, and the audit that round A's callback holds
   no per-element gather.

The campaign helpers here are shared by tests/test_posmap_ab.py and
tests/test_tree_cache.py. Set $GRAPEVINE_VPHASES_CAMPAIGNS to run more
campaigns than the tier-1 eight.
"""

import os
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from grapevine_tpu.config import GrapevineConfig
from grapevine_tpu.engine.batcher import GrapevineEngine
from grapevine_tpu.engine.state import (
    ENT_SEQ,
    ENT_SEQH,
    ENTRY_WORDS,
    EngineConfig,
    KEY_WORDS,
    init_engine,
)
from grapevine_tpu.engine.vphases import (
    _drop_oldest,
    _index_groups,
    _oldest_first,
    _pth_entry,
    _recipient_groups,
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


def _assert_responses_bitequal(rd, rs, ctx=""):
    for j, (d, s) in enumerate(zip(rd, rs)):
        assert d.status_code == s.status_code, f"{ctx} slot {j}: status"
        assert d.record.msg_id == s.record.msg_id, f"{ctx} slot {j}: id"
        assert d.record.sender == s.record.sender, f"{ctx} slot {j}: sender"
        assert d.record.recipient == s.record.recipient, f"{ctx} slot {j}"
        assert d.record.timestamp == s.record.timestamp, f"{ctx} slot {j}: ts"
        assert d.record.payload == s.record.payload, f"{ctx} slot {j}: payload"


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


def _assert_matches_oracle(oracle, reqs, resps, t, ctx=""):
    """One batch's responses against the oracle's, the engine's msg ids
    forced into it (they are engine-private PRP outputs)."""
    forced = [
        d.record.msg_id
        if r.request_type == C.REQUEST_TYPE_CREATE
        and d.status_code == C.STATUS_CODE_SUCCESS
        else None
        for r, d in zip(reqs, resps)
    ]
    want = oracle.handle_batch(reqs, t, forced)
    for j, (d, o) in enumerate(zip(resps, want)):
        assert d.status_code == o.status_code, (
            f"{ctx} slot {j}: engine {d.status_code} != oracle "
            f"{o.status_code}"
        )
    _assert_responses_bitequal(resps, want, ctx)


def _run_campaign(cfg_kwargs, seed, n_batches=3, batch_fill=None):
    """One campaign: a fresh engine and oracle, mixed batches; every
    response (status, id, sender, recipient, timestamp, payload) and the
    message and recipient counts after every batch equal the oracle's."""
    rng = np.random.default_rng(seed)
    engine = GrapevineEngine(
        GrapevineConfig(**cfg_kwargs), seed=int(rng.integers(1 << 30))
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
        resps = engine.handle_queries(reqs, t)
        _assert_matches_oracle(
            oracle, reqs, resps, t, f"seed {seed} batch {bi}")
        assert engine.message_count() == oracle.message_count()
        assert engine.recipient_count() == oracle.recipient_count()
        for r, d in zip(reqs, resps):
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


def test_randomized_campaigns_match_the_oracle():
    """Budget-shaped fast set: the cost is ~all jit compiles (one per
    distinct geometry), so the fast plan spans two geometries —
    steady-state and bus-saturation. Both saturation regimes resolve
    through the same _admission_slow scan (only the tripping guard
    differs), so bus-saturation keeps the fallback branch covered."""
    for i, (cfg, fill) in enumerate(_campaign_plan(_FAST_N)):
        if cfg is SAT_RECIP:
            cfg = SAT_BUS
        _run_campaign(cfg, seed=1000 + i, batch_fill=fill)


def test_saturation_fallback_engaged_and_matches_the_oracle():
    """Drive the bus to saturation so fast_ok is False (free_top < B):
    rounds resolve through _admission_slow and must match the oracle,
    TOO_MANY_MESSAGES admission order included."""
    engine = GrapevineEngine(GrapevineConfig(**SAT_BUS), seed=9)
    oracle = ReferenceEngine(
        config=GrapevineConfig(**SAT_BUS), rng=random.Random(9)
    )
    a, x = key(1), key(2)
    # 3 full batches of creates against max_messages=16: round 2 onward
    # runs with free_top < B=8 → the lax.scan branch
    for bi in range(3):
        reqs = [
            req(C.REQUEST_TYPE_CREATE, a, recipient=x, tag=bi * 8 + j)
            for j in range(8)
        ]
        resps = engine.handle_queries(reqs, NOW + bi)
        _assert_matches_oracle(oracle, reqs, resps, NOW + bi, f"sat {bi}")
    assert engine.message_count() == oracle.message_count() <= 16
    codes = {r.status_code for r in resps}
    assert C.STATUS_CODE_TOO_MANY_MESSAGES in codes  # quota actually hit


# ----------------------------------------------------------------------
# the group queries of _DenseGroups, each against a plain Python loop
# ----------------------------------------------------------------------

GROUPS_B = 12


def _group_pattern(name):
    """(idx u32[B], is_real bool[B]): duplicate-heavy keys, keys with
    dummies among them (a dummy shares its index value with other
    dummies and must still be a group of its own), one group, and
    all-distinct keys."""
    rng = np.random.default_rng(11)
    b = GROUPS_B
    real = np.ones(b, bool)
    with_dummies = rng.random(b) < 0.6
    with_dummies[:2] = [True, False]
    return {
        "duplicate-heavy": (rng.integers(0, 3, b), real),
        "dummies": (np.where(with_dummies, rng.integers(0, 4, b), 99),
                    with_dummies),
        "one-group": (np.full(b, 7), real),
        "all-distinct": (rng.permutation(b), real),
    }[name]


def _loop_groups(idx, is_real):
    """members[i]: the slots of op i's group, ascending — ops on the
    same index if op i is real, the op alone if it is a dummy."""
    b = len(idx)
    return [
        [j for j in range(b)
         if j == i or (is_real[i] and is_real[j] and idx[j] == idx[i])]
        for i in range(b)
    ]


def _loop_rank(members, flags):
    """Per op: the flagged strictly-earlier ops of its group."""
    return [sum(1 for j in m if j < i and flags[j])
            for i, m in enumerate(members)]


def _flag_sets(is_real):
    """Flag vectors as the phases raise them (never on a dummy) and, the
    last two, raised on dummies too: all set and none set included."""
    rng = np.random.default_rng(12)
    b = len(is_real)
    masked = [(rng.random(b) < p) & is_real for p in (0.2, 0.5, 0.8)]
    return masked + [is_real.copy(), np.zeros(b, bool),
                     rng.random(b) < 0.5, np.ones(b, bool)]


def _both_constructions(idx, is_real):
    """The two ways the phases build a group object: over one index
    column, and over a recipient key's eight words."""
    idx_j = jnp.asarray(idx, jnp.uint32)
    real_j = jnp.asarray(is_real)
    ka = jnp.stack(
        [idx_j * jnp.uint32(w + 1) + jnp.uint32(w) for w in range(KEY_WORDS)],
        axis=1,
    )
    return _index_groups(idx_j, real_j), _recipient_groups(ka, real_j)


def _want_counts_before(members, flags, **_):
    return _loop_rank(members, flags)


def _want_any_before(members, flags, **_):
    return [r > 0 for r in _loop_rank(members, flags)]


def _want_total_sum(members, flags, **_):
    return [sum(int(flags[j]) for j in m) for m in members]


def _want_total_or(members, flags, **_):
    return [any(flags[j] for j in m) for m in members]


def _want_total_sum_rows(members, u, **_):
    return [u[m].sum(axis=0).tolist() for m in members]


def _want_total_or_rows(members, u, **_):
    return [u[m].any(axis=0).tolist() for m in members]


def _want_group_first(members, **_):
    return [m[0] for m in members]


def _want_group_last(members, **_):
    return [m[-1] for m in members]


def _want_first_flag_index(members, flags, **_):
    hits = [[j for j in m if flags[j]] for m in members]
    return [h[0] if h else 0 for h in hits], [bool(h) for h in hits]


def _want_last_flag_index_upto(members, flags, **_):
    return [max([j for j in m if flags[j] and j <= i], default=-1)
            for i, m in enumerate(members)]


def _want_last_flag_index(members, flags, **_):
    return [max([j for j in m if flags[j]], default=-1) for m in members]


def _want_select_by_rank(members, flags, vals, q, **_):
    rank = _loop_rank(members, flags)
    out = []
    for i, m in enumerate(members):
        hit = [j for j in m if flags[j] and rank[j] == q[i]]
        assert len(hit) <= 1  # ranks within a group are distinct
        out.append(vals[hit[0]].tolist() if hit else [0] * vals.shape[1])
    return out


#: method -> (the loop model, which of flags / u / (vals, q) it takes)
_GROUP_QUERIES = {
    "counts_before": (_want_counts_before, "flags"),
    "any_before": (_want_any_before, "flags"),
    "total_sum": (_want_total_sum, "flags"),
    "total_or": (_want_total_or, "flags"),
    "total_sum_rows": (_want_total_sum_rows, "u"),
    "total_or_rows": (_want_total_or_rows, "u"),
    "group_first": (_want_group_first, None),
    "group_last": (_want_group_last, None),
    "first_flag_index": (_want_first_flag_index, "flags"),
    "last_flag_index_upto": (_want_last_flag_index_upto, "flags"),
    "last_flag_index": (_want_last_flag_index, "flags"),
    "select_by_rank": (_want_select_by_rank, "rank"),
}


def _as_lists(got):
    if isinstance(got, tuple):
        return tuple(np.asarray(g).tolist() for g in got)
    return np.asarray(got).tolist()


@pytest.mark.parametrize(
    "pattern", ["duplicate-heavy", "dummies", "one-group", "all-distinct"])
@pytest.mark.parametrize("method", list(_GROUP_QUERIES))
def test_dense_group_query_equals_a_python_loop(method, pattern):
    want_fn, takes = _GROUP_QUERIES[method]
    rng = np.random.default_rng(13)
    idx, is_real = _group_pattern(pattern)
    members = _loop_groups(idx, is_real)
    for groups in _both_constructions(idx, is_real):
        for k, flags in enumerate(_flag_sets(is_real)):
            if takes is None:
                got = getattr(groups, method)()
                want = want_fn(members)
            elif takes == "flags":
                got = getattr(groups, method)(jnp.asarray(flags))
                want = want_fn(members, flags=flags)
            elif takes == "u":
                u = (rng.random((GROUPS_B, 5)) < 0.4) & flags[:, None]
                got = getattr(groups, method)(jnp.asarray(u))
                want = want_fn(members, u=u)
            else:
                vals = rng.integers(
                    1, 1 << 32, (GROUPS_B, 3), dtype=np.uint64
                ).astype(np.uint32)
                q = rng.integers(-1, 4, GROUPS_B).astype(np.int32)
                got = groups.select_by_rank(
                    jnp.asarray(flags), jnp.asarray(vals), jnp.asarray(q))
                want = want_fn(members, flags=flags, vals=vals, q=q)
            got = _as_lists(got)
            want = tuple(want) if isinstance(got, tuple) else want
            assert got == want, f"flags {k}"
            if takes is None:
                break  # no flags to vary


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


JAXPR_B = 256


def _trace_round_a_apply():
    """The jaxpr of round A's callback alone: ``phase_a_batch``'s
    precomputation and its ``apply_batch`` over [B*D] fetched rows."""
    ecfg = EngineConfig.from_config(
        GrapevineConfig(**{**BASE, "batch_size": JAXPR_B, "mailbox_cap": 8})
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


def test_round_a_apply_has_no_per_element_gather():
    """On a CPU-only PR a ``take_along_axis`` over [B,K,cap] or [B,cap]
    would cost nothing here and 12 ns an element on the chip. B*cap
    slices is the smallest of the gathers PR 31 removed; what the
    callback still gathers by index is B*D slices or fewer, or whole
    rows of V words."""
    ecfg, jaxpr = _trace_round_a_apply()
    assert ecfg.mailbox_cap > ecfg.mb_choices
    bad = _per_element_gathers(
        jaxpr, JAXPR_B * ecfg.mailbox_cap, ENTRY_WORDS
    )
    assert not bad, f"per-element gathers in round A: {bad[:6]}"


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
