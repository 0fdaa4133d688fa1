"""u64 timestamp/seq lanes (round-3 review weak #7: 2106 rollover + 2^32
creates-per-lifetime were conscious-but-narrow u32 bounds; both are now
two u32 lanes end to end — device layouts, responses, expiry)."""

import numpy as np

from grapevine_tpu.config import GrapevineConfig
from grapevine_tpu.engine.batcher import GrapevineEngine
from grapevine_tpu.oblivious.primitives import (
    sort_rows_by_u64,
    u64_add_u32,
    u64_sub,
)
from grapevine_tpu.wire import constants as C
from grapevine_tpu.wire.records import QueryRequest, RequestRecord

#: a post-2106 clock: 2**32 + a bit (u32 seconds would have wrapped)
FUTURE = (1 << 32) + 12_345


def _mk(commit="phase"):
    cfg = GrapevineConfig(
        bucket_cipher_rounds=0,
        max_messages=128,
        max_recipients=16,
        mailbox_cap=4,
        batch_size=4,
        commit=commit,
        mailbox_choices=1 if commit == "op" else None,
    )
    return GrapevineEngine(cfg, seed=4)


def req(rt, auth, msg_id=C.ZERO_MSG_ID, recipient=C.ZERO_PUBKEY, tag=0):
    return QueryRequest(
        request_type=rt,
        auth_identity=auth,
        record=RequestRecord(
            msg_id=msg_id,
            recipient=recipient,
            payload=bytes([tag]) * C.PAYLOAD_SIZE,
        ),
    )


def _post_2106_round_trip(commits):
    for commit in commits:
        e = _mk(commit)
        a, b = b"\x11" * 32, b"\x22" * 32
        r = e.handle_queries([req(1, a, recipient=b, tag=7)], FUTURE)[0]
        assert r.status_code == C.STATUS_CODE_SUCCESS
        assert r.record.timestamp == FUTURE, commit
        r2 = e.handle_queries([req(2, b)], FUTURE + 5)[0]
        assert r2.status_code == C.STATUS_CODE_SUCCESS
        assert r2.record.timestamp == FUTURE  # stored ts, not the clock
        # UPDATE refreshes to the new post-2106 clock
        r3 = e.handle_queries(
            [req(3, a, msg_id=r.record.msg_id, recipient=b, tag=8)],
            FUTURE + 9,
        )[0]
        assert r3.status_code == C.STATUS_CODE_SUCCESS
        r4 = e.handle_queries([req(2, b)], FUTURE + 10)[0]
        assert r4.record.timestamp == FUTURE + 9, commit


def test_post_2106_timestamps_round_trip():
    """CREATE at a post-2106 clock returns the full u64 timestamp, READ
    echoes it, and the wire codec carries it (timestamp is u64 on the
    wire, reference README.md:135). On the production phase engine;
    the op-major arm is the test below."""
    _post_2106_round_trip(("phase",))


def test_post_2106_timestamps_round_trip_op_commit():
    _post_2106_round_trip(("op",))


def test_expiry_across_the_u32_boundary():
    """Records stamped below 2^32 must expire under a sweep clock above
    it (the exact case a u32 clock breaks: now wraps to a tiny value and
    nothing ever ages)."""
    e = _mk()
    a, b = b"\x11" * 32, b"\x22" * 32
    t0 = (1 << 32) - 50  # pre-boundary stamp
    r = e.handle_queries([req(1, a, recipient=b)], t0)[0]
    assert r.status_code == C.STATUS_CODE_SUCCESS
    # 100 s later the clock has crossed 2^32; period 60 ⇒ expired
    evicted = e.expire(t0 + 100, period=60)
    assert evicted == 1
    r2 = e.handle_queries([req(2, b)], t0 + 101)[0]
    assert r2.status_code == C.STATUS_CODE_NOT_FOUND
    # and a fresh record at the post-boundary clock does NOT expire
    r3 = e.handle_queries([req(1, a, recipient=b)], t0 + 101)[0]
    assert r3.status_code == C.STATUS_CODE_SUCCESS
    assert e.expire(t0 + 102, period=60) == 0


def test_mailbox_order_across_wrapped_seq():
    """Pop-oldest ordering is by the full 64-bit seq: entries created
    after the low lane wraps (seq_hi=1, small seq_lo) must pop AFTER
    pre-wrap entries (seq_hi=0, huge seq_lo) — a 32-bit comparison would
    invert them."""
    e = _mk()
    # force the engine's seq counter near the u32 boundary
    st = e.state
    e.state = st._replace(seq=np.asarray([0xFFFFFFFE, 0], np.uint32))
    a, b = b"\x11" * 32, b"\x22" * 32
    r1 = e.handle_queries([req(1, a, recipient=b, tag=1)], 1000)[0]
    assert r1.status_code == C.STATUS_CODE_SUCCESS
    # seq has advanced past the wrap (hi lane = 1 now)
    assert int(np.asarray(e.state.seq)[1]) == 1
    r2 = e.handle_queries([req(1, a, recipient=b, tag=2)], 1001)[0]
    assert r2.status_code == C.STATUS_CODE_SUCCESS
    pop1 = e.handle_queries([req(4, b)], 1002)[0]  # zero-id delete = pop
    assert pop1.record.payload[0] == 1, "oldest (pre-wrap) must pop first"
    pop2 = e.handle_queries([req(4, b)], 1003)[0]
    assert pop2.record.payload[0] == 2


def test_u64_lane_helpers():
    import jax.numpy as jnp

    lo, hi = u64_add_u32(
        jnp.uint32(0xFFFFFFFF), jnp.uint32(7), jnp.uint32(1)
    )
    assert (int(lo), int(hi)) == (0, 8)
    d_lo, d_hi = u64_sub(
        jnp.uint32(2), jnp.uint32(5), jnp.uint32(0xFFFFFFFF), jnp.uint32(4)
    )
    assert (int(d_lo), int(d_hi)) == (3, 0)
    # lexicographic sort: (hi, lo) pairs
    lo_a = jnp.asarray([5, 1, 9], jnp.uint32)
    hi_a = jnp.asarray([0, 2, 0], jnp.uint32)
    rows = jnp.asarray([[10, 11], [20, 21], [30, 31]], jnp.uint32)
    by_key = sort_rows_by_u64(lo_a, hi_a, rows, axis=0)
    # (0,5) < (0,9) < (2,1)
    assert by_key.tolist() == [[10, 11], [30, 31], [20, 21]]
