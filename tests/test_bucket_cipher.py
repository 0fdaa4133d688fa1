"""BucketCipher (oblivious/bucket_cipher.py): RFC vectors + properties."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from grapevine_tpu.oblivious.bucket_cipher import (
    chacha_blocks,
    epoch_next,
    row_keystream,
    row_plane_keystreams,
)
from grapevine_tpu.session.chacha import ChaCha20

U32 = jnp.uint32


def _host_block(key_words, counter, bucket, epoch_lo, epoch_hi=0):
    """RFC 7539 block via the host implementation: nonce = LE(bucket,
    epoch_lo, epoch_hi), counter = block index."""
    key = b"".join(int(w).to_bytes(4, "little") for w in key_words)
    nonce = (
        int(bucket).to_bytes(4, "little")
        + int(epoch_lo).to_bytes(4, "little")
        + int(epoch_hi).to_bytes(4, "little")
    )
    return ChaCha20(key, nonce=nonce, counter=counter)._block(counter)


def test_device_chacha20_matches_rfc_host_implementation():
    key = jnp.arange(1, 9, dtype=U32) * U32(0x9E3779B9)
    for bucket, elo, ehi, ctr in [
        (0, 1, 0, 0),
        (12345, 7, 0, 3),
        (0xFFFF, 0xABCD, 5, 63),
    ]:
        dev = chacha_blocks(
            key,
            jnp.full((1,), ctr, U32),
            jnp.full((1,), bucket, U32),
            jnp.full((1,), elo, U32),
            jnp.full((1,), ehi, U32),
            rounds=20,
        )[0]
        host = _host_block(np.asarray(key), ctr, bucket, elo, ehi)
        dev_bytes = b"".join(int(w).to_bytes(4, "little") for w in np.asarray(dev))
        assert dev_bytes == host


def test_row_keystream_roundtrip_and_epoch0_identity():
    key = jax.random.bits(jax.random.PRNGKey(0), (8,), U32)
    rows = jax.random.bits(jax.random.PRNGKey(1), (5, 100), U32)
    buckets = jnp.arange(5, dtype=U32)
    epochs = jnp.stack(
        [jnp.array([0, 1, 1, 2, 9], U32), jnp.zeros((5,), U32)], axis=1
    )
    ks = row_keystream(key, buckets, epochs, 100)
    ct = rows ^ ks
    # epoch 0 = identity (never-written bucket stays its own ciphertext)
    np.testing.assert_array_equal(np.asarray(ct[0]), np.asarray(rows[0]))
    assert (np.asarray(ct[1:]) != np.asarray(rows[1:])).mean() > 0.99
    # decrypt = same keystream
    np.testing.assert_array_equal(np.asarray(ct ^ ks), np.asarray(rows))
    # same bucket, different epoch ⇒ unrelated streams (snapshot diffing)
    ks2 = row_keystream(key, buckets, epochs.at[:, 0].add(U32(1)), 100)
    assert (np.asarray(ks[1]) != np.asarray(ks2[1])).mean() > 0.99
    # the high epoch word matters too (64-bit counter; wrap safety)
    ks3 = row_keystream(key, buckets, epochs.at[:, 1].add(U32(1)), 100)
    assert (np.asarray(ks[1]) != np.asarray(ks3[1])).mean() > 0.99


@pytest.mark.parametrize(
    "n_words",
    [
        4 + 6144,  # the mailbox row as stored (PR 44): three block groups,
        # 48 whole value tiles, the index words on lanes 0-3 of tile 48
        4 + 6080,  # its block words alone: a 64-lane last value tile
        4 + 1024,  # the records row: one group, eight tiles and four lanes
        4 + 380,   # the row the first chip window refused
        4 + 96,    # a row under one lane tile
    ],
)
def test_stream_order_is_the_stated_rule(n_words):
    """The at-rest format (the order of checkpoint version 2, at the
    row widths of version 3), stated against
    ``chacha_blocks`` itself: stream position p is state word
    (p // 128) % 16 of the block whose counter is
    (p // 2048) * 128 + p % 128; a bucket row's value words take the
    head of the stream and its slot-index words follow."""
    z = 4
    zv = n_words - z
    key = jax.random.bits(jax.random.PRNGKey(7), (8,), U32)
    buckets = jnp.array([0, 5, 0xFFFF0001], U32)
    epochs = jnp.array([[3, 0], [0, 0], [9, 2]], U32)  # row 1: never written
    p = np.arange(n_words)
    counter, word = (p // 2048) * 128 + p % 128, (p // 128) % 16
    # every (row, word) has a (block, state word) of its own
    assert len(set(zip(counter.tolist(), word.tolist()))) == n_words
    blocks = np.asarray(chacha_blocks(
        key,
        jnp.broadcast_to(jnp.asarray(counter, U32)[None, :], (3, n_words)),
        buckets[:, None], epochs[:, 0:1], epochs[:, 1:2],
    ))  # [3, n_words, 16]
    want = blocks[:, p, word]
    want[1] = 0  # epoch 0 = the identity
    ks = np.asarray(row_keystream(key, buckets, epochs, n_words))
    np.testing.assert_array_equal(ks, want)
    ks_idx, ks_val = row_plane_keystreams(key, buckets, epochs, z, n_words)
    np.testing.assert_array_equal(np.asarray(ks_val), want[:, :zv])
    np.testing.assert_array_equal(np.asarray(ks_idx), want[:, zv:])
    # lane tile q of the value plane is one whole state word, q % 16,
    # of the 128 blocks [(q // 16) * 128, +128): nothing starts off a
    # 128-lane boundary
    lane = jnp.arange(128, dtype=U32)
    for q in range(zv // 128):
        tile = np.asarray(chacha_blocks(
            key, lane + U32((q // 16) * 128), buckets[2], epochs[2, 0],
            epochs[2, 1],
        ))[:, q % 16]
        np.testing.assert_array_equal(
            np.asarray(ks_val)[2, q * 128:(q + 1) * 128], tile
        )


def test_epoch_next_carries():
    e = epoch_next(jnp.array([0xFFFFFFFF, 4], U32))
    np.testing.assert_array_equal(np.asarray(e), [0, 5])
    e2 = epoch_next(jnp.array([7, 0], U32))
    np.testing.assert_array_equal(np.asarray(e2), [8, 0])


def test_engine_trees_encrypted_at_rest():
    """After traffic, the HBM tree arrays must not contain the payload
    plaintext, and rewriting identical content must change ciphertext
    (fresh epoch per round). The oracle-equality suites prove semantics
    are unchanged; this proves the at-rest property itself."""
    from grapevine_tpu.config import GrapevineConfig
    from grapevine_tpu.engine.batcher import GrapevineEngine
    from grapevine_tpu.wire import constants as C
    from grapevine_tpu.wire.records import QueryRequest, RequestRecord

    cfg = GrapevineConfig(
        max_messages=64, max_recipients=8, mailbox_cap=4, batch_size=2,
        bucket_cipher_rounds=8,
    )
    engine = GrapevineEngine(cfg, seed=4)
    me = b"\x21" * 32
    marker = (b"\xDE\xAD\xBE\xEF" * 234)[: C.PAYLOAD_SIZE]

    def create():
        return engine.handle_queries(
            [
                QueryRequest(
                    request_type=C.REQUEST_TYPE_CREATE,
                    auth_identity=me,
                    auth_signature=b"\x01" * C.SIGNATURE_SIZE,
                    record=RequestRecord(
                        msg_id=C.ZERO_MSG_ID, recipient=me, payload=marker
                    ),
                )
            ],
            1_700_000_000,
        )[0]

    r = create()
    assert r.status_code == C.STATUS_CODE_SUCCESS
    tree_bytes = np.asarray(engine.state.rec.tree_val).tobytes()
    assert marker not in tree_bytes, "payload visible in HBM tree"
    word = int.from_bytes(b"\xDE\xAD\xBE\xEF", "little")
    frac = float((np.asarray(engine.state.rec.tree_val) == word).mean())
    assert frac < 1e-3, "payload words visible in HBM tree"

    # a read rewrites the same record content; the touched rows must not
    # repeat their previous ciphertext (epoch advances)
    # a wide row is stored as (tiles, 128): rows, however stored
    rows_of = lambda plane: np.asarray(plane).reshape(plane.shape[0], -1)  # noqa: E731
    snap1 = rows_of(engine.state.rec.tree_val).copy()
    nz1 = snap1[snap1.any(axis=1)]
    rd = engine.handle_queries(
        [
            QueryRequest(
                request_type=C.REQUEST_TYPE_READ,
                auth_identity=me,
                auth_signature=b"\x01" * C.SIGNATURE_SIZE,
                record=RequestRecord(
                    msg_id=r.record.msg_id,
                    recipient=C.ZERO_PUBKEY,
                    payload=b"\x00" * C.PAYLOAD_SIZE,
                ),
            )
        ],
        1_700_000_001,
    )[0]
    assert rd.status_code == C.STATUS_CODE_SUCCESS
    assert rd.record.payload == marker  # semantics intact through cipher
    snap2 = rows_of(engine.state.rec.tree_val)
    nz2 = snap2[snap2.any(axis=1)]
    assert nz1.shape[0] >= 1 and nz2.shape[0] >= 1
    row_sets_equal = {r.tobytes() for r in nz1} == {r.tobytes() for r in nz2}
    assert not row_sets_equal, "rewritten rows kept identical ciphertext"


def test_expiry_sweep_with_cipher_evicts_and_reencrypts():
    from grapevine_tpu.config import GrapevineConfig
    from grapevine_tpu.engine.batcher import GrapevineEngine
    from grapevine_tpu.wire import constants as C
    from grapevine_tpu.wire.records import QueryRequest, RequestRecord

    cfg = GrapevineConfig(
        max_messages=64, max_recipients=8, mailbox_cap=4, batch_size=2,
        bucket_cipher_rounds=8, expiry_period=10,
    )
    engine = GrapevineEngine(cfg, seed=6)
    me = b"\x33" * 32
    r = engine.handle_queries(
        [
            QueryRequest(
                request_type=C.REQUEST_TYPE_CREATE,
                auth_identity=me,
                auth_signature=b"\x01" * C.SIGNATURE_SIZE,
                record=RequestRecord(
                    msg_id=C.ZERO_MSG_ID,
                    recipient=me,
                    payload=b"\x07" * C.PAYLOAD_SIZE,
                ),
            )
        ],
        1_700_000_000,
    )[0]
    assert r.status_code == C.STATUS_CODE_SUCCESS
    assert engine.message_count() == 1
    evicted = engine.expire(now=1_700_000_100)
    assert evicted == 1 and engine.message_count() == 0
    # the record is gone for clients
    rd = engine.handle_queries(
        [
            QueryRequest(
                request_type=C.REQUEST_TYPE_READ,
                auth_identity=me,
                auth_signature=b"\x01" * C.SIGNATURE_SIZE,
                record=RequestRecord(
                    msg_id=r.record.msg_id,
                    recipient=C.ZERO_PUBKEY,
                    payload=b"\x00" * C.PAYLOAD_SIZE,
                ),
            )
        ],
        1_700_000_101,
    )[0]
    assert rd.status_code == C.STATUS_CODE_NOT_FOUND


_T0 = 1_700_000_000

#: the sweep's clocks: (now - _T0, period) and what they leave of the
#: three waves written at _T0, _T0 + 100 and _T0 + 200
_CLOCKS = {
    "none": (205, 1000),
    "some": (150, 100),  # the first wave is due; the third lies ahead
    "all": (10_000, 10),
}


@functools.lru_cache(maxsize=None)
def _populated(**geometry):
    """(engine config resolved to jnp, the state after three waves of
    CREATEs through a jnp engine, messages alive)."""
    from grapevine_tpu.config import GrapevineConfig
    from grapevine_tpu.engine.batcher import GrapevineEngine
    from grapevine_tpu.wire import constants as C
    from grapevine_tpu.wire.records import QueryRequest, RequestRecord

    kw = dict(max_messages=64, max_recipients=8, mailbox_cap=4, batch_size=4,
              bucket_cipher_rounds=8, bucket_cipher_impl="jnp",
              expiry_period=10)
    cfg = GrapevineConfig(**{**kw, **geometry})
    engine = GrapevineEngine(cfg, seed=11)
    for wave in range(3):
        resps = engine.handle_queries(
            [
                QueryRequest(
                    request_type=C.REQUEST_TYPE_CREATE,
                    auth_identity=bytes([i + 1]) * 32,
                    auth_signature=b"\x01" * C.SIGNATURE_SIZE,
                    record=RequestRecord(
                        msg_id=C.ZERO_MSG_ID,
                        recipient=bytes([(i + wave) % 5 + 1]) * 32,
                        payload=bytes([16 * wave + i]) * C.PAYLOAD_SIZE,
                    ),
                )
                for i in range(cfg.batch_size)
            ],
            _T0 + 100 * wave,
        )
        assert all(r.status_code == C.STATUS_CODE_SUCCESS for r in resps)
    return engine.ecfg, engine.state, engine.message_count()


@pytest.mark.parametrize(
    "geometry,clock,chunk_rows",
    [
        # records rows stored as (8, 128) tiles and a flat 512-word
        # mailbox row, tree-top caches on (the resolved default), 64 and
        # 16 rows a tree: under one row tile of the kernel's 64
        ({}, "some", None),
        ({}, "none", None),
        ({}, "all", None),
        ({"tree_top_cache_levels": 0}, "some", None),
        ({"posmap_impl": "recursive"}, "some", None),
        # the mailbox row with its pad: 6,080 words stored as (48, 128)
        ({"mailbox_cap": 62}, "some", None),
        # two chunks of 32 rows a tree: the kernel's blocks start at the
        # chunk's rows of the plane (not 16 rows: XLA:CPU does not get
        # through compiling the interpreted kernel on one 16-row block
        # of (8, 128) rows written into a plane, in an hour)
        ({"max_recipients": 128}, "some", 32),
    ],
    ids=["tiled-and-flat-rows-some-due", "none-due", "all-due",
         "no-tree-top-cache", "recursive-posmap", "padded-mailbox-row",
         "several-chunks"],
)
def test_swept_state_is_bit_identical_across_cipher_impls(
    monkeypatch, geometry, clock, chunk_rows
):
    """``expiry_sweep`` under ``cipher_impl`` "jnp" and "pallas" (the
    kernel reading and writing each chunk where it lies in the plane;
    interpret mode here) leaves the same state, word for word: both
    trees' planes, nonces and epochs, caches, stashes, the free list
    and the counters."""
    import dataclasses

    from grapevine_tpu.engine import expiry

    ecfg, state, alive = _populated(**geometry)
    if chunk_rows is not None:
        monkeypatch.setattr(expiry, "_chunk_rows", lambda cfg, n: chunk_rows)
        assert ecfg.rec.n_buckets_padded > chunk_rows
        assert ecfg.mb.n_buckets_padded > chunk_rows
    now, period = _CLOCKS[clock]
    swept = {}
    for impl in ("jnp", "pallas"):
        cfg = dataclasses.replace(
            ecfg,
            rec=dataclasses.replace(ecfg.rec, cipher_impl=impl),
            mb=dataclasses.replace(ecfg.mb, cipher_impl=impl),
        )
        # a jit of its own: nothing traced under another impl or
        # another chunking is found again
        swept[impl] = jax.jit(
            lambda st, cfg=cfg: expiry.expiry_sweep(
                cfg, st, np.uint32(_T0 + now), np.uint32(period))
        )(state)
    left = ecfg.max_messages - int(swept["jnp"].free_top)
    assert left == {"none": alive, "some": alive - 4, "all": 0}[clock]
    # every bucket was re-keyed, under either executor
    assert not np.array_equal(np.asarray(swept["jnp"].rec.tree_val),
                              np.asarray(state.rec.tree_val))
    want = jax.tree_util.tree_leaves_with_path(swept["jnp"])
    got = jax.tree.leaves(swept["pallas"])
    assert len(want) == len(got)
    for (path, x), y in zip(want, got):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y),
            err_msg=jax.tree_util.keystr(path))
