"""Fused Pallas cipher kernel ≡ the jnp keystream path (bit-identical).

The kernel runs in interpret mode on the CPU test backend (the
SGX_MODE=SW analog); on real TPU the same code compiles to Mosaic.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from grapevine_tpu.oblivious.bucket_cipher import row_plane_keystreams
from grapevine_tpu.oblivious.pallas_cipher import cipher_rows_pallas

U32 = jnp.uint32


@pytest.mark.parametrize(
    "r,w,rounds",
    [
        (5, 100, 8),     # ragged rows, non-multiple-of-16 words
        (37, 1024, 8),   # records-tree row shape (Z + Z*V = 4 + 4*255)
        (16, 4100, 20),  # mailbox-like wide row, ChaCha20
        (24, 6084, 8),   # the mailbox row, 4 + 6080: three block groups,
        # a 64-lane last value tile, index words on lanes 64-67 of it
        (16, 130, 8),    # index words straddle a tile boundary (126 + 4)
        (16, 6148, 8),   # the mailbox row as it is stored since PR 44,
        # 4 + 6144: the value plane ends on a tile boundary, the index
        # words take lanes 0-3 of tile 48, no partial tile is stored
    ],
)
def test_fused_kernel_matches_jnp_keystream(r, w, rounds):
    key = jax.random.bits(jax.random.PRNGKey(0), (8,), U32)
    data = jax.random.bits(jax.random.PRNGKey(1), (r, w), U32)
    bucket = jax.random.bits(jax.random.PRNGKey(2), (r,), U32)
    epoch = jnp.stack(
        [jax.random.bits(jax.random.PRNGKey(3), (r,), U32) % 5,
         jnp.zeros((r,), U32)],
        axis=1,
    )  # includes epoch-0 (identity) rows
    z = 4  # slot-index words, as in the ORAM bucket rows
    ks_idx, ks_val = row_plane_keystreams(key, bucket, epoch, z, w, rounds)
    want = data ^ jnp.concatenate([ks_idx, ks_val], axis=1)
    gi, gv = cipher_rows_pallas(
        key, bucket, epoch, data[:, :z], data[:, z:], rounds, interpret=True
    )
    got = jnp.concatenate([gi, gv], axis=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # decrypt = same pass
    bi, bv = cipher_rows_pallas(key, bucket, epoch, gi, gv, rounds, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(jnp.concatenate([bi, bv], axis=1)), np.asarray(data)
    )


@pytest.mark.parametrize(
    "zin,zv",
    [
        (6080, 6144),  # the mailbox row: 64 pad words in the last tile
        (1000, 1024),  # a pad that shares its tile with value words
        (896, 1152),   # whole pad tiles: the last value tile is full
    ],
)
def test_kernel_pads_narrow_plaintext_as_the_jnp_path_does(zin, zv):
    """Plaintext rows handed over without their zero pad (the write-back
    does: ``cipher_rows``) come back at the stored width, bit for bit
    what the jnp path makes of the padded rows; the pad words at rest
    are the keystream, and decrypt to zeros."""
    r, z, rounds = 16, 4, 8
    key = jax.random.bits(jax.random.PRNGKey(0), (8,), U32)
    idx = jax.random.bits(jax.random.PRNGKey(1), (r, z), U32)
    val = jax.random.bits(jax.random.PRNGKey(2), (r, zin), U32)
    bucket = jnp.arange(r, dtype=U32) * U32(7)
    epoch = jnp.stack(
        [jnp.arange(r, dtype=U32) % 3, jnp.zeros((r,), U32)], axis=1
    )  # includes epoch-0 (identity) rows
    ks_idx, ks_val = row_plane_keystreams(key, bucket, epoch, z, z + zv, rounds)
    gi, gv = cipher_rows_pallas(
        key, bucket, epoch, idx, val, rounds, interpret=True, zv=zv
    )
    assert gv.shape == (r, zv)
    want = jnp.pad(val, ((0, 0), (0, zv - zin))) ^ ks_val
    np.testing.assert_array_equal(np.asarray(gv), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(idx ^ ks_idx))
    _, back = cipher_rows_pallas(key, bucket, epoch, gi, gv, rounds, interpret=True)
    np.testing.assert_array_equal(np.asarray(back[:, :zin]), np.asarray(val))
    assert not np.asarray(back[:, zin:]).any()


@pytest.mark.slow  # XLA:CPU does not get through compiling one round
# of interpreted kernels at this size in ten minutes (PR 49; 68 s when
# the mark was set, before PR 40's kernel). The kernel's bit-equality
# tests above and the Mosaic compile gate (test_mosaic_lowering.py)
# stay always-on; chip_smoke.py's kernel phase runs this identity on
# the chip, every leaf of the state.
def test_engine_states_bit_identical_across_cipher_impls():
    """A CRUD stream through cipher_impl='pallas' produces the same
    responses AND the same device state as cipher_impl='jnp' — the two
    paths are interchangeable at rest, every leaf, the padded bucket
    included."""
    import dataclasses

    from grapevine_tpu.config import GrapevineConfig
    from grapevine_tpu.engine.batcher import GrapevineEngine
    from grapevine_tpu.wire import constants as C
    from grapevine_tpu.wire.records import QueryRequest, RequestRecord

    base = GrapevineConfig(
        max_messages=64,
        max_recipients=16,
        mailbox_cap=4,
        batch_size=4,
        stash_size=96,
        bucket_cipher_rounds=8,
    )

    def req(rt, auth, recipient=C.ZERO_PUBKEY, tag=0):
        return QueryRequest(
            request_type=rt,
            auth_identity=auth,
            auth_signature=b"\x01" * C.SIGNATURE_SIZE,
            record=RequestRecord(
                msg_id=C.ZERO_MSG_ID,
                recipient=recipient,
                payload=bytes([tag]) * C.PAYLOAD_SIZE,
            ),
        )

    a, b = bytes([1]) * 32, bytes([2]) * 32
    streams = []
    states = []
    for impl in ("jnp", "pallas"):
        cfg = dataclasses.replace(base, bucket_cipher_impl=impl)
        e = GrapevineEngine(cfg, seed=7)
        resps = []
        for t in range(3):
            resps += e.handle_queries(
                [
                    req(C.REQUEST_TYPE_CREATE, a, recipient=b, tag=t),
                    req(C.REQUEST_TYPE_READ, b),
                ],
                1_700_000_000 + t,
            )
        streams.append([(x.status_code, x.record.payload) for x in resps])
        states.append(e.state)
    assert streams[0] == streams[1]
    from grapevine_tpu.testing.compare import states_equal

    same, where = states_equal(*states)
    assert same, f"state differs at {where}"
