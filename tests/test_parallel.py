"""Sharded engine ≡ single-chip engine, bit for bit.

Test pyramid item (5) from SURVEY.md §4: multi-chip = single-chip results
under sharding, on the virtual 8-device CPU mesh (the stand-in for real
hardware, the way the reference CI's SGX_MODE=SW simulator stands in for
SGX, reference .github/workflows/ci.yaml:15-16).
"""

import dataclasses

import numpy as np
import jax
import pytest

from grapevine_tpu.config import GrapevineConfig
from grapevine_tpu.engine.batcher import GrapevineEngine, pack_batch
from grapevine_tpu.engine.round_step import engine_round_step
from grapevine_tpu.engine.state import EngineConfig, init_engine
from grapevine_tpu.parallel import (
    make_mesh,
    make_sharded_step,
    make_sharded_sweep,
    shard_engine_state,
)
from grapevine_tpu.wire import constants as C
from grapevine_tpu.wire.records import QueryRequest, RequestRecord

NOW = 1_700_000_000

def make_cfg(cipher_rounds: int, cipher_impl: str = "jnp") -> GrapevineConfig:
    return GrapevineConfig(
        max_messages=64,
        max_recipients=8,
        mailbox_cap=4,
        batch_size=4,
        stash_size=64,
        bucket_cipher_rounds=cipher_rounds,
        bucket_cipher_impl=cipher_impl,
    )


def key(n: int) -> bytes:
    return bytes([n, n ^ 0x5A]) + b"\x01" * 30


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


@pytest.mark.parametrize(
    "cipher_rounds,n_dev,impl",
    [
        (0, 2, "jnp"),
        (0, 8, "jnp"),
        (8, 8, "jnp"),
        (8, 4, "jnp"),  # the cipher and mesh width backlog-4chip runs
        # the Pallas cipher waits for its own fork's decision
        pytest.param(8, 8, "pallas", marks=pytest.mark.slow),
    ],
)
def test_sharded_step_matches_single_chip(cipher_rounds, n_dev, impl):
    """Sharded ≡ single-chip at 2/4/8-way meshes, with the at-rest
    bucket cipher both off and on (the cipher's nonce arrays are sharded
    along the bucket axis like the trees), and the fused Pallas cipher
    kernel running inside shard_map (the pod + pallas combination)."""
    assert len(jax.devices()) >= 8, "conftest forces an 8-device CPU mesh"
    ecfg = EngineConfig.from_config(make_cfg(cipher_rounds, impl))

    state = init_engine(ecfg, seed=3)
    single = jax.jit(engine_round_step, static_argnums=(0,))

    mesh = make_mesh(jax.devices()[:n_dev])
    sstate = shard_engine_state(init_engine(ecfg, seed=3), mesh)
    sstep = make_sharded_step(ecfg, mesh)

    a, b, c = key(1), key(2), key(3)
    batches = [
        [req(C.REQUEST_TYPE_CREATE, a, recipient=b, tag=7),
         req(C.REQUEST_TYPE_CREATE, a, recipient=c, tag=8),
         req(C.REQUEST_TYPE_CREATE, c, recipient=b, tag=9)],
        [req(C.REQUEST_TYPE_READ, b),
         req(C.REQUEST_TYPE_DELETE, c),
         req(C.REQUEST_TYPE_READ, b, msg_id=b"\x99" * 16)],
        [req(C.REQUEST_TYPE_DELETE, b),
         req(C.REQUEST_TYPE_READ, b),
         req(C.REQUEST_TYPE_CREATE, b, recipient=a, tag=10)],
    ]

    for i, reqs in enumerate(batches):
        batch = pack_batch(reqs, ecfg.batch_size, NOW + i)
        state, resp1, tr1 = single(ecfg, state, batch)
        sstate, resp2, tr2 = sstep(sstate, batch)
        for k in resp1:
            assert np.array_equal(np.asarray(resp1[k]), np.asarray(resp2[k])), (
                f"batch {i}: response field {k} diverged"
            )
        assert np.array_equal(np.asarray(tr1), np.asarray(tr2)), (
            f"batch {i}: transcript diverged"
        )

    # full final state equality, including both bucket trees
    flat1, _ = jax.tree.flatten(state)
    flat2, _ = jax.tree.flatten(sstate)
    for x, y in zip(flat1, flat2):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    # PR 22: the sharded program compiles ONCE. With its shardings left
    # to inference, jit compiled it again on the second call (a
    # zero-length plane comes back replicated whatever its spec says) —
    # minutes of hidden compile inside the first served round on a mesh.
    assert sstep._cache_size() == 1


def test_sharded_knob_refusals():
    """Every genuinely uncovered knob combination fails with a precise
    error naming it — never a silent fallback, never an opaque shape
    error later."""
    with pytest.raises(ValueError, match="power-of-two"):
        GrapevineConfig(shards=3)
    with pytest.raises(ValueError, match="commit='op'"):
        GrapevineConfig(shards=2, commit="op")
    with pytest.raises(ValueError, match="JAX device"):
        GrapevineEngine(dataclasses.replace(make_cfg(8), shards=64))
    # a mesh that does not divide the padded bucket counts (6 devices
    # vs power-of-two trees) names the tree and the geometry
    with pytest.raises(ValueError, match="padded buckets"):
        make_sharded_step(EngineConfig.from_config(make_cfg(8)),
                          make_mesh(jax.devices()[:6]))


def test_sharded_sweep_matches_single_chip():
    """PR 22: the expiry sweep is shard_map'd like the step (under plain
    jit GSPMD replicates the trees, and a mesh-sized bus then does not
    fit its chips). Each chip sweeps the heap range it owns under the
    GLOBAL bucket ids' keystream; surviving ids and the recipient count
    are summed over the mesh. The whole swept state — re-keyed trees,
    nonces, freelist, counters — equals the single-chip sweep bit for
    bit."""
    from grapevine_tpu.engine.expiry import expiry_sweep

    ecfg = EngineConfig.from_config(make_cfg(8))
    state = init_engine(ecfg, seed=3)
    single = jax.jit(engine_round_step, static_argnums=(0,))
    a, b, c = key(1), key(2), key(3)
    batches = [
        [req(C.REQUEST_TYPE_CREATE, a, recipient=b, tag=7),
         req(C.REQUEST_TYPE_CREATE, a, recipient=c, tag=8),
         req(C.REQUEST_TYPE_CREATE, c, recipient=b, tag=9)],
        [req(C.REQUEST_TYPE_CREATE, b, recipient=a, tag=10),
         req(C.REQUEST_TYPE_READ, b)],
    ]
    for i, reqs in enumerate(batches):  # the second batch is 50 s younger
        batch = pack_batch(reqs, ecfg.batch_size, NOW + 50 * i)
        state, _, _ = single(ecfg, state, batch)
    mesh = make_mesh(jax.devices()[:2])
    sstate = shard_engine_state(state, mesh)

    clock = (np.uint32(NOW + 60), np.uint32(30), np.uint32(0))
    swept = jax.jit(expiry_sweep, static_argnums=(0,))(ecfg, state, *clock)
    sswept = make_sharded_sweep(ecfg, mesh)(sstate, *clock)
    assert int(swept.free_top) == ecfg.max_messages - 1  # 3 of 4 expired
    for (path, x), y in zip(
        jax.tree_util.tree_leaves_with_path(swept), jax.tree.leaves(sswept)
    ):
        assert np.array_equal(np.asarray(x), np.asarray(y)), (
            f"swept state diverged at {jax.tree_util.keystr(path)}"
        )
