"""Sharded engine ≡ single-chip engine, bit for bit.

Test pyramid item (5) from SURVEY.md §4: multi-chip = single-chip results
under sharding, on the virtual 8-device CPU mesh (the stand-in for real
hardware, the way the reference CI's SGX_MODE=SW simulator stands in for
SGX, reference .github/workflows/ci.yaml:15-16).

Since ISSUE 18 the delayed-eviction flush composes with the mesh
(parallel/mesh.py make_sharded_flush; OPERATIONS.md §22): fetch-only
rounds accumulate into the REPLICATED eviction buffer and the flush
owner-masks its scatters per chip. Always-on cost: one tiny 2-shard E=2
step/flush pair (trace + compile of the small geometry only); the
E∈{2,4} × shards∈{2,4} campaign breadth — saturation, recursive posmap,
tree-top cache, ReferenceEngine oracle — rides ``-m slow``.
"""

import random

import numpy as np
import jax
import pytest

from grapevine_tpu.config import GrapevineConfig
from grapevine_tpu.engine.batcher import GrapevineEngine, pack_batch
from grapevine_tpu.engine.round_step import engine_flush_step, engine_round_step
from grapevine_tpu.engine.state import EngineConfig, init_engine
from grapevine_tpu.parallel import (
    make_mesh,
    make_sharded_flush,
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
        # real equality cases, ~35 s each on the timesliced CPU mesh —
        # they ride -m slow to keep tier-1 inside its 870 s budget
        # (they only became runnable with the shard_map compat shim —
        # before that the whole set failed at import-time attribute;
        # run `pytest -m slow tests/test_parallel.py` for the sweep).
        pytest.param(0, 2, "jnp", marks=pytest.mark.slow),
        pytest.param(0, 8, "jnp", marks=pytest.mark.slow),
        pytest.param(8, 8, "jnp", marks=pytest.mark.slow),
        pytest.param(8, 4, "jnp", marks=pytest.mark.slow),
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


# -- ISSUE 18: the delayed-eviction flush composes with the mesh --------


def _evict_cfg(shards=1, e=2, **kw):
    base = dict(
        max_messages=64, max_recipients=8, mailbox_cap=4,
        batch_size=4, stash_size=64, bucket_cipher_rounds=8,
    )
    base.update(kw)
    return GrapevineConfig(shards=shards, evict_every=e, **base)


def test_sharded_flush_knob_refusals():
    """Satellite 1's directed-refusal direction: every genuinely
    uncovered knob combination fails with a precise error naming it —
    never a silent fallback, never an opaque shape error later."""
    with pytest.raises(ValueError, match="power-of-two"):
        GrapevineConfig(shards=3)
    with pytest.raises(ValueError, match="commit='op'"):
        GrapevineConfig(shards=2, commit="op")
    with pytest.raises(ValueError, match="JAX device"):
        GrapevineEngine(_evict_cfg(shards=64))
    ecfg = EngineConfig.from_config(_evict_cfg())
    with pytest.raises(ValueError, match="evict_every=1 has no flush"):
        make_sharded_flush(
            EngineConfig.from_config(_evict_cfg(e=1)),
            make_mesh(jax.devices()[:2]),
        )
    # a mesh that does not divide the padded bucket counts (6 devices
    # vs power-of-two trees) names the tree and the geometry
    with pytest.raises(ValueError, match="padded buckets"):
        make_sharded_step(ecfg, make_mesh(jax.devices()[:6]))
    with pytest.raises(ValueError, match="padded buckets"):
        make_sharded_flush(ecfg, make_mesh(jax.devices()[:6]))


def test_sharded_flush_matches_single_chip_fast():
    """The always-on ISSUE-18 identity pair (tier-1 budget: this one
    small 2-shard E=2 compile): fetch-only rounds accumulate into the
    replicated buffer, the owner-masked flush drains the window, and
    responses, transcripts, AND the full final state — trees, nonces,
    buffer planes, window counters — equal the single-chip engine bit
    for bit. Plaintext geometry keeps the four compiles inside the
    budget; breadth (shards×E×cipher×recursive×cache) rides -m slow."""
    assert len(jax.devices()) >= 2, "conftest forces an 8-device CPU mesh"
    ecfg = EngineConfig.from_config(_evict_cfg(bucket_cipher_rounds=0))

    state = init_engine(ecfg, seed=3)
    single = jax.jit(engine_round_step, static_argnums=(0,))
    sflush1 = jax.jit(engine_flush_step, static_argnums=(0,))

    mesh = make_mesh(jax.devices()[:2])
    sstate = shard_engine_state(init_engine(ecfg, seed=3), mesh)
    sstep = make_sharded_step(ecfg, mesh)
    sflush = make_sharded_flush(ecfg, mesh)

    a, b, c = key(1), key(2), key(3)
    batches = [
        [req(C.REQUEST_TYPE_CREATE, a, recipient=b, tag=7),
         req(C.REQUEST_TYPE_CREATE, a, recipient=c, tag=8)],
        [req(C.REQUEST_TYPE_READ, b),
         req(C.REQUEST_TYPE_CREATE, c, recipient=b, tag=9)],
        [req(C.REQUEST_TYPE_DELETE, c),
         req(C.REQUEST_TYPE_READ, b)],
        [req(C.REQUEST_TYPE_READ, b),
         req(C.REQUEST_TYPE_CREATE, b, recipient=a, tag=10)],
    ]
    for i, reqs in enumerate(batches):
        batch = pack_batch(reqs, ecfg.batch_size, NOW + i)
        state, resp1, tr1 = single(ecfg, state, batch)
        sstate, resp2, tr2 = sstep(sstate, batch)
        for k in resp1:
            assert np.array_equal(
                np.asarray(resp1[k]), np.asarray(resp2[k])
            ), f"batch {i}: response field {k} diverged"
        assert np.array_equal(np.asarray(tr1), np.asarray(tr2)), (
            f"batch {i}: transcript diverged"
        )
        if (i + 1) % ecfg.evict_every == 0:
            state = sflush1(ecfg, state)
            sstate = sflush(sstate)

    flat1, _ = jax.tree.flatten(state)
    flat2, _ = jax.tree.flatten(sstate)
    for x, y in zip(flat1, flat2):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    # PR 22: each sharded program compiles ONCE. With its shardings left
    # to inference, jit compiled it again on the second call (a
    # zero-length plane comes back replicated whatever its spec says) —
    # minutes of hidden compile inside the first served round on a mesh.
    assert sstep._cache_size() == 1 and sflush._cache_size() == 1


@pytest.mark.parametrize(
    "e", [pytest.param(1, marks=pytest.mark.slow), 2]  # ~20 s each
)
def test_sharded_sweep_matches_single_chip(e):
    """PR 22: the expiry sweep is shard_map'd like the step (under plain
    jit GSPMD replicates the trees, and a mesh-sized bus then does not
    fit its chips). Each chip sweeps the heap range it owns under the
    GLOBAL bucket ids' keystream; surviving ids and the recipient count
    are summed over the mesh. The whole swept state — re-keyed trees,
    nonces, freelist, counters — equals the single-chip sweep bit for
    bit; E=2 sweeps mid-window, so stale-tagged buckets (the replicated
    tag plane, sliced per chip) are covered too."""
    from grapevine_tpu.engine.expiry import expiry_sweep

    ecfg = EngineConfig.from_config(_evict_cfg(e=e))
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


def _key32(n: int) -> bytes:
    return bytes([n & 0xFF, (n >> 8) & 0xFF, n ^ 0x5A]) + b"\x01" * 29


def _run_sharded_campaign(cfg_kwargs, seed, shards, e, n_batches=6,
                          oracle=False):
    """One randomized campaign: a sharded engine and a single-chip
    engine at the SAME evict_every consume identical mixed batches —
    responses bit-equal every round (mid-window included), full final
    state bit-equal, and optionally the ReferenceEngine oracle's
    responses + live counts every batch (the E=1↔E>1 logical-content
    leg is test_evict.py's; composing both gives sharded E>1 ↔
    oracle)."""
    from test_vphases_scan import _assert_responses_bitequal, _gen_batch

    from grapevine_tpu.testing.reference import ReferenceEngine

    e1 = GrapevineEngine(_evict_cfg(shards=1, e=e, **cfg_kwargs),
                         seed=seed)
    es = GrapevineEngine(_evict_cfg(shards=shards, e=e, **cfg_kwargs),
                         seed=seed)
    ref = (ReferenceEngine(config=_evict_cfg(e=e, **cfg_kwargs),
                           rng=random.Random(seed))
           if oracle else None)
    rng = np.random.default_rng(seed)
    idents = [_key32(i) for i in range(1, 5)]
    live: list[tuple[bytes, bytes]] = []
    bs = es.ecfg.batch_size
    for bi in range(n_batches):
        reqs = _gen_batch(rng, idents, live, int(rng.integers(1, bs + 1)))
        t = NOW + bi
        r1 = e1.handle_queries(reqs, t)
        rs = es.handle_queries(reqs, t)
        _assert_responses_bitequal(
            r1, rs, f"shards={shards} E={e} seed={seed} batch={bi}"
        )
        assert es.health()["stash_overflow"] == 0
        if ref is not None:
            forced = [
                d.record.msg_id
                if r.request_type == C.REQUEST_TYPE_CREATE
                and d.status_code == C.STATUS_CODE_SUCCESS
                else None
                for r, d in zip(reqs, r1)
            ]
            ro = ref.handle_batch(reqs, t, forced)
            _assert_responses_bitequal(
                r1, ro, f"oracle shards={shards} E={e} batch={bi}"
            )
            assert es.message_count() == ref.message_count()
            assert es.recipient_count() == ref.recipient_count()
        for q, d in zip(reqs, r1):
            if (q.request_type == C.REQUEST_TYPE_CREATE
                    and d.status_code == C.STATUS_CODE_SUCCESS):
                live.append((d.record.msg_id, q.record.recipient))
            elif (q.request_type == C.REQUEST_TYPE_DELETE
                    and d.status_code == C.STATUS_CODE_SUCCESS):
                live = [x for x in live if x[0] != d.record.msg_id]
    flat1, _ = jax.tree.flatten(e1.state)
    flat2, _ = jax.tree.flatten(es.state)
    for x, y in zip(flat1, flat2):
        assert np.array_equal(np.asarray(x), np.asarray(y)), (
            f"shards={shards} E={e}: final state diverged"
        )


@pytest.mark.slow
@pytest.mark.parametrize("shards,e", [(2, 2), (2, 4), (4, 2), (4, 4)])
def test_sharded_evict_campaign(shards, e):
    """The acceptance grid: randomized campaigns at E∈{2,4} ×
    shards∈{2,4}, ciphered, vs the single-chip engine AND the
    ReferenceEngine oracle (logical content)."""
    _run_sharded_campaign({}, seed=8100 + 10 * shards + e,
                          shards=shards, e=e, oracle=True)


@pytest.mark.slow
def test_sharded_evict_campaign_recursive_cache():
    """ROADMAP item 1 composition cell: recursive posmap (replicated
    inner trees flushing inside the same owner-masked pass) × tree-top
    cache (replicated planes peeled off the scatter) × the mesh."""
    _run_sharded_campaign(
        dict(posmap_impl="recursive", tree_top_cache_levels=2),
        seed=8200, shards=2, e=4,
    )


@pytest.mark.slow
def test_sharded_evict_campaign_saturated_window():
    """Saturation fallback on the mesh: a near-full tiny bus at E=4
    drives flush_target_slots to its n_buckets_padded clamp — the
    owner partition must hold when every chip's mask covers its whole
    local range."""
    from grapevine_tpu.oram.round import flush_target_slots

    kw = dict(max_messages=16, mailbox_cap=16, batch_size=8,
              stash_size=96)
    ecfg = EngineConfig.from_config(_evict_cfg(e=4, **kw))
    assert flush_target_slots(ecfg.rec) == ecfg.rec.n_buckets_padded
    _run_sharded_campaign(kw, seed=8300, shards=2, e=4, n_batches=9)
