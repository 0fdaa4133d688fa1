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
from grapevine_tpu.engine.batcher import (
    GrapevineEngine,
    pack_batch,
    unpack_responses,
)
from grapevine_tpu.engine.round_step import engine_round_step
from grapevine_tpu.engine.state import EngineConfig, init_engine
from grapevine_tpu.parallel import (
    make_mesh,
    make_sharded_step,
    make_sharded_sweep,
    shard_engine_state,
)
from grapevine_tpu.testing.reference import ReferenceEngine
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


def perpath_cfg() -> GrapevineConfig:
    """The shape ``host4-sharded-2p23`` brings to the mesh, small: both
    trees keep per-path levels under a dense range that reaches past the
    tree-top cache into the sharded planes, and either tree has more
    buckets than the mesh has chips (a batch of 16 covers 5 of 10 record
    levels, 32 accesses a pass 6 of 8 mailbox levels; the cache holds
    4). ChaCha8 at rest, as served; a cap of 4 so that the script below
    meets it."""
    return GrapevineConfig(
        max_messages=1024, max_recipients=512, mailbox_cap=4,
        batch_size=16, tree_density=2, bucket_cipher_rounds=8,
        bucket_cipher_impl="jnp",
    )


def three_batches(_ecfg, _known):
    a, b, c = key(1), key(2), key(3)
    yield [req(C.REQUEST_TYPE_CREATE, a, recipient=b, tag=7),
           req(C.REQUEST_TYPE_CREATE, a, recipient=c, tag=8),
           req(C.REQUEST_TYPE_CREATE, c, recipient=b, tag=9)]
    yield [req(C.REQUEST_TYPE_READ, b),
           req(C.REQUEST_TYPE_DELETE, c),
           req(C.REQUEST_TYPE_READ, b, msg_id=b"\x99" * 16)]
    yield [req(C.REQUEST_TYPE_DELETE, b),
           req(C.REQUEST_TYPE_READ, b),
           req(C.REQUEST_TYPE_CREATE, b, recipient=a, tag=10)]


def mixed_script(ecfg, known, rounds=8, seed=2023):
    """Seeded rounds of the cells' mix (creates, reads and updates by
    id, zero-id reads and pops, deletes by id) among six identities, two
    of them hot: their mailboxes meet the cap, so creates are refused.
    A by-id op names one of ``known``, the ``(id, sender, recipient)``
    of the creates the engine has answered, which the caller appends to
    between rounds."""
    rng = np.random.default_rng(seed)
    who = [key(i + 1) for i in range(6)]
    hot = [0.4, 0.3, 0.1, 0.1, 0.05, 0.05]
    for r in range(rounds):
        reqs = []
        for j in range(int(rng.integers(12, ecfg.batch_size + 1))):
            u, tag = rng.random(), 16 * r + j
            asker = who[rng.choice(6, p=hot)]
            if u < 0.45 or not known:
                reqs.append(req(C.REQUEST_TYPE_CREATE, who[rng.choice(6)],
                                recipient=asker, tag=tag))
            elif u < 0.6:
                reqs.append(req(C.REQUEST_TYPE_READ, asker))
            elif u < 0.7:
                reqs.append(req(C.REQUEST_TYPE_DELETE, asker))
            else:
                mid, sender, recipient = known[rng.integers(len(known))]
                if u < 0.8:
                    reqs.append(req(C.REQUEST_TYPE_READ, recipient,
                                    msg_id=mid))
                elif u < 0.9:
                    reqs.append(req(C.REQUEST_TYPE_UPDATE, sender,
                                    msg_id=mid, recipient=recipient,
                                    tag=tag))
                else:
                    reqs.append(req(C.REQUEST_TYPE_DELETE, recipient,
                                    msg_id=mid))
        yield reqs


@pytest.mark.parametrize(
    "cipher_rounds,n_dev,impl,script",
    [
        (0, 2, "jnp", three_batches),
        (0, 8, "jnp", three_batches),
        (8, 8, "jnp", three_batches),
        # the cipher and mesh width backlog-4chip runs
        (8, 4, "jnp", three_batches),
        # what backlog-4chip-2p23 adds: per-path levels of both trees
        # gathered, all-reduced and scattered on a chip
        (8, 4, "jnp", mixed_script),
        # the cipher a TPU resolves (PR 40): XLA:CPU does not get
        # through compiling a round of interpreted kernels in ten
        # minutes (PR 49), so the chip's own run of it is
        # chip_smoke.py --four-chips
        pytest.param(8, 8, "pallas", three_batches, marks=pytest.mark.slow),
    ],
)
def test_sharded_step_matches_single_chip(cipher_rounds, n_dev, impl, script):
    """Sharded ≡ single-chip at 2/4/8-way meshes, with the at-rest
    bucket cipher both off and on (the cipher's nonce arrays are sharded
    along the bucket axis like the trees), and the fused Pallas cipher
    kernel running inside shard_map (the pod + pallas combination).
    The mixed script runs at ``perpath_cfg()``, and its answers are held
    to the plain oracle's too."""
    assert len(jax.devices()) >= 8, "conftest forces an 8-device CPU mesh"
    perpath = script is mixed_script
    cfg = perpath_cfg() if perpath else make_cfg(cipher_rounds, impl)
    ecfg = EngineConfig.from_config(cfg)
    if perpath:
        b, bd = ecfg.batch_size, ecfg.batch_size * ecfg.mb_choices
        for oram, n in ((ecfg.rec, b), (ecfg.mb, bd)):
            assert oram.perpath_bucket_rows(n) > 0
            # dense rows of the sharded planes above them
            assert oram.fetched_bucket_rows(n) > oram.perpath_bucket_rows(n)
            assert oram.n_buckets > n_dev
    oracle = ReferenceEngine(cfg)
    statuses = set()

    state = init_engine(ecfg, seed=3)
    single = jax.jit(engine_round_step, static_argnums=(0,))

    mesh = make_mesh(jax.devices()[:n_dev])
    sstate = shard_engine_state(init_engine(ecfg, seed=3), mesh)
    sstep = make_sharded_step(ecfg, mesh)

    known: list[tuple[bytes, bytes, bytes]] = []
    for i, reqs in enumerate(script(ecfg, known)):
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
        if perpath:
            answers = unpack_responses(resp2, len(reqs))
            created = [q.request_type == C.REQUEST_TYPE_CREATE
                       and d.status_code == C.STATUS_CODE_SUCCESS
                       for q, d in zip(reqs, answers)]
            want = oracle.handle_batch(
                reqs, NOW + i, [d.record.msg_id if new else None
                                for d, new in zip(answers, created)])
            for j, (q, d, w) in enumerate(zip(reqs, answers, want)):
                assert d.pack() == w.pack(), (i, j, q.request_type)
                statuses.add((q.request_type, d.status_code))
            known += [(d.record.msg_id, q.auth_identity, q.record.recipient)
                      for q, d, new in zip(reqs, answers, created) if new]
    if perpath:
        assert i + 1 >= 6
        # the cap refused creates, and every kind of op succeeded
        assert (C.REQUEST_TYPE_CREATE,
                C.STATUS_CODE_TOO_MANY_MESSAGES_FOR_RECIPIENT) in statuses
        assert {t for t, st in statuses if st == C.STATUS_CODE_SUCCESS} == {
            C.REQUEST_TYPE_CREATE, C.REQUEST_TYPE_READ,
            C.REQUEST_TYPE_UPDATE, C.REQUEST_TYPE_DELETE}

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


def _psum_operand_bytes(jaxpr) -> int:
    """Bytes handed to ``psum`` by every equation of ``jaxpr`` and of
    the jaxprs nested in it (``shard_map``, ``pjit``, ``cond``
    branches; the round keeps no psum inside a loop)."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "psum":
            total += sum(v.aval.size * v.aval.dtype.itemsize
                         for v in eqn.invars)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            inner = _psum_operand_bytes(sub)
            # a loop's body would run more than once
            assert not (inner and eqn.primitive.name in ("scan", "while"))
            total += inner
    return total


def test_the_mesh_psum_gauge_is_what_the_round_hands_to_psum():
    """``grapevine_mesh_psum_bytes{tree}`` is set once from the
    geometry: rows a pass x stored row bytes (index, value and nonce
    planes) x passes. Held here to that arithmetic at the toy twin of
    ``host4-sharded-2p23``, to the operands of every ``psum`` in the
    traced sharded round (which adds nothing else to reduce), and to 0
    on one device."""
    import json
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "tests", "data", "configs", "host4-sharded-2p23.json")
    with open(path) as f:
        knobs = json.load(f)["grapevine_config"]
    assert knobs["shards"] == 4
    eng = GrapevineEngine(GrapevineConfig(**knobs))
    ecfg, gauge = eng.ecfg, eng.metrics.registry.get(
        "grapevine_mesh_psum_bytes")
    b, bd = ecfg.batch_size, ecfg.batch_size * ecfg.mb_choices
    want = {}
    for tree, oram, n, passes in (("rec", ecfg.rec, b, 1),
                                  ("mb", ecfg.mb, bd, 2)):
        rows = oram.fetched_bucket_rows(n)
        assert oram.perpath_bucket_rows(n) > 0 and oram.n_buckets > 4
        row_bytes = 4 * (oram.bucket_slots + oram.stored_row_words + 2)
        want[tree] = passes * rows * row_bytes
        assert gauge.get(tree=tree) == want[tree] > 0
    assert ecfg.mb.stored_row_words == 6144  # the real mailbox row
    assert want == {"rec": 128 * 4120, "mb": 2 * 144 * 24600}
    traced = jax.make_jaxpr(eng._step_jit)(
        eng.state, pack_batch([], ecfg.batch_size, NOW))
    assert _psum_operand_bytes(traced.jaxpr) == sum(want.values())

    one = GrapevineEngine(GrapevineConfig(**dict(knobs, shards=1)))
    gauge = one.metrics.registry.get("grapevine_mesh_psum_bytes")
    assert gauge.get(tree="rec") == gauge.get(tree="mb") == 0
