"""Fused gather+decrypt kernel (oblivious/pallas_gather.py).

Correctness contract: the fused single-pass fetch is bit-identical to
gather → keystream XOR, at the kernel level and through a full engine
round (interpret mode on CPU — the Mosaic compile is exercised on real
TPU by bench.py's pallas configs)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from grapevine_tpu.config import GrapevineConfig
from grapevine_tpu.engine.batcher import GrapevineEngine
from grapevine_tpu.oblivious.bucket_cipher import row_plane_keystreams
from grapevine_tpu.oblivious.pallas_gather import gather_decrypt_rows
from grapevine_tpu.wire import constants as C
from grapevine_tpu.wire.records import QueryRequest, RequestRecord

NOW = 1_700_000_000


@pytest.mark.parametrize(
    "n,v",
    [
        (64, 6),     # a row under one lane tile
        (8, 1520),   # the mailbox row's block words, 4 + 6080
        (8, 1536),   # the mailbox row as stored, 4 + 6144 (PR 44)
    ],
)
def test_kernel_matches_gather_then_xor(n, v):
    rng = np.random.default_rng(2)
    z = 4
    zv = z * v
    tree_idx = jnp.asarray(rng.integers(0, 2**31, (n * z,)), jnp.uint32)
    tree_val = jnp.asarray(rng.integers(0, 2**31, (n, zv)), jnp.uint32)
    nonces = jnp.asarray(rng.integers(0, 3, (n, 2)), jnp.uint32)  # some 0
    key = jnp.asarray(rng.integers(0, 2**31, (8,)), jnp.uint32)
    flat_b = jnp.asarray(rng.integers(0, n, (17,)), jnp.uint32)
    oi, ov = gather_decrypt_rows(
        key, tree_idx, tree_val, nonces, flat_b, z=z, rounds=8,
        interpret=True,
    )
    pidx = tree_idx.reshape(n, z)[flat_b]
    pval = tree_val[flat_b]
    pn = nonces[flat_b]
    # epoch-0 rows come back as they are: the keystream rows are zero
    ks_idx, ks_val = row_plane_keystreams(key, flat_b, pn, z, z + zv, 8)
    assert np.array_equal(np.asarray(oi), np.asarray(pidx ^ ks_idx))
    assert np.array_equal(np.asarray(ov), np.asarray(pval ^ ks_val))


def test_plaintext_rounds0_is_plain_gather():
    rng = np.random.default_rng(3)
    n, z, zv = 16, 4, 8
    tree_idx = jnp.asarray(rng.integers(0, 2**31, (n * z,)), jnp.uint32)
    tree_val = jnp.asarray(rng.integers(0, 2**31, (n, zv)), jnp.uint32)
    nonces = jnp.zeros((n, 2), jnp.uint32)
    key = jnp.zeros((8,), jnp.uint32)
    flat_b = jnp.asarray([3, 0, 3], jnp.uint32)
    oi, ov = gather_decrypt_rows(
        key, tree_idx, tree_val, nonces, flat_b, z=z, rounds=0,
        interpret=True,
    )
    assert np.array_equal(np.asarray(oi), np.asarray(tree_idx.reshape(n, z)[flat_b]))
    assert np.array_equal(np.asarray(ov), np.asarray(tree_val[flat_b]))


def _run_crd(impl: str, seed: int = 9):
    cfg = GrapevineConfig(
        max_messages=64,
        max_recipients=8,
        mailbox_cap=4,
        batch_size=4,
        stash_size=64,
        bucket_cipher_rounds=8,
        bucket_cipher_impl=impl,
    )
    e = GrapevineEngine(cfg, seed=seed)
    a, b = b"\x11" * 32, b"\x22" * 32
    outs = []
    r = e.handle_queries(
        [QueryRequest(request_type=C.REQUEST_TYPE_CREATE, auth_identity=a,
                      record=RequestRecord(recipient=b,
                                           payload=b"\x05" * C.PAYLOAD_SIZE))],
        NOW,
    )[0]
    outs.append((r.status_code, r.record.msg_id, r.record.payload))
    r2 = e.handle_queries(
        [QueryRequest(request_type=C.REQUEST_TYPE_READ, auth_identity=b,
                      record=RequestRecord(msg_id=C.ZERO_MSG_ID))],
        NOW + 1,
    )[0]
    outs.append((r2.status_code, r2.record.msg_id, r2.record.payload))
    r3 = e.handle_queries(
        [QueryRequest(request_type=C.REQUEST_TYPE_DELETE, auth_identity=b,
                      record=RequestRecord(msg_id=C.ZERO_MSG_ID))],
        NOW + 2,
    )[0]
    outs.append((r3.status_code, r3.record.msg_id, r3.record.payload))
    return outs, e.state


@pytest.mark.slow  # the repo's single fattest test (~66 s interpret-mode
# e2e over three cipher impls); the kernel-level equality tests above
# stay always-on and chip_smoke.py's kernel phase re-proves this
# contract on device — moved off the tier-1 budget in PR 3
def test_engine_round_identical_across_cipher_impls():
    """Full engine C-R-D through the fused fetch ≡ the jnp path: same
    seed ⇒ same ids, payloads, statuses, AND bit-identical state up to
    the junk bucket (found divergent-by-design in round 5; everything
    path-addressable must match exactly)."""
    from grapevine_tpu.testing.compare import states_equal_excluding_junk

    outs_f, state_f = _run_crd("pallas_fused")
    outs_j, state_j = _run_crd("jnp")
    assert outs_f == outs_j
    same, first_diff = states_equal_excluding_junk(state_j, state_f)
    assert same, f"state diverges at {first_diff}"


@pytest.mark.slow  # 8-virtual-device compile ~25 s; sharded equality
# coverage in tier-1 budget lives in tests/test_parallel.py's fast params
def test_sharded_path_ignores_fused_fetch():
    """Under shard_map (axis_name set) the fused fetch must NOT engage —
    the sharded program still compiles and matches single-chip (the
    plaintext-over-ICI guard)."""
    from grapevine_tpu.engine.state import EngineConfig, init_engine
    from grapevine_tpu.engine.batcher import pack_batch
    from grapevine_tpu.parallel import make_mesh, make_sharded_step, shard_engine_state

    cfg = GrapevineConfig(
        max_messages=64, max_recipients=8, mailbox_cap=4, batch_size=4,
        stash_size=64, bucket_cipher_rounds=8,
        bucket_cipher_impl="pallas_fused",
    )
    ecfg = EngineConfig.from_config(cfg)
    mesh = make_mesh(jax.devices()[:4])
    state = shard_engine_state(init_engine(ecfg, seed=1), mesh)
    step = make_sharded_step(ecfg, mesh)
    req = QueryRequest(
        request_type=C.REQUEST_TYPE_CREATE,
        auth_identity=b"\x11" * 32,
        record=RequestRecord(recipient=b"\x22" * 32,
                             payload=b"\x07" * C.PAYLOAD_SIZE),
    )
    batch = pack_batch([req], 4, NOW)
    state, resp, _ = step(state, batch)
    assert int(np.asarray(resp["status"])[0]) == C.STATUS_CODE_SUCCESS


def test_scatter_encrypt_matches_encrypt_then_scatter():
    """The fused write-back ≡ cipher_rows → masked scatter: owners'
    rows land encrypted, non-owner duplicates are dropped, untouched
    rows (and nothing else) keep their exact contents."""
    from grapevine_tpu.oblivious.pallas_gather import scatter_encrypt_rows

    rng = np.random.default_rng(5)
    n, z, v = 32, 4, 6
    zv = z * v
    tree_idx = jnp.asarray(rng.integers(0, 2**31, (n * z,)), jnp.uint32)
    tree_val = jnp.asarray(rng.integers(0, 2**31, (n, zv)), jnp.uint32)
    nonces = jnp.asarray(rng.integers(0, 3, (n, 2)), jnp.uint32)
    key = jnp.asarray(rng.integers(0, 2**31, (8,)), jnp.uint32)
    epoch = jnp.asarray([7, 0], jnp.uint32)
    flat_b = jnp.asarray([3, 9, 3, 20], jnp.uint32)  # 3 duplicated
    owner = jnp.asarray([True, True, False, True])
    new_pidx = jnp.asarray(rng.integers(0, 2**31, (4, z)), jnp.uint32)
    new_pval = jnp.asarray(rng.integers(0, 2**31, (4, zv)), jnp.uint32)
    # snapshot BEFORE the call: the kernel donates the tree buffers
    # (in-place update is the point), so the inputs die with the call
    orig_i = np.asarray(tree_idx).reshape(n, z).copy()
    orig_v = np.asarray(tree_val).copy()
    orig_n = np.asarray(nonces).copy()
    oi, ov, on = scatter_encrypt_rows(
        key, tree_idx, tree_val, nonces, flat_b, owner, epoch, new_pidx,
        new_pval, z=z, rounds=8, interpret=True,
    )
    oi = np.asarray(oi).reshape(n, z)
    ov = np.asarray(ov)
    on = np.asarray(on)
    ks_idx, ks_val = row_plane_keystreams(
        key, flat_b, jnp.broadcast_to(epoch[None, :], (4, 2)), z, z + zv, 8
    )
    ref_i, ref_v = orig_i.copy(), orig_v.copy()
    for j in range(4):
        if bool(owner[j]):
            ref_i[int(flat_b[j])] = np.asarray(new_pidx[j] ^ ks_idx[j])
            ref_v[int(flat_b[j])] = np.asarray(new_pval[j] ^ ks_val[j])
    for row in range(n - 1):  # row n-1 is the junk pad bucket
        if row in (3, 9, 20):
            assert np.array_equal(oi[row], ref_i[row]), f"idx row {row}"
            assert np.array_equal(ov[row], ref_v[row]), f"val row {row}"
            assert np.array_equal(on[row], np.asarray(epoch)), f"nonce {row}"
        else:
            assert np.array_equal(oi[row], orig_i[row]), row
            assert np.array_equal(ov[row], orig_v[row]), row
            assert np.array_equal(on[row], orig_n[row]), f"nonce {row}"
