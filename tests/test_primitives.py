"""Unit tests for the oblivious vector primitives (batched under one jit)."""

import jax
import jax.numpy as jnp
import numpy as np

from grapevine_tpu.oblivious import primitives as P


def test_cmov_and_words_equal():
    out = np.asarray(
        jax.jit(
            lambda: jnp.stack(
                [
                    P.cmov(True, jnp.uint32(1), jnp.uint32(2)),
                    P.cmov(False, jnp.uint32(1), jnp.uint32(2)),
                ]
            )
        )()
    )
    assert out.tolist() == [1, 2]

    a = jnp.array([[1, 2], [3, 4], [0, 0]], jnp.uint32)
    b = jnp.array([[1, 2], [3, 5], [0, 0]], jnp.uint32)
    eq = np.asarray(jax.jit(P.words_equal)(a, b))
    assert eq.tolist() == [True, False, True]
    zero = np.asarray(jax.jit(P.is_zero_words)(a))
    assert zero.tolist() == [False, False, True]


def test_onehot_select_and_first_true():
    vals = jnp.arange(12, dtype=jnp.uint32).reshape(4, 3)
    mask = jnp.array([False, True, False, False])
    sel = np.asarray(jax.jit(P.onehot_select)(mask, vals))
    assert sel.tolist() == [3, 4, 5]

    none = jnp.zeros((4,), jnp.bool_)
    assert np.asarray(jax.jit(P.onehot_select)(none, vals)).tolist() == [0, 0, 0]

    oh = np.asarray(jax.jit(P.first_true_onehot)(jnp.array([False, True, True, False])))
    assert oh.tolist() == [False, True, False, False]
    oh = np.asarray(jax.jit(P.first_true_onehot)(none))
    assert oh.tolist() == [False] * 4


def test_argmin_u64_onehot_edges():
    f = jax.jit(P.argmin_u64_onehot)
    valid = jnp.array([True, True, True, False])
    hi = jnp.array([2, 1, 1, 0], jnp.uint32)
    lo = jnp.array([0, 5, 3, 0], jnp.uint32)
    oh, any_valid = f(valid, hi, lo)
    assert np.asarray(oh).tolist() == [False, False, True, False]  # (1,3) < (1,5) < (2,0)
    assert bool(any_valid)

    # all invalid → no selection
    oh, any_valid = f(jnp.zeros((4,), jnp.bool_), hi, lo)
    assert np.asarray(oh).tolist() == [False] * 4
    assert not bool(any_valid)

    # lanes whose payload equals the masking sentinel still win when valid
    valid = jnp.array([True, False, False, False])
    hi = jnp.full((4,), 0xFFFFFFFF, jnp.uint32)
    lo = jnp.full((4,), 0xFFFFFFFF, jnp.uint32)
    oh, any_valid = f(valid, hi, lo)
    assert np.asarray(oh).tolist() == [True, False, False, False]
    assert bool(any_valid)

    # tie on (hi, lo): first lane wins
    valid = jnp.array([True, True, True, True])
    hi = jnp.array([7, 7, 7, 7], jnp.uint32)
    lo = jnp.array([9, 3, 3, 9], jnp.uint32)
    oh, _ = f(valid, hi, lo)
    assert np.asarray(oh).tolist() == [False, True, False, False]


def test_rank_of():
    mask = jnp.array([True, False, True, True, False, True])
    r = np.asarray(jax.jit(P.rank_of)(mask))
    assert r.tolist() == [0, 1, 1, 2, 3, 3]


# ----------------------------------------------------------------------
# partition_rank: the expiry sweep's stable free-first partition
# ----------------------------------------------------------------------


def _partition_inverse(flags):
    """Where a stable argsort of the 1-bit keys puts each element."""
    flags = np.asarray(flags)
    perm = np.argsort(flags.astype(np.int64), kind="stable")
    pos = np.zeros(flags.shape[0], np.int64)
    pos[perm] = np.arange(flags.shape[0])
    return pos


def test_partition_rank_is_the_freelist_formula():
    """partition_rank == the expiry sweep's stable free-first partition
    (engine/expiry.py) == the inverse of a stable sort of 1-bit keys."""
    rng = np.random.default_rng(5)
    for n in (1, 2, 100, 1023):
        present = rng.random(n) < 0.4
        pos = np.asarray(P.partition_rank(jnp.asarray(present)))
        pi = present.astype(np.int64)
        n_free = n - pi.sum()
        ref = np.where(
            present,
            n_free + (np.cumsum(pi) - pi),
            np.cumsum(1 - pi) - (1 - pi),
        )
        np.testing.assert_array_equal(pos, ref)
        np.testing.assert_array_equal(pos, _partition_inverse(present))


def test_partition_rank_is_stable_on_each_side():
    """Each side keeps its original order: the positions of the False
    flags ascend with the index, and so do those of the True ones."""
    flags = np.array([1, 0, 1, 0, 1, 1, 0, 1, 1, 0], bool)
    pos = np.asarray(P.partition_rank(jnp.asarray(flags)))
    assert pos[~flags].tolist() == [0, 1, 2, 3]
    assert pos[flags].tolist() == [4, 5, 6, 7, 8, 9]
    rng = np.random.default_rng(0)
    heavy = rng.random(512) < 0.9
    np.testing.assert_array_equal(
        np.asarray(P.partition_rank(jnp.asarray(heavy))),
        _partition_inverse(heavy),
    )


def test_partition_rank_all_equal_flags_is_the_identity():
    for b in (1, 2, 97):
        for value in (False, True):
            got = np.asarray(P.partition_rank(jnp.full((b,), value)))
            np.testing.assert_array_equal(got, np.arange(b))


def test_partition_rank_one_element_and_one_flag_edges():
    """One flag of either kind at either end, and tiny batches."""
    rng = np.random.default_rng(1)
    for b in (1, 2, 5, 256):
        for flags in (
            rng.integers(0, 2, b).astype(bool),
            np.arange(b) == 0,
            np.arange(b) == b - 1,
            np.arange(b) != 0,
            np.arange(b) != b - 1,
        ):
            np.testing.assert_array_equal(
                np.asarray(jax.jit(P.partition_rank)(jnp.asarray(flags))),
                _partition_inverse(flags),
            )
