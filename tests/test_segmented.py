"""Segmented saturating-scan primitives vs naive sequential models."""

import numpy as np
import jax.numpy as jnp

from grapevine_tpu.oblivious.segmented import (
    group_sort,
    sat_apply,
    sat_compose,
    sat_elem,
    sat_identity,
    segmented_exclusive_sat_scan,
)


def naive_sat(x, steps):
    """Apply (add, lo, hi) steps sequentially to x."""
    for a, lo, hi in steps:
        x = min(max(x + a, lo), hi)
    return x


def test_sat_compose_matches_sequential():
    rng = np.random.default_rng(0)
    for _ in range(200):
        steps = [
            (int(rng.integers(-3, 4)), int(rng.integers(-5, 1)), int(rng.integers(1, 8)))
            for _ in range(rng.integers(1, 6))
        ]
        f = sat_identity()
        for s in steps:
            f = sat_compose(f, sat_elem(*s))
        for x0 in range(-4, 9):
            assert int(sat_apply(f, jnp.int32(x0))) == naive_sat(x0, steps), (
                steps,
                x0,
            )


def test_segmented_exclusive_scan_counts():
    """Mailbox-style walk: +1 clamped at cap, -1 clamped at 0, identity."""
    rng = np.random.default_rng(1)
    b, cap = 64, 3
    group = rng.integers(0, 6, b).astype(np.uint32)
    kind = rng.integers(0, 3, b)  # 0=create, 1=pop, 2=other
    c0 = {g: int(rng.integers(0, cap + 1)) for g in range(6)}

    # naive per-group walk
    want_before = np.zeros(b, np.int32)
    cnt = dict(c0)
    for j in range(b):
        g = int(group[j])
        want_before[j] = cnt[g]
        if kind[j] == 0:
            cnt[g] = min(cnt[g] + 1, cap)
        elif kind[j] == 1:
            cnt[g] = max(cnt[g] - 1, 0)

    add = np.where(kind == 0, 1, np.where(kind == 1, -1, 0)).astype(np.int32)
    lo = np.zeros(b, np.int32)
    hi = np.full(b, cap, np.int32)

    perm, inv, seg_start = group_sort(jnp.asarray(group))
    elems = (
        jnp.asarray(add)[perm],
        jnp.asarray(lo)[perm],
        jnp.asarray(hi)[perm],
    )
    pre = segmented_exclusive_sat_scan(elems, seg_start)
    c0_arr = jnp.asarray([c0[int(g)] for g in np.asarray(group[np.asarray(perm)])], np.int32)
    before_sorted = sat_apply(pre, c0_arr)
    got = np.asarray(before_sorted[inv])
    np.testing.assert_array_equal(got, want_before)


def test_group_sort_vs_naive():
    """The admission walk's grouping against a plain Python model: the
    permutation orders ops by (group, slot), ``inv`` undoes it, and the
    segment starts mark the group boundaries — B = 1 and one group
    included."""
    rng = np.random.default_rng(3)
    for b, n_groups in ((1, 1), (2, 2), (7, 1), (16, 3), (33, 40)):
        group = rng.integers(0, n_groups, b).astype(np.uint32)
        perm, inv, seg = map(np.asarray, group_sort(jnp.asarray(group)))
        want = sorted(range(b), key=lambda i: (group[i], i))
        assert perm.tolist() == want
        assert (np.arange(b)[perm][inv] == np.arange(b)).all()
        assert seg.tolist() == [True] + [
            group[perm[i]] != group[perm[i - 1]] for i in range(1, b)
        ]
