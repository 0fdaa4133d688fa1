"""Segmented parallel-prefix primitives for vectorized batch semantics.

The batched engine resolves within-round read-after-write chains without
any sequential ``lax.scan`` (measured at ~30-130µs per iteration on TPU —
the dominant cost of the whole framework before this module existed).
Chains are grouped by key, sorted so each group is contiguous, and
resolved with **segmented associative scans** in O(log B) depth.

The workhorse is the *saturating-counter monoid*: functions of the form

    f(x) = min(max(x + a, lo), hi)

which are closed under composition — exactly the algebra of a bounded
counter walk (mailbox occupancy: CREATE = min(x+1, cap), zero-id DELETE
pop = max(x-1, 0), everything else = identity). Composing the per-op
steps with an exclusive segmented scan yields every op's
"count before me" in parallel, clamps included — the trick familiar from
parallel bracket matching.

All shapes are static and data-independent; values flow only through
min/max/add — the same oblivious discipline as the rest of the package.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

I32 = jnp.int32

#: lo/hi sentinels for the identity element (int32-safe, never saturate)
_NEG = jnp.int32(-(1 << 30))
_POS = jnp.int32(1 << 30)


def sat_identity(shape=()):
    """Identity element of the saturating-counter monoid."""
    return (
        jnp.zeros(shape, I32),
        jnp.full(shape, _NEG, I32),
        jnp.full(shape, _POS, I32),
    )


def sat_elem(add, lo, hi):
    """Element f(x) = min(max(x + add, lo), hi); args broadcastable i32."""
    return (
        jnp.asarray(add, I32),
        jnp.asarray(lo, I32),
        jnp.asarray(hi, I32),
    )


def sat_compose(f, g):
    """(g ∘ f): apply f first, then g. Both (add, lo, hi) triples.

    g(f(x)) = min(max(min(max(x+a1, l1), h1) + a2, l2), h2)
            = min(max(x + a1+a2, l'), h')   with
      l' = min(max(l1 + a2, l2), h2)
      h' = min(max(h1 + a2, l2), h2)
    """
    a1, l1, h1 = f
    a2, l2, h2 = g
    return (
        a1 + a2,
        jnp.minimum(jnp.maximum(l1 + a2, l2), h2),
        jnp.minimum(jnp.maximum(h1 + a2, l2), h2),
    )


def sat_apply(f, x):
    """Apply a saturating element to a counter value."""
    a, lo, hi = f
    return jnp.minimum(jnp.maximum(x + a, lo), hi)


def segmented_exclusive_sat_scan(elems, seg_start):
    """Exclusive segmented scan of saturating elements along axis 0.

    elems: (add, lo, hi) each i32[B], in segment-contiguous order.
    seg_start: bool[B], True at the first element of each segment.

    Returns (add, lo, hi) prefix elements: prefix[j] composes
    elems[s..j) where s is j's segment start (identity at segment
    starts). O(log B) depth via ``jax.lax.associative_scan``.
    """

    def combine(x, y):
        xs, xf = x
        ys, yf = y
        f = jax.tree.map(
            lambda keep, merged: jnp.where(ys, keep, merged),
            yf,
            sat_compose(xf, yf),
        )
        return (xs | ys, f)

    flags = seg_start.astype(jnp.bool_)
    _, incl = jax.lax.associative_scan(combine, (flags, elems))
    # exclusive: shift right within segments; segment starts get identity
    ident = sat_identity(seg_start.shape)
    excl = jax.tree.map(
        lambda i, v: jnp.where(
            seg_start, i, jnp.roll(v, 1, axis=0)
        ),
        ident,
        incl,
    )
    return excl


def group_sort(group: jax.Array):
    """Stable permutation ordering ops by (group, slot).

    group: u32[B] group id per op (e.g. the first-occurrence slot of the
    op's key). Returns (perm, inv, seg_start_sorted):
    ``x[perm]`` is segment-contiguous, ``y[inv]`` undoes it, and
    seg_start marks group boundaries in sorted order.
    """
    perm = jnp.argsort(group, stable=True)  # stable ⇒ slot order
    inv = jnp.argsort(perm)
    sorted_g = group[perm]
    seg_start = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), sorted_g[1:] != sorted_g[:-1]]
    )
    return perm, inv, seg_start
