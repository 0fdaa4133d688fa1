"""Oblivious (branchless, constant-shape) building blocks.

Every function here is a pure jnp program whose *instruction trace and
memory addresses are independent of the data values* — the vectorized
analog of the reference's constant-time cmov discipline (upstream
``aligned-cmov``; SURVEY.md §2b). Secret-dependent decisions only ever
appear as mask values flowing through `jnp.where`.

Conventions:
- multi-word values (keys, ids) are uint32 arrays with the word axis last;
- masks are bool arrays;
- "select one row" helpers use one-hot masked sums, never gathers at a
  secret-dependent index (a gather's address would put the secret in the
  access transcript).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

U32 = jnp.uint32
#: sentinel for "empty slot" in index arrays (a numpy scalar, not a device
#: array: importing this package must not initialize a JAX backend)
SENTINEL = np.uint32(0xFFFFFFFF)


def cmov(cond, a, b):
    """Constant-shape conditional move: cond ? a : b (broadcasting where)."""
    return jnp.where(cond, a, b)


def words_equal(a, b):
    """Rowwise equality of multi-word values: a[..., W] == b[..., W] → bool[...]."""
    return jnp.all(a == b, axis=-1)


def is_zero_words(a):
    """True where a multi-word value is all-zero (invalid key / empty id)."""
    return jnp.all(a == 0, axis=-1)


def onehot_select(mask, values):
    """Select the single row of ``values`` where ``mask`` is True.

    mask: bool[N]; values: u32[N, ...] → u32[...]. If the mask has no (or
    several) set lanes the result is the masked sum — callers guarantee
    at-most-one match (an ORAM/table invariant) and handle the none-set
    case via a separate ``found`` flag.
    """
    m = mask.astype(values.dtype)
    m = m.reshape(m.shape + (1,) * (values.ndim - m.ndim))
    return jnp.sum(values * m, axis=0)


def first_true_onehot(mask):
    """One-hot of the first True lane (all-False → all-False). bool[N]→bool[N]."""
    idx = jnp.argmax(mask)  # 0 if none set; guarded below
    onehot = jnp.arange(mask.shape[0]) == idx
    return onehot & jnp.any(mask)


def argmin_u64_onehot(valid, hi, lo):
    """One-hot of the valid lane with the smallest (hi, lo) pair.

    valid: bool[N]; hi, lo: u32[N] (a u64 split into words — jax runs with
    x64 disabled, so the comparison is done lexicographically in u32).
    Invalid lanes rank as +inf; ties break toward the lowest lane index.
    Returns (onehot bool[N], any_valid bool).
    """
    inf = jnp.uint32(0xFFFFFFFF)
    hi_m = jnp.where(valid, hi, inf)
    min_hi = jnp.min(hi_m)
    cand = valid & (hi_m == min_hi)
    lo_m = jnp.where(cand, lo, inf)
    min_lo = jnp.min(lo_m)
    return first_true_onehot(cand & (lo_m == min_lo)), jnp.any(valid)


def rank_of(mask):
    """Exclusive prefix count of True lanes: rank[i] = #True among mask[:i].

    Computed as the shifted inclusive cumsum rather than
    ``cumsum(m) - m``: identical values (exclusive prefix, always
    >= 0), but interval-transparent — a non-relational domain
    (analysis/rangelint.py) cannot see that a prefix sum dominates its
    own last term, so the subtraction form reads as "can go to -1" and
    poisons every downstream u32 cast."""
    m = mask.astype(jnp.int32)
    incl = jnp.cumsum(m)
    return jnp.concatenate([jnp.zeros((1,), jnp.int32), incl[:-1]])


def partition_rank(flags):
    """Positions of a stable two-way partition (False first): i32[B].

    ``pos[i]`` is where element i lands when all False-flagged elements
    precede all True ones, each side in original order: two exclusive
    ranks, no sort and no bin table. The expiry sweep's freelist rebuild
    is exactly this pass (engine/expiry.py).
    """
    digit = jnp.asarray(flags).astype(jnp.int32)
    b = digit.shape[0]
    iota = jnp.arange(b, dtype=jnp.int32)
    # the max/clip below are runtime identities (an exclusive prefix
    # never exceeds its position, a permutation never exceeds B-1)
    # written so a non-relational interval domain (analysis/rangelint.py)
    # can carry the bound instead of widening to 2B — which would escape
    # int32 at the 2^30 certified geometry
    incl = jnp.cumsum(digit)
    ones_before = jnp.concatenate([jnp.zeros((1,), jnp.int32), incl[:-1]])
    zeros_before = jnp.maximum(iota - ones_before, 0)
    n_zeros = jnp.maximum(b - incl[-1], 0)
    # n_zeros + ones_before <= B-1 truly (a stable partition is a
    # permutation) but sums to 2B in interval arithmetic; the add rides
    # RANGE_ALLOWLIST and the clip re-bounds the permutation downstream
    return jnp.clip(
        jnp.where(digit == 1, n_zeros + ones_before, zeros_before),
        0, b - 1,
    )


def u64_add_u32(lo, hi, k):
    """(lo, hi) + k with carry — u64 arithmetic in u32 lanes (x64 off)."""
    s = lo + k
    return s, hi + (s < lo).astype(lo.dtype)


def u64_le(a_lo, a_hi, b_lo, b_hi):
    """a <= b over (lo, hi) u32 lane pairs."""
    return (a_hi < b_hi) | ((a_hi == b_hi) & (a_lo <= b_lo))


def u64_sub(a_lo, a_hi, b_lo, b_hi):
    """a - b (mod 2^64) over u32 lane pairs."""
    lo = a_lo - b_lo
    return lo, a_hi - b_hi - (a_lo < b_lo).astype(a_lo.dtype)


def sort_rows_by_u64(lo, hi, rows, axis):
    """Stable ascending sort of ``rows`` by the 64-bit key (hi, lo).

    lo, hi: u32[..., n, ...] with the sorted axis at ``axis``; rows:
    u32[lo.shape + (W,)]. One ``lax.sort`` over the two key lanes with
    the W row words as payload operands: the network carries the rows
    itself, so no permutation is materialised and nothing is gathered
    (a ``take_along_axis`` by a per-element index runs one element at
    a time on a TPU). Lexicographic in u32 lanes — jax runs with x64
    off. The operands go in as [n, everything else] planes, sorted
    along the leading axis: a TPU then has the batch on its lanes and
    not a short trailing axis (sorted in place over [2048,4,62] the
    compiler put K = 4 on the 128 lanes and round A's callback took
    10.9 ms on a v5e; as [62,8192] planes 5.6, the sort 0.12 of it).
    """
    axis = axis % lo.ndim
    n = lo.shape[axis]

    def plane(x):
        return jnp.moveaxis(x, axis, 0).reshape(n, -1)

    words = tuple(plane(rows[..., w]) for w in range(rows.shape[-1]))
    out = jax.lax.sort(
        (plane(hi), plane(lo)) + words, dimension=0, num_keys=2,
        is_stable=True,
    )
    moved = (n,) + lo.shape[:axis] + lo.shape[axis + 1:]
    return jnp.stack(
        [jnp.moveaxis(o.reshape(moved), 0, axis) for o in out[2:]], axis=-1
    )


def shift_down(x, s, axis):
    """``out[i] = x[i + s]`` along ``axis``, zeros past the end.

    x: [..., n, ...]; s: integers in [0, n], broadcastable against x
    with extent 1 at ``axis`` (a per-row shift). A barrel shifter: one
    static slice-and-pad per bit of ``s``, taken where the bit is set —
    ``n.bit_length()`` elementwise stages and no per-element index.
    """
    axis = axis % x.ndim
    n = x.shape[axis]
    for j in range(n.bit_length()):
        step = 1 << j  # <= n: at n itself the slice is empty, all padding
        pad = [(0, 0)] * x.ndim
        pad[axis] = (0, step)
        moved = jnp.pad(jax.lax.slice_in_dim(x, step, n, axis=axis), pad)
        x = jnp.where(((s >> j) & 1) != 0, moved, x)
    return x
