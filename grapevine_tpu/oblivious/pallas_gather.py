"""Pallas TPU kernel: fused path-row gather + ChaCha decrypt.

PERF.md "next levers" 2: the unfused round does

    gather rows (HBM read + HBM write of the gathered copy)
    → keystream XOR (read + write again, or the fused cipher kernel)

i.e. the gathered working set crosses HBM at least twice before the
engine sees plaintext. This kernel performs the gather *and* the
decrypt in one pass: each grid step DMAs one tree row into VMEM (the
row index comes from the scalar-prefetched path-bucket vector, the
standard Pallas TPU dynamic-gather pattern), generates that row's
keystream in VMEM, and writes the decrypted row to the output — the
row's ciphertext never lands in HBM a second time and no keystream is
ever materialized.

Scope: the single-chip fetch path (``axis_name is None``). The sharded
path keeps gather → psum → decrypt: buckets are decrypted only *after*
ICI assembly, so tree plaintext never transits the interconnect —
fusing there would trade that property for bandwidth.

Like the fused cipher kernel (pallas_cipher.py) this reuses
bucket_cipher's ChaCha core verbatim and is asserted bit-identical to
the jnp path (tests/test_pallas_gather.py); on the CPU it runs in
Pallas interpret mode. Both kernels move one row per grid step: an
8-row manual-DMA pair was removed in PR 22 because Mosaic refuses a DMA
window narrower than a 128-word tile, and the 4-word index, 2-word
nonce and 1016-word value rows are all such windows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .bucket_cipher import keystream_tile

U32 = jnp.uint32


def _key_words(key_ref):
    """The eight key words of a u32[1, 1, 8] ref, as scalars."""
    return [key_ref[0, 0, i] for i in range(8)]


def _gather_kernel(
    bucket_ref,  # scalar-prefetch: u32[R] row indices (the public path)
    key_ref,  # u32[1, 1, 8]
    idx_row_ref,  # u32[1, 1, z]      tree_idx row bucket_ref[i]
    val_row_ref,  # u32[1, 1, z*v]    tree_val row bucket_ref[i]
    nonce_row_ref,  # u32[1, 1, 2]    epoch nonce of that row
    oidx_ref,  # u32[1, 1, z]
    oval_ref,  # u32[1, 1, z*v]
    *,
    z,
    n_words,
    rounds,
):
    # refs are rank-3 [1, 1, width]: Mosaic requires the last TWO block
    # dims be 8/128-divisible or equal to the array dims, and a gather
    # block is one non-contiguous row — so rows live on a leading
    # (untiled) axis and the trailing (1, width) plane equals the array
    bid = bucket_ref[pl.program_id(0)]
    lo, hi = nonce_row_ref[0, 0, 0], nonce_row_ref[0, 0, 1]
    ks = keystream_tile(_key_words(key_ref), bid, lo, hi, 1, n_words, rounds)
    written = (lo != U32(0)) | (hi != U32(0))
    # stream order (bucket_cipher.py): the value words, then the index
    oval_ref[0, 0, :] = val_row_ref[0, 0, :] ^ jnp.where(
        written, ks[0, : n_words - z], U32(0)
    )
    oidx_ref[0, 0, :] = idx_row_ref[0, 0, :] ^ jnp.where(
        written, ks[0, n_words - z:], U32(0)
    )


@functools.partial(
    jax.jit, static_argnames=("z", "rounds", "interpret")
)
def gather_decrypt_rows(
    key: jax.Array,  # u32[8]
    tree_idx: jax.Array,  # u32[n_padded * z] (flat slot words)
    tree_val: jax.Array,  # u32[n_padded, z*v]
    nonces: jax.Array,  # u32[n_padded, 2]
    flat_b: jax.Array,  # u32[R] heap-bucket indices (public transcript)
    z: int,
    rounds: int = 8,
    interpret: bool = False,
):
    """(pidx u32[R, z], pval u32[R, z*v]) — gathered AND decrypted.

    ``rounds=0`` (plaintext trees) still uses the fused gather so the
    single-chip fetch is one HBM pass either way.
    """
    n_padded = tree_val.shape[0]
    # rows, whatever shape the plane stores them in (a wide row is
    # stored as whole memory tiles: OramConfig.stored_row_shape)
    tree_val = tree_val.reshape(n_padded, -1)
    zv = tree_val.shape[1]
    r = flat_b.shape[0]
    w = z + zv
    idx_rows = tree_idx.reshape(n_padded, z)
    if rounds == 0:
        # no cipher: plain dynamic-slice gather (XLA emits one pass)
        return idx_rows[flat_b], tree_val[flat_b]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(r,),
        in_specs=[
            pl.BlockSpec((1, 1, 8), lambda i, b_ref: (0, 0, 0)),
            pl.BlockSpec(
                (1, 1, z), lambda i, b_ref: (b_ref[i].astype(jnp.int32), 0, 0)
            ),
            pl.BlockSpec(
                (1, 1, zv), lambda i, b_ref: (b_ref[i].astype(jnp.int32), 0, 0)
            ),
            pl.BlockSpec(
                (1, 1, 2), lambda i, b_ref: (b_ref[i].astype(jnp.int32), 0, 0)
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, z), lambda i, b_ref: (i, 0, 0)),
            pl.BlockSpec((1, 1, zv), lambda i, b_ref: (i, 0, 0)),
        ],
    )
    oidx, oval = pl.pallas_call(
        functools.partial(
            _gather_kernel, z=z, n_words=w, rounds=rounds
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((r, 1, z), U32),
            jax.ShapeDtypeStruct((r, 1, zv), U32),
        ],
        interpret=interpret,
    )(flat_b, key[None, None, :], idx_rows[:, None, :], tree_val[:, None, :],
      nonces[:, None, :])
    return oidx[:, 0, :], oval[:, 0, :]


def _scatter_kernel(
    bucket_ref,  # scalar-prefetch: u32[R] write targets (junk-redirected)
    key_ref,  # u32[1, 1, 8]
    idx_new_ref,  # u32[1, 1, z]    plaintext row i to write
    val_new_ref,  # u32[1, 1, z*v]
    epoch_ref,  # u32[1, 1, 2]     write epoch (same for all rows)
    tree_idx_in_ref,  # aliased input (unread; aliasing carries state)
    tree_val_in_ref,  # aliased input (unread)
    nonces_in_ref,  # aliased input (unread)
    otree_idx_ref,  # u32[1, 1, z]   aliased tree_idx row bucket_ref[i]
    otree_val_ref,  # u32[1, 1, zv]  aliased tree_val row bucket_ref[i]
    ononce_ref,  # u32[1, 1, 2]     aliased nonce row bucket_ref[i]
    *,
    z,
    n_words,
    rounds,
):
    # rank-3 refs for the same Mosaic tiling reason as _gather_kernel
    bid = bucket_ref[pl.program_id(0)]
    ks = keystream_tile(
        _key_words(key_ref), bid, epoch_ref[0, 0, 0], epoch_ref[0, 0, 1],
        1, n_words, rounds,
    )
    otree_val_ref[0, 0, :] = val_new_ref[0, 0, :] ^ ks[0, : n_words - z]
    otree_idx_ref[0, 0, :] = idx_new_ref[0, 0, :] ^ ks[0, n_words - z:]
    # the write epoch rides the same pass — the separate XLA nonce
    # scatter the jnp path pays (round.py) has no fused-path cost at all
    ononce_ref[0, 0, :] = epoch_ref[0, 0, :]


@functools.partial(
    jax.jit,
    static_argnames=("z", "rounds", "interpret"),
    donate_argnums=(1, 2, 3),
)
def scatter_encrypt_rows(
    key: jax.Array,  # u32[8]
    tree_idx: jax.Array,  # u32[n_padded * z] (flat; updated in place)
    tree_val: jax.Array,  # u32[n_padded, z*v] (updated in place)
    nonces: jax.Array,  # u32[n_padded, 2] (updated in place)
    flat_b: jax.Array,  # u32[R] heap-bucket targets (public transcript)
    owner: jax.Array,  # bool[R]; False rows must not write
    epoch: jax.Array,  # u32[2] the write epoch for every owned row
    new_pidx: jax.Array,  # u32[R, z] plaintext rows to commit
    new_pval: jax.Array,  # u32[R, z*v]
    z: int,
    rounds: int,
    interpret: bool = False,
):
    """Encrypt + write back owned path rows in ONE HBM pass.

    The write-back mirror of :func:`gather_decrypt_rows`: each grid
    step generates its row's keystream in VMEM and writes the
    ciphertext straight into the (input/output-aliased) tree arrays —
    the encrypted copy never exists as a separate HBM array, and rows
    no grid step targets keep their contents via the aliasing.
    Non-owner rows (duplicate-bucket fetch copies) are redirected to
    the padded junk bucket, which heap indices never address; owner
    targets are unique, so writes never conflict (the junk row takes
    several writes — last wins, never read). The per-row write epoch
    (nonce) is committed in the same pass, so the fused path needs no
    separate XLA nonce scatter.

    Returns the updated ``(tree_idx, tree_val, nonces)``.
    """
    n_padded = tree_val.shape[0]
    stored = tree_val.shape
    tree_val = tree_val.reshape(n_padded, -1)
    zv = tree_val.shape[1]
    r = flat_b.shape[0]
    w = z + zv
    idx_rows = tree_idx.reshape(n_padded, z)
    # non-owners write the junk row (n_padded - 1: heap indices stop at
    # n_buckets = n_padded - 1, see OramConfig.n_buckets_padded)
    junk = U32(n_padded - 1)
    tgt = jnp.where(owner, flat_b, junk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(r,),
        in_specs=[
            pl.BlockSpec((1, 1, 8), lambda i, b_ref: (0, 0, 0)),
            pl.BlockSpec((1, 1, z), lambda i, b_ref: (i, 0, 0)),
            pl.BlockSpec((1, 1, zv), lambda i, b_ref: (i, 0, 0)),
            pl.BlockSpec((1, 1, 2), lambda i, b_ref: (0, 0, 0)),
            # aliased tree inputs: unread by the kernel (constant row-0
            # block so the pipeline loads stay trivial)
            pl.BlockSpec((1, 1, z), lambda i, b_ref: (0, 0, 0)),
            pl.BlockSpec((1, 1, zv), lambda i, b_ref: (0, 0, 0)),
            pl.BlockSpec((1, 1, 2), lambda i, b_ref: (0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, 1, z), lambda i, b_ref: (b_ref[i].astype(jnp.int32), 0, 0)
            ),
            pl.BlockSpec(
                (1, 1, zv), lambda i, b_ref: (b_ref[i].astype(jnp.int32), 0, 0)
            ),
            pl.BlockSpec(
                (1, 1, 2), lambda i, b_ref: (b_ref[i].astype(jnp.int32), 0, 0)
            ),
        ],
    )
    oidx, oval, ononce = pl.pallas_call(
        functools.partial(
            _scatter_kernel, z=z, n_words=w, rounds=rounds
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n_padded, 1, z), U32),
            jax.ShapeDtypeStruct((n_padded, 1, zv), U32),
            jax.ShapeDtypeStruct((n_padded, 1, 2), U32),
        ],
        # operand indices count ALL inputs incl. the scalar prefetch:
        # tgt=0, key=1, new_pidx=2, new_pval=3, epoch=4, idx_rows=5,
        # tree_val=6, nonces=7
        input_output_aliases={5: 0, 6: 1, 7: 2},
        interpret=interpret,
    )(tgt, key[None, None, :], new_pidx[:, None, :], new_pval[:, None, :],
      epoch[None, None, :], idx_rows[:, None, :], tree_val[:, None, :],
      nonces[:, None, :])
    return oidx.reshape(-1), oval.reshape(stored), ononce[:, 0, :]
