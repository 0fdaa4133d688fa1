"""Pallas TPU kernel: ChaCha keystream made and XORed in one pass.

The jnp cipher path (bucket_cipher.row_keystream) hands the ChaCha
state and then the whole keystream through HBM (bucket_cipher.py's
docstring says what the compiler makes of it). This kernel makes the
keystream in VMEM, one lane tile at a time, and XORs each tile into the
row block where it is made: one HBM read + one HBM write per row, no
keystream, state plane, concatenate, reshape or masked copy in HBM.
The slot-index and value arrays are separate kernel refs, so no
concatenated staging copy is made either, and the grid's last step is
a partial block where the row tile does not divide the row count — no
operand is padded.

Layout: the stream order defined once in bucket_cipher.py
(``stream_tiles`` / ``group_words``): lane tile ``q`` of a row's stream
is state word ``q % 16`` of the 128 blocks of group ``q // 16``, the
value plane takes the head of the stream and the slot-index words the
positions after it. So per block group the kernel computes sixteen
``[rows, 128]`` state arrays and stores each straight into its own lane
tile of the value block; the index words come out of the tile after
the value plane's last (lanes 0-3 of tile 48 for the mailbox row, 6,144
words as it is stored since PR 44, and of tile 8 for the 1,024-word
records row; a width off the tile boundary, 6,080 say, puts them on the
lanes after its last value words). Plaintext handed over narrower than
the stream's value plane is taken with zeros after it (``zv``): the
kernel stores those words' keystream itself. The ChaCha core
itself (quarter-round, constants, round schedule, feedforward) is
bucket_cipher's ``chacha_words``, so the implementations cannot drift;
bit-identical ciphertext is asserted by tests/test_pallas_cipher.py,
making engine states interchangeable between impls.

Callers, all through ``path_oram.cipher_rows``: the rounds' fetch and
write-back (rows gathered from the planes, and rows on their way to the
scatter) and, since PR 48, the expiry sweep's two passes over each
chunk of a tree (engine/expiry.py), which name their rows by ``chunk``:
the kernel then reads them from the plane itself and writes them back
into it, the plane aliased onto the output (``cipher_rows_pallas``).

Off-TPU the kernel runs in Pallas interpret mode (CI's CPU backend —
the SGX_MODE=SW analog), so the selection knob is safe everywhere; on a
TPU Mosaic compiles it and the engine resolves to it by default.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .bucket_cipher import LANES, group_words, stream_tiles

U32 = jnp.uint32

#: rows per grid step: one DMA block each way (64 mailbox rows are
#: 1.5 MB; in + out, double-buffered, 6.2 MB of VMEM). On the chip
#: (PERF.md §5, PR 40) 16 / 32 / 64 / 128 rows read 1.75 / 1.60 / 1.54 /
#: 1.54 ms a pass of 20,464 mailbox rows
_ROW_TILE = 64
#: rows the ChaCha state is held for at a time: [16, 128] is two vregs
#: a state word, the sixteen words 32 of the 64. 8 rows read the same
#: on the mailbox row (the DMA is the limit there) and 0.79 against
#: 0.52 ms on the records row, whose one group uses nine words of its
#: sixteen: there the vector unit is the limit
_SUB_ROWS = 16


def _words(ref, rows, cols):
    """Index of ``[rows, cols]`` of a value row block: the plain 2-D
    window, or, where the block holds its rows as ``(tiles, 128)`` (a
    plane's stored rows, ``OramConfig.stored_row_shape``), the same
    words: ``cols`` never crosses a lane tile."""
    if len(ref.shape) == 2:
        return rows, cols
    q = cols.start // LANES
    return rows, q, slice(cols.start - q * LANES, cols.stop - q * LANES)


def _cipher_kernel(
    key_ref, bucket_ref, epoch_ref, idx_ref, val_ref, oidx_ref, oval_ref,
    *, sub, z, zin, zv, rounds,
):
    """One row block: (idx [TR, z], val [TR, zin]) ^ keystream rows ->
    (idx [TR, z], val [TR, zv]), ``sub`` rows at a time. ``zin <= zv``:
    value words the input lacks are zeros, so their ciphertext is the
    keystream itself. Either value block may hold its rows as ``(tiles,
    128)`` instead (:func:`_words`)."""
    key = [key_ref[i] for i in range(8)]
    lane = jax.lax.broadcasted_iota(U32, (sub, LANES), 1)

    def sub_tile(s, carry):
        rows = pl.ds(pl.multiple_of(s * sub, sub), sub)
        n1 = bucket_ref[rows, :]
        n2 = epoch_ref[rows, 0:1]
        n3 = epoch_ref[rows, 1:2]
        # epoch 0 = never written = identity; computed all the same, so
        # the work is content-independent
        written = jnp.broadcast_to((n2 != U32(0)) | (n3 != U32(0)), (sub, LANES))
        for group, word, start, width in stream_tiles(zv + z):
            if word == 0:
                words = group_words(
                    key, lane, n1, n2, n3, group, rounds, rolled=True)
            ks = jnp.where(written, words[word], U32(0))
            end = start + width
            if start < zin:  # this tile's value words
                cols = slice(start, min(end, zin))
                oval_ref[_words(oval_ref, rows, cols)] = (
                    val_ref[_words(val_ref, rows, cols)]
                    ^ ks[:, : cols.stop - start]
                )
            cols = slice(max(start, zin), min(end, zv))
            if cols.start < cols.stop:  # and the pad words after them
                oval_ref[_words(oval_ref, rows, cols)] = (
                    ks[:, cols.start - start: cols.stop - start]
                )
            if end > zv:  # and its slot-index words
                lo = max(start, zv)
                cols = slice(lo - zv, end - zv)
                oidx_ref[rows, cols] = (
                    idx_ref[rows, cols] ^ ks[:, lo - start: width]
                )
        return carry

    jax.lax.fori_loop(0, idx_ref.shape[0] // sub, sub_tile, 0)


@functools.partial(
    jax.jit, static_argnames=("rounds", "interpret", "zv", "tiled_out"))
def cipher_rows_pallas(
    key: jax.Array,  # u32[8]
    bucket: jax.Array,  # u32[R]
    epoch: jax.Array,  # u32[R, 2]; 0 = identity (never written)
    pidx: jax.Array,  # u32[R, z] slot-index words
    pval: jax.Array,  # u32[R, zin] value words, or [R, zin / 128, 128]
    rounds: int = 8,
    interpret: bool = False,
    zv: int | None = None,
    tiled_out: bool = False,
    chunk: jax.Array | None = None,  # scalar: which R rows of a plane
    into: jax.Array | None = None,  # the plane the value rows go into
):
    """Fused ``row ^ keystream``; returns (pidx', pval'), both u32.

    ``zv`` is the width of the value row as the stream lays it out and
    as it is returned (``pval``'s own where None). Rows narrower than
    that are plaintext without its zero pad: the kernel writes the pad
    words' keystream itself, so no padded copy of the rows is made.

    Value rows may come, and with ``tiled_out`` go, as a plane stores
    them: ``[R, tiles, 128]``, each row whole memory tiles
    (``OramConfig.stored_row_shape``). The kernel then reads or writes
    those words where they lie, so neither direction of the round pays a
    pass to bring rows cut from a plane to ``[R, zv]`` or back.

    With ``chunk`` the ``R`` rows are rows ``[chunk * R, (chunk + 1) *
    R)`` of a whole plane, read and written where they lie (the expiry
    sweep's pass over a tree, engine/expiry.py): a Pallas call takes no
    ``dynamic_slice`` as a fused producer and no ``dynamic_update_slice``
    as a fused consumer, so a chunk cut out and pasted back is a copy
    each way. On the way in ``pval`` is the plane itself and the value
    blocks' index map starts at the chunk's first row block (``chunk``
    is scalar-prefetched). On the way out ``into`` is the plane: it is
    aliased onto the value output, whose blocks land on the chunk's
    rows, and comes back in place of the rows; every other row keeps its
    contents through the aliasing and no step reads the plane."""
    r, z = pidx.shape
    tiled_in = pval.ndim == 3
    zin = pval.shape[1] * (LANES if tiled_in else 1)
    zv = zin if zv is None else zv
    sub = _SUB_ROWS
    # the row tile is the second-minor block dim of every operand: a
    # multiple of the u32 sublane count, no larger than the rows need
    tr = min(_ROW_TILE, -(-r // sub) * sub)
    # an index map takes the grid step and, with ``chunk``, the
    # prefetched scalar's ref after it; the row block of a step: in an
    # array of the R rows, and in a whole plane
    here = lambda i, *_: i  # noqa: E731
    row_block = lambda width: pl.BlockSpec(  # noqa: E731
        (tr, width), lambda i, *_: (i, 0))
    if chunk is not None:
        plane = pval if into is None else into
        if r % tr and plane.shape[0] != r:
            raise ValueError(
                f"a chunk of {r} rows is not whole row tiles of {tr}")
        there = lambda i, first: first[0] * (r // tr) + i  # noqa: E731

    def val_block(width, tiled, rows, at):
        """(block, array shape) of value rows ``width`` words wide in
        an array of ``rows`` rows; ``at``: a step's row block there."""
        if not tiled:
            return (pl.BlockSpec((tr, width), lambda *a: (at(*a), 0)),
                    (rows, width))
        tiles = width // LANES
        return (pl.BlockSpec((tr, tiles, LANES), lambda *a: (at(*a), 0, 0)),
                (rows, tiles, LANES))

    in_block, _ = val_block(
        zin, tiled_in, pval.shape[0],
        there if chunk is not None and into is None else here)
    if into is None:
        out_block, out_shape = val_block(zv, tiled_out, r, here)
    else:
        out_block, out_shape = val_block(
            zv, tiled_out, into.shape[0], there)
    kernel = functools.partial(
        _cipher_kernel, sub=sub, z=z, zin=zin, zv=zv, rounds=rounds
    )
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        # rank-1 blocks must tile by 128 on TPU; carry the bucket id
        # as a [rows, 1] column instead so tr only needs 8-alignment
        row_block(1),
        row_block(2),
        row_block(z),
        in_block,
    ]
    out_specs = [row_block(z), out_block]
    out_shapes = [
        jax.ShapeDtypeStruct((r, z), U32),
        jax.ShapeDtypeStruct(out_shape, U32),
    ]
    operands = (key, bucket[:, None], epoch, pidx, pval)
    if chunk is None:
        return pl.pallas_call(
            kernel, grid=(pl.cdiv(r, tr),), in_specs=in_specs,
            out_specs=out_specs, out_shape=out_shapes, interpret=interpret,
        )(*operands)

    def chunk_kernel(first_ref, *refs):
        # the prefetched scalar is the index maps'; the plane handed in
        # for the aliasing (``into``) is never read
        del first_ref
        kernel(*refs[:5], *refs[-2:])

    aliases = {}
    if into is not None:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        operands += (into,)
        # operand indices count the scalar prefetch: ``into`` is 6
        aliases = {6: 1}
    return pl.pallas_call(
        chunk_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(pl.cdiv(r, tr),),
            in_specs=in_specs, out_specs=out_specs,
        ),
        out_shape=out_shapes,
        input_output_aliases=aliases,
        interpret=interpret,
    )(jnp.asarray(chunk, jnp.int32).reshape(1), *operands)
