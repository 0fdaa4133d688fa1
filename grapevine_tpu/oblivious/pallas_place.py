"""Pallas TPU kernel: place rows into a wide plane by DMA.

The write-back of a round hands ``_path_scatter`` (oram/path_oram.py)
``R`` finished rows and their ``R`` target rows of a value plane. As
``plane.at[tgt].set(rows)`` on a 2-D ``u32[n, W]`` XLA:TPU, wherever the
rows are an eighth or more of the plane's, sorts the targets, permutes
the rows into a second copy and streams the WHOLE plane through the
core: 1.61 GB read and written to place 503 MB (9.0 + 2.2 ms a pass at
2^21 messages / 2^17 recipients, twice a round; PERF.md section 6,
PRs 44 and 46).

This kernel moves the rows and nothing else. The targets are
scalar-prefetched into SMEM, the rows and the plane stay in HBM
(``pl.ANY``), the plane is aliased onto the output, and row ``i`` goes
``rows[i] -> plane[tgt[i]]`` as one DMA, ``_IN_FLIGHT`` of them under
way at any time, each on its own semaphore. Rows no copy targets keep
their contents through the aliasing and no step reads the plane.

The plane is ``u32[n, tiles, 128]`` (``OramConfig.stored_row_shape``):
a row is whole ``(8, 128)`` memory tiles on an untiled leading axis,
contiguous in HBM, and ``.at[row]`` is one window. A one-row window of
a 2-D ``u32[n, W]`` is what Mosaic refuses ("Slice shape along
dimension 0 must be aligned to tiling (8), but is 1": a row there is
``W/128`` pieces of 512 B at a stride of 4 KB; tests/
test_mosaic_lowering.py holds the refusal), so narrower planes, stored
2-D, keep XLA's scatter.

A target at or past the plane's end is a row that must not be written
(the jnp scatter's ``mode="drop"``): it starts no copy. Which rows those
are is decided by the caller from public values alone (the path, the
chip's index), so the DMA addresses are exactly the addresses the jnp
scatter writes: the same transcript.

The kernel computes nothing, so the plane is bit for bit what the jnp
scatter leaves (tests/test_pallas_place.py; on the CPU it runs in
Pallas interpret mode).
"""

from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: row copies under way at once, one DMA semaphore each
_IN_FLIGHT = 32


def _place_kernel(tgt_ref, rows_ref, plane_in_ref, plane_ref, sems, *, n_rows):
    """Start row ``j``'s copy once the copy that last used its semaphore
    (row ``j - _IN_FLIGHT``) has landed; drain the last ones."""
    del plane_in_ref  # aliased onto plane_ref; never read
    n_plane = plane_ref.shape[0]
    k = min(_IN_FLIGHT, n_rows)

    def row_copy(j, tgt):
        return pltpu.make_async_copy(
            rows_ref.at[j], plane_ref.at[tgt],
            sems.at[jax.lax.rem(j, k)],
        )

    def landed(j):
        @pl.when(tgt_ref[j] < n_plane)
        def _():
            # a wait reads the semaphore and the copy's size alone
            row_copy(j, 0).wait()

    def step(j, carry):
        @pl.when(j >= k)
        def _():
            landed(j - k)

        tgt = tgt_ref[j]

        @pl.when(tgt < n_plane)
        def _():
            row_copy(j, tgt).start()

        return carry

    jax.lax.fori_loop(0, n_rows, step, 0)
    jax.lax.fori_loop(n_rows - k, n_rows, lambda j, c: (landed(j), c)[1], 0)


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def place_rows(
    plane: jax.Array,  # [n, tiles, 128]; written in place
    tgt: jax.Array,  # i32[R] target rows, unique below n; >= n: skip
    rows: jax.Array,  # [R, tiles, 128]
    interpret: bool = False,
):
    """``plane.at[tgt].set(rows, mode="drop", unique_indices=True)``,
    each row placed by one DMA and the plane otherwise untouched."""
    n_rows = rows.shape[0]
    return pl.pallas_call(
        functools.partial(_place_kernel, n_rows=n_rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA((min(_IN_FLIGHT, n_rows),))],
        ),
        out_shape=jax.ShapeDtypeStruct(plane.shape, plane.dtype),
        # operand indices count the scalar prefetch: tgt 0, rows 1, plane 2
        input_output_aliases={2: 0},
        interpret=interpret,
    )(tgt, rows, plane)
