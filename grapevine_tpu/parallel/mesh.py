"""Bucket-tree sharding over a JAX device mesh.

Design (the TPU re-platforming of "one enclave's EPC holds everything",
SURVEY.md §1, §2c):

- The two Path-ORAM bucket trees (records + mailbox, the only state that
  scales with bus capacity) are sharded along the bucket axis: each chip
  owns a contiguous heap-index range of ``n_buckets_padded / n_chips``
  buckets in its local HBM.
- Per access, every chip gathers the path buckets it owns and one
  ``psum`` over ICI assembles the full root→leaf working set on all chips
  (oram/path_oram.py:_path_gather) — BASELINE config 5's "stash
  all-gather over ICI" in reduce form. Write-back is purely local: each
  heap index has exactly one owner.
- Stash, position map, freelist, and all scalar bookkeeping are
  replicated; every chip executes the identical branchless program, so
  the replicated state stays bit-identical without extra collectives.
  (The position map at 2^24 entries is 64 MiB — cheap to replicate; the
  trees are the GBs.)

Communication cost per access: one psum of ``path_len * Z`` slots
(index + leaf + value words) — for the records tree at 2^24 that is
25 * 4 * 1 KiB ≈ 100 KiB over ICI per op, overlapped across the batch by
XLA's scheduler. There is no NCCL/MPI analog anywhere: chip↔chip is XLA
collectives over ICI, host↔device is one dispatch per batch round
(SURVEY.md §5 "Distributed communication backend").
"""

from __future__ import annotations

import functools

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..engine.expiry import expiry_sweep
from ..engine.round_step import engine_round_step
from ..engine.state import EngineConfig, EngineState
from ..oram.path_oram import OramState

#: mesh axis across which the bucket trees are sharded
TREE_AXIS = "tree"

def make_mesh(devices=None) -> Mesh:
    """1-D mesh over the given (default: all) devices."""
    if devices is None:
        devices = jax.devices()
    return Mesh(devices, (TREE_AXIS,))


def _oram_specs() -> OramState:
    return OramState(
        tree_idx=P(TREE_AXIS),
        tree_val=P(TREE_AXIS),
        # tree-top cache planes: replicated private state (stash
        # standing) — every chip reads and writes the identical values,
        # so cache accesses need no collective (2^k−1 buckets is KBs,
        # not the GBs the sharded trees are)
        cache_idx=P(),
        cache_val=P(),
        cache_leaf=P(),
        # leaf-metadata plane (recursive posmap): sharded like tree_idx;
        # zero-length under a flat map (every shard is empty — valid)
        tree_leaf=P(TREE_AXIS),
        stash_idx=P(),
        stash_val=P(),
        stash_leaf=P(),
        # flat: one replicated array. Recursive: a RecursivePosMapState
        # pytree — the P() prefix replicates the whole internal ORAM
        # (its own bucket tree included; sharding the *inner* tree along
        # the bucket axis is the ROADMAP item 1/3 composition point)
        posmap=P(),
        overflow=P(),
        nonces=P(TREE_AXIS),
        cipher_key=P(),
        epoch=P(),
    )


def engine_state_specs() -> EngineState:
    """PartitionSpec pytree matching EngineState: trees sharded, rest replicated."""
    return EngineState(
        rec=_oram_specs(),
        mb=_oram_specs(),
        freelist=P(),
        free_top=P(),
        recipients=P(),
        seq=P(),
        hash_key=P(),
        id_key=P(),
        rng=P(),
    )


def shard_engine_state(state: EngineState, mesh: Mesh) -> EngineState:
    """Place an engine state onto the mesh per ``engine_state_specs``."""
    specs = engine_state_specs()
    return jax.tree.map(
        lambda s, x: jax.device_put(x, NamedSharding(mesh, s)),
        specs,
        state,
        is_leaf=lambda s: isinstance(s, P),
    )


def engine_state_shardings(mesh: Mesh) -> EngineState:
    """``engine_state_specs`` as NamedShardings on ``mesh`` (a pytree
    prefix of EngineState, as ``jit``'s in/out_shardings accept)."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), engine_state_specs(),
        is_leaf=lambda s: isinstance(s, P),
    )


def _jit_sharded(fn, mesh: Mesh, n_replicated_in: int, n_replicated_out: int):
    """``jit`` a shard_map'd state program with its shardings pinned.

    Left to infer them from its arguments, jit compiles the program
    TWICE: JAX reports a zero-length plane (``tree_leaf`` under a flat
    position map) as replicated on the way out whatever its spec says,
    so the second call's state no longer matches the first call's
    cache key. Found on four v5e chips in PR 22, where the second
    compile of the 2^22 round hid inside the first served round."""
    state, rep = engine_state_shardings(mesh), NamedSharding(mesh, P())
    outs = (state,) + (rep,) * n_replicated_out
    return jax.jit(
        fn, donate_argnums=0,
        in_shardings=(state,) + (rep,) * n_replicated_in,
        out_shardings=outs if n_replicated_out else state,
    )


def init_sharded_engine(ecfg: EngineConfig, mesh: Mesh, seed: int = 0) -> EngineState:
    """Initialize engine state *directly* sharded over the mesh.

    ``init_engine`` + ``shard_engine_state`` stages the full state on one
    device before copying shard-wise — impossible at pod scale (a 2^24
    bus is a 32 GB records tree; one v5e chip holds 16 GB) and a 2×
    host-memory spike in simulation. Jitting the initializer with
    ``out_shardings`` lets XLA materialize each shard on its owner
    device only, so peak memory is the sharded footprint itself."""
    from ..engine.state import init_engine

    return jax.jit(
        lambda: init_engine(ecfg, seed),
        out_shardings=engine_state_shardings(mesh),
    )()


def validate_sharded_geometry(ecfg: EngineConfig, mesh: Mesh) -> None:
    """Directed refusal for knob combinations the sharded programs do
    not cover: raise a precise error naming the combination, or return.

    Everything the sharded step DOES cover is silent here: recursive
    position maps (inner trees replicated), tree-top caching (cache planes
    replicated), both cipher impls (gather, psum, then the cipher: tree
    plaintext never transits ICI).
    """
    n_dev = mesh.devices.size
    for label, cfg in (("records", ecfg.rec), ("mailbox", ecfg.mb)):
        if cfg.n_buckets_padded % n_dev:
            raise ValueError(
                f"sharded path: {n_dev} mesh devices do not divide the "
                f"{label} tree's {cfg.n_buckets_padded} padded buckets "
                "— the bucket axis shards as contiguous equal heap "
                "ranges; use a power-of-two mesh no larger than the "
                "smaller tree"
            )


def make_sharded_step(ecfg: EngineConfig, mesh: Mesh):
    """Jit-compiled engine step with the bucket trees sharded over ``mesh``.

    The returned function has the same signature and semantics as
    ``engine_round_step(ecfg, state, batch)`` — the phase-major batched
    engine, i.e. the same commit schedule the single-chip production path
    uses (bit-identical results — tested in tests/test_parallel.py, the
    analog of the reference's SGX_MODE=SW simulation testing, reference
    .github/workflows/ci.yaml:15-16).
    """
    validate_sharded_geometry(ecfg, mesh)
    specs = engine_state_specs()
    step = jax.shard_map(
        functools.partial(engine_round_step, ecfg, axis_name=TREE_AXIS),
        mesh=mesh,
        in_specs=(specs, P()),
        out_specs=(specs, P(), P()),
        check_vma=False,
    )
    return _jit_sharded(step, mesh, 1, 2)


def make_sharded_sweep(ecfg: EngineConfig, mesh: Mesh):
    """Jit-compiled expiry sweep with the bucket trees sharded.

    Same semantics as ``expiry_sweep(ecfg, state, now, period, now_hi)``
    and bit-identical results (tests/test_parallel.py): each chip sweeps
    the heap range it owns, and which message ids survive and how many
    recipients remain are summed over the mesh (engine/expiry.py). The
    sweep must be shard_map'd like the step: under plain ``jit`` GSPMD
    replicates the trees rather than partition the chunk scan, which a
    mesh-sized bus does not survive."""
    validate_sharded_geometry(ecfg, mesh)
    specs = engine_state_specs()
    sweep = jax.shard_map(
        functools.partial(expiry_sweep, ecfg, axis_name=TREE_AXIS),
        mesh=mesh,
        in_specs=(specs, P(), P(), P()),
        out_specs=specs,
        check_vma=False,
    )
    return _jit_sharded(sweep, mesh, 3, 0)
