"""Multi-chip parallelism: device mesh, state shardings, sharded engine step.

The reference is single-node/single-enclave (capacity "close to the RAM
limits of the machine", reference README.md:75-76); its named scale-out
future is node-to-node replication (README.md:117-121). The TPU build's
scale axis instead shards the ORAM bucket trees across a chip mesh so bus
capacity grows with pod HBM (SURVEY.md §2c, BASELINE config 5).
"""

from .mesh import (
    TREE_AXIS,
    engine_state_specs,
    init_sharded_engine,
    make_mesh,
    make_sharded_step,
    make_sharded_sweep,
    shard_engine_state,
    validate_sharded_geometry,
)

__all__ = [
    "TREE_AXIS",
    "engine_state_specs",
    "init_sharded_engine",
    "make_mesh",
    "make_sharded_step",
    "make_sharded_sweep",
    "shard_engine_state",
    "validate_sharded_geometry",
]
