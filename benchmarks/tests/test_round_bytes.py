"""The benchmark's least-bytes-per-round function, checked once against
the program's cost ledger at each cell's geometry (the ledger matches
the traced row census bit-exactly, PR 17): equal today, and from here on
the benchmark's own."""

import json
import os

import pytest

from benchmarks.lib import round_bytes
from benchmarks.lib.manifest import HERE


@pytest.mark.parametrize("config", ["chipshare-2p20", "host4-sharded-2p22"])
def test_least_bytes_equal_the_cost_ledger_at_the_cell_geometry(config):
    import dataclasses

    from grapevine_tpu.analysis.costmodel import engine_cost_ledger
    from grapevine_tpu.config import GrapevineConfig
    from grapevine_tpu.engine.state import EngineConfig

    spec = json.load(open(os.path.join(HERE, "configs", f"{config}.json")))
    cfg = GrapevineConfig(**spec["grapevine_config"])
    # what the code resolves on a TPU (printed by every chip run's "init"
    # line): tree-top cache 4, per-round eviction; the CPU resolves the same
    ecfg = EngineConfig.from_config(cfg)
    assert ecfg.tree_top_cache_levels == 4 and ecfg.evict_every == 1
    geometry = round_bytes.round_geometry(ecfg, cfg.shards)
    ledger = engine_cost_ledger(ecfg, shards=cfg.shards)
    assert (round_bytes.least_round_bytes_per_chip(geometry)
            == ledger.per_shard_steady_round_bytes)
    assert geometry["trees"]["records"]["value_words"] == 256  # 1 KiB records
    assert dataclasses.is_dataclass(ecfg)


def test_least_bytes_by_hand():
    tree = {"accesses": 10, "path_len": 6, "cached_levels": 2,
            "bucket_slots": 4, "value_words": 3, "encrypted": True}
    # 40 rows of 4 * (1 + 3) + 2 = 18 words
    assert round_bytes.tree_round_bytes(tree) == (40 * 18 * 4, 40 * 18 * 4)
    g = {"shards": 4, "trees": {"t": tree}}
    assert round_bytes.least_round_bytes_per_chip(g) == 2880 + 2880 / 4
    plain = dict(tree, encrypted=False)
    assert round_bytes.tree_round_bytes(plain)[0] == 40 * 16 * 4
