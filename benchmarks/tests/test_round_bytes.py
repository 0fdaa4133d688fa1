"""The benchmark's least-bytes-per-round function is a floor: derived
from what an oblivious round must move, checked by hand on a tree with
dense and per-path levels, and held at or under the program's cost
ledger (which matches the traced row census bit-exactly, PR 17) at the
geometry of every configuration ``BENCHMARK.json`` names. Today the
program moves exactly the floor's rows, so the two are equal; a program
that moved more would still pass, one that moved less would not."""

import pytest

from benchmarks.lib import round_bytes
from benchmarks.lib.manifest import Benchmark


def _configs():
    return [c["name"] for c in Benchmark.load().manifest["configs"]]


@pytest.mark.parametrize("config", _configs())
def test_least_bytes_are_at_most_the_cost_ledger_at_every_configuration(config):
    from grapevine_tpu.analysis.costmodel import engine_cost_ledger
    from grapevine_tpu.config import GrapevineConfig
    from grapevine_tpu.engine.state import EngineConfig

    spec = Benchmark.load().config(config)
    cfg = GrapevineConfig(**spec["grapevine_config"])
    # what the code resolves on a TPU (printed by every chip run's "init"
    # line): tree-top cache 4, per-round eviction; the CPU resolves the same
    ecfg = EngineConfig.from_config(cfg)
    assert ecfg.tree_top_cache_levels == 4 and ecfg.evict_every == 1
    geometry = round_bytes.round_geometry(ecfg, cfg.shards)
    least = round_bytes.least_round_bytes_per_chip(geometry)
    ledger = engine_cost_ledger(ecfg, shards=cfg.shards)
    assert least <= ledger.per_shard_steady_round_bytes
    # equal today: the level-dense round (PR 26) moves the floor's rows
    assert least == ledger.per_shard_steady_round_bytes
    for name, oram in (("records", ecfg.rec), ("mailbox", ecfg.mb)):
        t = geometry["trees"][name]
        # the program's own count is the check, not the source
        assert t["rows_per_pass"] == oram.fetched_bucket_rows(t["accesses"])
    assert geometry["trees"]["records"]["value_words"] == 256  # 1 KiB records


@pytest.mark.parametrize("recipients_log2,records_levels,want", [
    (12, 20, (20464, 2032)),    # chipshare-2p20
    (12, 22, (24560, 2032)),    # host4-sharded-2p22, the records tree deeper
    (16, 20, (20464, 16368)),   # a mailbox tree taller than the batch covers
])
def test_rows_of_the_deployments_by_hand(recipients_log2, records_levels,
                                          want):
    rec = {"accesses": 2048, "path_len": records_levels, "cached_levels": 4}
    mb = {"accesses": 4096, "path_len": recipients_log2 - 1,
          "cached_levels": 4}
    assert (round_bytes.pass_rows(rec), round_bytes.pass_rows(mb)) == want


def test_least_bytes_by_hand_on_a_dense_and_per_path_tree():
    tree = {"accesses": 10, "passes": 2, "path_len": 6, "cached_levels": 2,
            "bucket_slots": 4, "value_words": 3, "encrypted": True}
    # levels 2 and 3 hold 4 and 8 buckets, no more than the 10 accesses:
    # moved whole; levels 4 and 5 (16, 32 buckets) cost a row per access
    assert round_bytes.pass_rows(tree) == 4 + 8 + 10 + 10
    # two passes of 32 rows of 4 * (1 + 3) + 2 = 18 words
    assert round_bytes.tree_round_bytes(tree) == (64 * 18 * 4, 64 * 18 * 4)
    g = {"shards": 4, "trees": {"t": tree}}
    assert round_bytes.least_round_bytes_per_chip(g) == 4608 + 4608 / 4
    plain = dict(tree, encrypted=False, passes=1)
    assert round_bytes.tree_round_bytes(plain)[0] == 32 * 16 * 4
    # a tree the batch covers whole, and one wholly per path
    assert round_bytes.pass_rows(dict(tree, accesses=64)) == 4 + 8 + 16 + 32
    assert round_bytes.pass_rows(dict(tree, accesses=1)) == 4
    assert round_bytes.pass_rows(dict(tree, cached_levels=6)) == 0
