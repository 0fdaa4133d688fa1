"""The ``oram_tree`` reader: a tree's share of its HBM roofline by hand
on the capture recorded on a TPU v5 lite (tests/data/
scopes_backlog_v5e.json: two whole rounds of backlog-1chip under PR
25's program, whose mailbox rounds still fetched a row per path and
level), the stash high-water read from a registry that keeps it per
tree, and nothing where there is nothing to read: a CPU rehearsal (no
device plane), a program that keeps one stash gauge over both trees."""

import json
import os
import types

import pytest

from benchmarks.lib import round_bytes
from benchmarks.lib.manifest import Benchmark
from benchmarks.readers import oram_tree

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

#: chipshare-2p20's trees as the run's ``init`` line says them
GEOMETRY = {"batch": 2048, "shards": 1, "trees": {
    "records": {"accesses": 2048, "passes": 1, "path_len": 20,
                "cached_levels": 4, "bucket_slots": 4, "value_words": 256,
                "encrypted": True},
    "mailbox": {"accesses": 4096, "passes": 2, "path_len": 11,
                "cached_levels": 4, "bucket_slots": 4, "value_words": 1520,
                "encrypted": True}}}


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "scopes_backlog_v5e.json")) as f:
        return json.load(f)


def _params(name):
    return Benchmark.load().layer_metric(name)["params"]


def _obs(capture, geometry=GEOMETRY, kind="TPU v5 lite"):
    return {"trace": capture, "_scopes": capture, "geometry": geometry,
            "device_kind": kind,
            "ctx": types.SimpleNamespace(say=lambda **kv: None)}


def test_roofline_shares_by_hand_on_the_recorded_capture(recorded):
    obs = _obs(recorded)
    # mailbox: 2 passes x 2,032 rows of 4 * (1 + 1520) + 2 = 6,086 words,
    # read once and written once, at 819 GB/s, over the 148.883617 +
    # 126.538601 ms that rounds A and C took in this capture
    mb_bytes = 2 * (2 * 2032 * 6086 * 4)
    assert round_bytes.tree_round_bytes(GEOMETRY["trees"]["mailbox"]) == (
        mb_bytes // 2, mb_bytes // 2)
    want = 100.0 * (mb_bytes / 819e9 * 1e3) / (148.883617 + 126.538601)
    got = oram_tree.read(_params("mailbox_hbm_roofline"), obs)
    assert got == pytest.approx(want, rel=1e-6) == pytest.approx(0.08772, rel=1e-3)
    # records: one pass of 20,464 rows of 4 * 257 + 2 = 1,030 words over
    # round B's 78.943745 ms
    rec_bytes = 2 * (20464 * 1030 * 4)
    want = 100.0 * (rec_bytes / 819e9 * 1e3) / 78.943745
    got = oram_tree.read(_params("records_hbm_roofline"), obs)
    assert got == pytest.approx(want, rel=1e-6) == pytest.approx(0.26080, rel=1e-3)
    # on a mesh a chip writes only the buckets it owns
    sharded = dict(GEOMETRY, shards=4)
    assert oram_tree.read(_params("records_hbm_roofline"),
                          _obs(recorded, sharded)) == pytest.approx(
        want * (1 + 1 / 4) / 2, rel=1e-6)


def test_no_share_without_a_device_plane_a_scope_or_a_known_peak(recorded):
    for params in (_params("mailbox_hbm_roofline"),
                   _params("records_hbm_roofline")):
        assert oram_tree.read(params, {"trace": None}) is None
        # a CPU rehearsal's capture holds host spans and no device plane
        cpu = dict(recorded, planes=[])
        assert oram_tree.read(params, _obs(cpu)) is None
    nowhere = dict(_params("mailbox_hbm_roofline"),
                   scope="grapevine/no_such_round(?:/|$)")
    assert oram_tree.read(nowhere, _obs(recorded)) is None
    with pytest.raises(KeyError, match="no published HBM peak"):
        oram_tree.read(_params("records_hbm_roofline"),
                       _obs(recorded, kind="TPU v9"))
    with pytest.raises(ValueError):
        oram_tree.read({"quantity": "nonsense"}, _obs(recorded))


def test_stash_peak_reads_the_mailbox_trees_own_gauge():
    from grapevine_tpu.engine.metrics import EngineMetrics
    from grapevine_tpu.obs.registry import TelemetryRegistry

    params = _params("mailbox_stash_peak")
    server = types.SimpleNamespace()
    obs = {"ctx": types.SimpleNamespace(server=server)}
    assert oram_tree.read(params, obs) is None  # no registry
    server.metrics_registry = TelemetryRegistry()
    assert oram_tree.read(params, obs) is None  # no such gauge
    # a program that keeps one gauge over both trees (this PR's parent)
    server.metrics_registry.gauge("grapevine_stash_high_water", "max").set(7)
    assert oram_tree.read(params, obs) is None
    metrics = EngineMetrics()
    server.metrics_registry = metrics.registry
    assert oram_tree.read(params, obs) == 0
    metrics.observe_stash("rec", 40)
    metrics.observe_stash("mb", 9)
    metrics.observe_stash("mb", 3)
    assert oram_tree.read(params, obs) == 9
