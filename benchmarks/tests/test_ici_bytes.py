"""The mesh's assembly of the fetched rows (``lib/ici_bytes.py`` and the
``mesh_psum`` reader): the bytes a chip must receive by hand and at the
geometry of ``host4-sharded-2p23``, the share of the inter-chip
roofline on a by-hand capture, the two scope metrics apart on it, and
nothing on one chip or without a capture."""

import types

import pytest

from benchmarks.lib import ici_bytes, round_bytes, xplane
from benchmarks.lib.manifest import Benchmark
from benchmarks.readers import mesh_psum, xplane_scope

FUSION = "%fusion.1 = u32[8]{0} fusion(u32[8]{0} %p), kind=kLoop"
ALL_REDUCE = "%all-reduce.3 = u32[8]{0} all-reduce(u32[8]{0} %p)"
PSUM = "jit(step)/grapevine/round_{}/grapevine/oram_fetch/grapevine/" \
    "path_gather/grapevine/psum_assembly/psum"


def _tree(path_len, accesses, passes, value_words, cached=4):
    t = {"accesses": accesses, "passes": passes, "path_len": path_len,
         "cached_levels": cached, "bucket_slots": 4,
         "value_words": value_words, "encrypted": True}
    t["rows_per_pass"] = round_bytes.pass_rows(t)
    return t


#: ``host4-sharded-2p23`` as ``round_bytes.round_geometry`` says it
REAL = {"batch": 2048, "shards": 4, "trees": {
    "records": _tree(23, 2048, 1, 256), "mailbox": _tree(18, 4096, 2, 1520)}}


def test_received_bytes_by_hand():
    # 3 levels under the cache of 2: 4 + 8 whole, then one row an access
    g = {"shards": 4, "trees": {"t": dict(
        _tree(5, 8, 2, 10, cached=2), encrypted=False)}}
    rows = 4 + 8 + 8
    assert round_bytes.pass_rows(g["trees"]["t"]) == rows
    row = 4 * (4 + 4 * 10)
    assert ici_bytes.least_received_bytes_per_chip(g) == 2 * rows * row * 3 / 4
    # a stored row wider than its blocks, and a nonce where encrypted
    g["trees"]["t"]["encrypted"] = True
    assert ici_bytes.least_received_bytes_per_chip(g, {"t": 48}) == (
        2 * rows * 4 * (4 + 48 + 2) * 3 / 4)
    assert ici_bytes.least_received_bytes_per_chip(dict(g, shards=1)) == 0.0
    assert ici_bytes.least_received_bytes_per_chip(dict(g, shards=2)) == (
        2 * rows * 4 * (4 + 40 + 2) / 2)


def test_received_bytes_at_the_real_geometry():
    assert REAL["trees"]["records"]["rows_per_pass"] == 26_608
    assert REAL["trees"]["mailbox"]["rows_per_pass"] == 28_656
    got = ici_bytes.least_received_bytes_per_chip(
        REAL, {"records": 1024, "mailbox": 6144})
    # the mailbox row is stored as 6,144 words, with 4 index words and
    # a 2-word nonce: 24,600 B; the records row 4,120 B
    assert got == (2 * 28_656 * 24_600 + 26_608 * 4_120) * 3 / 4
    assert got == 1_139_625_120.0
    # 5.7 ms at the published 200 GB/s: the least a round's assembly
    # can take on this chip
    assert got / (ici_bytes.peak_ici_gbps("TPU v5 lite") * 1e9) == (
        pytest.approx(5.698e-3, rel=1e-3))
    with pytest.raises(KeyError):
        ici_bytes.peak_ici_gbps("TPU v9")


def _obs(shards=4, with_capture=True):
    """Two whole rounds of 100 ns: 30 ns of mailbox psum (a mask and an
    all-reduce in round a, an all-reduce in round c), 10 ns of records
    psum and 20 ns of other work, a round."""
    paths = [PSUM.format("a_mailbox"), PSUM.format("c_mailbox"),
             PSUM.format("b_records"),
             "jit(step)/grapevine/round_b_records/grapevine/oram_evict/sort"]
    ops = []
    for t in (100.0, 200.0):
        ops += [[FUSION, t, 5.0, 0], [ALL_REDUCE, t + 5, 10.0, 0],
                [ALL_REDUCE, t + 20, 10.0, 2], [FUSION, t + 30, 20.0, 3],
                [ALL_REDUCE, t + 60, 15.0, 1]]
    mods = [["jit_round(1)", t, 100.0] for t in (0.0, 100.0, 200.0, 300.0)]
    capture = {"scope_paths": paths, "host_spans": [], "planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": xplane.MODULES_LINE, "events": mods},
            {"name": xplane.OPS_LINE, "events": ops}]}]}
    ecfg = types.SimpleNamespace(
        rec=types.SimpleNamespace(stored_row_words=1024),
        mb=types.SimpleNamespace(stored_row_words=6144))
    obs = {"geometry": dict(REAL, shards=shards),
           "device_kind": "TPU v5 lite",
           "ctx": types.SimpleNamespace(
               engine=types.SimpleNamespace(ecfg=ecfg), scratch="/nowhere",
               say=lambda **kv: None)}
    if with_capture:
        obs["trace"] = {"planes": capture["planes"]}
        obs["_scopes"] = capture
    return obs


def _params(name):
    return Benchmark.load().layer_metric(name)["params"]


def test_the_psum_scopes_are_read_apart_and_the_roofline_over_both():
    obs = _obs()
    mailbox = xplane_scope.read(_params("scope_ms.psum_mailbox"), obs)
    records = xplane_scope.read(_params("scope_ms.psum_records"), obs)
    assert mailbox == pytest.approx(30e-6) and records == pytest.approx(10e-6)
    share = mesh_psum.read(_params("psum_ici_roofline"), obs)
    floor_ms = 1_139_625_120.0 / 200e9 * 1e3
    assert share == pytest.approx(100.0 * floor_ms / 40e-6)
    # the metric's own file names the reader and no other quantity
    assert Benchmark.load().layer_metric("psum_ici_roofline")["reader"] == (
        "mesh_psum")
    with pytest.raises(ValueError):
        mesh_psum.read({"quantity": "other", "scope": "x"}, obs)


def test_nothing_on_one_chip_without_a_capture_or_without_the_scope():
    params = _params("psum_ici_roofline")
    assert mesh_psum.read(params, _obs(shards=1)) is None
    assert mesh_psum.read(params, _obs(with_capture=False)) is None
    # a program whose assembly carries no such scope: nothing to read
    assert mesh_psum.read(dict(params, scope="grapevine/absent"),
                          _obs()) is None
    # a program that says no stored width: the block words stand in
    obs = _obs()
    obs["ctx"].engine = None
    got = mesh_psum.read(params, obs)
    blocks = (2 * 28_656 * 4 * (4 + 6080 + 2) + 26_608 * 4_120) * 3 / 4
    assert got == pytest.approx(100.0 * blocks / 200e9 * 1e3 / 40e-6)
