"""The expiry sweep's yardstick: the least bytes a sweep must move, by
hand and held at or under the program's own cost ledger at every
configuration; the reader of the benchmark's sweep log; and the reader
of the capture, on a small capture written by hand (two programs of
different lengths on one device, as the sweep cell's holds)."""

import types

import pytest

from benchmarks.lib import peaks, sweep_bytes
from benchmarks.lib.manifest import Benchmark
from benchmarks.readers import sweep_log, xplane_sweep


def _configs():
    return [c["name"] for c in Benchmark.load().manifest["configs"]]


@pytest.mark.parametrize("config", _configs())
def test_least_sweep_bytes_are_at_most_the_cost_ledger(config):
    from grapevine_tpu.analysis.costmodel import engine_cost_ledger
    from grapevine_tpu.config import GrapevineConfig
    from grapevine_tpu.engine.state import EngineConfig

    spec = Benchmark.load().config(config)
    cfg = GrapevineConfig(**spec["grapevine_config"])
    ecfg = EngineConfig.from_config(cfg)
    least = sweep_bytes.least_sweep_bytes_per_chip(
        sweep_bytes.sweep_geometry(ecfg, cfg.shards))
    ledger = engine_cost_ledger(ecfg, shards=cfg.shards).phases["sweep"]
    # the program streams padded planes (one bucket more a tree) and
    # leaves the stashes out of its count; the floor counts them
    stashes = 2 * sum(sweep_bytes.tree_sweep_bytes(t)[1] for t in
                      sweep_bytes.sweep_geometry(ecfg, 1)["trees"].values())
    assert least <= ledger.hbm_bytes / cfg.shards + stashes
    assert least >= 0.99 * ledger.hbm_bytes / cfg.shards


def test_least_sweep_bytes_of_the_ttl_deployment_by_hand():
    g = {"shards": 1, "trees": {
        "records": {"buckets": (1 << 21) - 1, "bucket_slots": 4,
                    "value_words": 256, "encrypted": True, "stash_rows": 96},
        "mailbox": {"buckets": (1 << 16) - 1, "bucket_slots": 4,
                    "value_words": 1520, "encrypted": True,
                    "stash_rows": 96}}}
    rec = ((1 << 21) - 1) * (4 * 257 + 2) * 4
    mb = ((1 << 16) - 1) * (4 * 1521 + 2) * 4
    stash = 96 * (257 + 1521) * 4
    assert sweep_bytes.tree_sweep_bytes(g["trees"]["records"])[0] == rec
    assert sweep_bytes.least_sweep_bytes_per_chip(g) == 2 * (rec + mb + stash)
    # 20.47 GB, 25 ms at the v5e's 819 GB/s
    assert 24.9 < 2 * (rec + mb + stash) / 819e9 * 1e3 < 25.1
    # on a mesh a chip sweeps its share of the buckets and every stash
    assert sweep_bytes.least_sweep_bytes_per_chip(dict(g, shards=4)) == \
        2 * ((rec + mb) / 4 + stash)
    plain = dict(g["trees"]["records"], encrypted=False)
    assert sweep_bytes.tree_sweep_bytes(plain)[0] == \
        ((1 << 21) - 1) * 4 * 257 * 4


def test_sweep_log_reader_reads_the_sweeps_called_inside_the_window():
    sweeps = [{"t_start": t, "t_end": t + d, "evicted": 0}
              for t, d in [(8.0, 5.0), (10.5, 0.5), (12.0, 0.7), (19.8, 0.6),
                           (21.0, 0.5)]]
    obs = {"window": (10.0, 20.0), "sweeps": sweeps}
    read = lambda q: sweep_log.read({"quantity": q}, obs)  # noqa: E731
    # set-up's and the one called after the window are out
    assert read("wall_ms") == pytest.approx(600.0)
    assert sweep_log.read({"quantity": "wall_ms"},
                          {"window": (0.0, 1.0), "sweeps": []}) is None
    assert sweep_log.read({"quantity": "wall_ms"},
                          {"window": (0.0, 1.0)}) is None
    with pytest.raises(ValueError):
        read("no_such")


def test_sweep_stall_is_the_mean_over_every_sweep_inside_the_window():
    from benchmarks.drivers import scheduler_backlog_sweep as driver

    sweeps = [{"t_start": t, "t_end": t + d} for t, d in
              [(9.9, 0.3),             # called before the window opened
               (10.5, 0.5), (12.0, 0.7), (15.0, 0.9),
               (19.8, 0.6),            # still running when it closed
               (21.0, 0.5)]]           # the one made due behind it
    rounds = [{"ok": [True] * 8, "t_resolved": 11.0},
              {"ok": [True] * 7 + [False], "t_resolved": 20.0}]
    obs = {"window": (10.0, 20.0), "sweeps": sweeps, "rounds": rounds}
    assert driver.stalls_ms(obs) == pytest.approx([500.0, 700.0, 900.0])
    values = driver.end_to_end(None, obs)
    assert values["sweep_stall_ms"] == pytest.approx(700.0)
    assert values["ops_per_s"] == pytest.approx(1.5)  # printed, not judged
    assert driver.end_to_end(None, dict(obs, sweeps=[]))[
        "sweep_stall_ms"] is None


REC = "jit(expiry_sweep)/grapevine/sweep_records/while/body/fusion"
MB = "jit(expiry_sweep)/grapevine/sweep_mailbox/while/body/fusion"
ROUND = "jit(engine_round_step)/grapevine/round_b_records/gather"


def _capture():
    """Sweeps of 400 ns at 0 (cut by the capture's start), 620, 1240 and
    1860 (the last), two rounds of 100 ns after each."""
    modules, ops, calls = [], [], []
    for s in (0, 620, 1240, 1860):
        modules.append(["jit_expiry_sweep(1)", s, 400])
        calls.append(["bench/sweep", s - 50, 460])
        ops += [["copy.1 wrapper", s, 400, -1],
                ["while.4", s + 5, 300, 0],        # holds the fusion
                ["fusion.7", s + 10, 290, 0],
                ["fusion.9", s + 305, 90, 1]]
        for r in (s + 410, s + 515):
            modules.append(["jit_engine_round_step(2)", r, 100])
            ops += [["copy.2 wrapper", r, 100, -1], ["gather.3", r + 10, 80, 2]]
    device = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": modules},
        {"name": "XLA Ops", "events": ops}]}
    capture = {"scope_paths": [REC, MB, ROUND], "planes": [device],
               "host_spans": []}
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": [e[:3] for e in ops]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "bench-sweeps", "events": calls},
            {"name": "gv-collector",
             "events": [["bench/dispatch", 100, 5]]}]}]}
    return capture, trace


def _obs():
    capture, trace = _capture()
    tree = lambda levels, v: types.SimpleNamespace(  # noqa: E731
        path_len=levels, bucket_slots=4, value_words=v, encrypted=True,
        stash_size=8)
    ecfg = types.SimpleNamespace(rec=tree(5, 16), mb=tree(3, 32))
    ctx = types.SimpleNamespace(engine=types.SimpleNamespace(ecfg=ecfg))
    return {"trace": trace, "_scopes": capture, "ctx": ctx, "shards": 1,
            "device_kind": "TPU v5 lite"}


def test_xplane_sweep_reads_whole_sweeps_and_leaves_the_rounds_out():
    obs = _obs()
    read = lambda q, **kw: xplane_sweep.read(  # noqa: E731
        dict(kw, quantity=q), obs)
    assert read("device_ms") == pytest.approx(400e-6)
    # the two whole sweeps: 300 ns under sweep_records (the while and
    # the fusion it holds, counted once), 90 under sweep_mailbox; the
    # wrapper as long as its program is no work
    assert read("scope_ms", scope="grapevine/sweep_records(?:/|$)") == \
        pytest.approx(300e-6)
    assert read("scope_ms", scope="grapevine/sweep_mailbox(?:/|$)") == \
        pytest.approx(90e-6)
    assert read("scope_ms", scope="grapevine/round_b_records") == 0
    assert read("wait_ms") == pytest.approx(50e-6)
    least = sweep_bytes.least_sweep_bytes_per_chip(
        sweep_bytes.sweep_geometry(obs["ctx"].engine.ecfg, 1))
    assert least == 2 * 4 * (31 * 70 + 7 * 134 + 8 * (17 + 33))
    floor_ms = least / (peaks.peak_hbm_gbps("TPU v5 lite") * 1e9) * 1e3
    assert read("hbm_roofline_pct") == pytest.approx(100 * floor_ms / 400e-6)
    with pytest.raises(ValueError):
        read("no_such")


def test_xplane_sweep_returns_nothing_without_three_sweeps_or_a_capture():
    obs = _obs()
    device = obs["_scopes"]["planes"][0]
    device["lines"][0]["events"] = [
        m for m in device["lines"][0]["events"]
        if "round" in m[0] or m[1] < 1000]  # two sweep programs are left
    assert xplane_sweep.read({"quantity": "device_ms"}, obs) is None
    assert xplane_sweep.read({"quantity": "hbm_roofline_pct"}, obs) is None
    assert xplane_sweep.read({"quantity": "device_ms"},
                             {"trace": None}) is None
