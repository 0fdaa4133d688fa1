"""The ``registry_gauge`` reader and the two metrics that use it
(``hbm_peak_share``, ``state_init_s``), by hand on a registry: a share
of two gauges, one gauge alone, and nothing from a program that keeps
neither (the metrics' parent) or from a labelled gauge, and a share of 0
on a backend that reports no memory statistics."""

import types

from benchmarks.lib.manifest import Benchmark
from benchmarks.readers import registry_gauge


def _spec(name):
    bench = Benchmark.load()
    entry, = [m for m in bench.manifest["per_layer"] if m["name"] == name]
    spec = bench.layer_metric(name)
    assert spec["reader"] == "registry_gauge"
    return entry, spec["params"]


def test_the_two_metrics_are_declared_for_the_cells_that_hold_most():
    peak, _ = _spec("hbm_peak_share")
    init, _ = _spec("state_init_s")
    cells = ["backlog-1chip-r2p16", "backlog-1chip-2p21"]
    assert peak["workloads"] == init["workloads"] == cells
    assert (peak["unit"], peak["layer"], peak["moves"]) == (
        "%", "device", "ops_per_s")
    assert (init["unit"], init["layer"], init["moves"]) == (
        "s", "engine host side", "setup_s")


def test_a_share_of_two_gauges_and_a_gauge_alone():
    from grapevine_tpu.engine.metrics import EngineMetrics
    from grapevine_tpu.obs.registry import TelemetryRegistry

    _, peak = _spec("hbm_peak_share")
    _, init = _spec("state_init_s")
    server = types.SimpleNamespace()
    obs = {"ctx": types.SimpleNamespace(server=server)}
    for params in (peak, init):
        assert registry_gauge.read(params, obs) is None  # no registry
    server.metrics_registry = TelemetryRegistry()
    for params in (peak, init):
        assert registry_gauge.read(params, obs) is None  # no such gauge
    metrics = EngineMetrics()
    if metrics.registry.get("grapevine_hbm_peak_bytes") is None:
        return  # a program from before these gauges: nothing to read
    server.metrics_registry = metrics.registry
    # the CPU reports no memory statistics: peak and limit read 0
    assert registry_gauge.read(peak, obs) == 0.0
    assert registry_gauge.read(init, obs) == 0.0
    metrics.observe_device_memory([{"peak_bytes_in_use": 10_500_000_000,
                                    "bytes_limit": 16_909_336_576}])
    metrics.set_state_size(3.25, 10_253_823_600)
    assert registry_gauge.read(peak, obs) == 100 * 10_500_000_000 / 16_909_336_576
    assert registry_gauge.read(init, obs) == 3.25
    # a labelled gauge is not this reader's to read
    assert registry_gauge.read(
        {"gauge": "grapevine_stash_high_water"}, obs) is None
