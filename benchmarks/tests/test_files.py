"""The harness is driven by data: every name in BENCHMARK.json has its
file, a name with no file is refused, and a configuration, a traffic mix
and a per-layer metric can each be added as one new file."""

import copy
import json
import os
import shutil

import pytest

from benchmarks.lib.manifest import (HERE, ROOT, Benchmark, ManifestError,
                                     load_kind)


def test_every_named_file_loads():
    bench = Benchmark.load()
    bench.check_files()
    for w in bench.manifest["workloads"]:
        assert bench.config(w["config"])["chips"] == w["chips"]
        assert bench.traffic(w["traffic"])["name"] == w["traffic"]
        assert bench.per_layer(w["name"]), "a cell reports a per-layer metric"
        names = {m["name"] for m in bench.end_to_end(w["name"])}
        assert "setup_s" in names and len(names) >= 2


def test_every_cell_of_a_metric_reports_what_it_moves():
    bench = Benchmark.load()
    for m in bench.manifest["per_layer"]:
        # the manifest alone says unit, layer, moves and cells; the
        # metric's own file says how it is read
        assert set(bench.layer_metric(m["name"])) <= {"what", "reader",
                                                      "params"}
        for w in m["workloads"]:
            assert m["moves"] in {e["name"] for e in bench.end_to_end(w)}


def test_config_file_holds_what_the_manifest_and_the_server_need():
    bench = Benchmark.load()
    for c in bench.manifest["configs"]:
        cfg = bench.config(c["name"])
        assert cfg["source"] == c["source"]
        assert set(c["reduced"]) == set(cfg["reduced"])
        g, k = cfg["guarantees"], cfg["grapevine_config"]
        assert g["max_messages"] == k["max_messages"]
        assert g["max_recipients"] == k["max_recipients"]
        assert g["mailbox_cap"] == 62 and g["stash_overflow"] == 0


@pytest.mark.parametrize("broken", ["workload", "config", "traffic",
                                    "layer_metric", "driver", "reader"])
def test_a_name_with_no_file_is_refused(broken, tmp_path):
    bench = Benchmark.load()
    manifest = copy.deepcopy(bench.manifest)
    base = tmp_path / "base"
    shutil.copytree(os.path.join(HERE, "traffic"), base / "traffic")
    shutil.copytree(os.path.join(HERE, "layer_metrics"),
                    base / "layer_metrics")
    if broken == "workload":
        with pytest.raises(ManifestError):
            Benchmark(manifest, ROOT, str(base)).cell("no-such-cell")
        return
    if broken == "config":
        manifest["configs"][0]["file"] = "benchmarks/configs/absent.json"
    elif broken == "traffic":
        manifest["workloads"][0]["traffic"] = "absent-mix"
    elif broken == "layer_metric":
        manifest["per_layer"][0]["name"] = "absent_metric"
    elif broken == "driver":
        name = manifest["workloads"][0]["traffic"]
        path = base / "traffic" / f"{name}.json"
        path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                        kind="absent_driver")))
    else:
        name = manifest["per_layer"][0]["name"]
        path = base / "layer_metrics" / f"{name}.json"
        path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                        reader="absent_reader")))
    with pytest.raises(ManifestError):
        Benchmark(manifest, ROOT, str(base)).check_files()


def test_one_new_file_adds_a_config_a_mix_and_a_metric(tmp_path):
    """What a later PR does: it edits no file that is there."""
    bench = Benchmark.load()
    manifest = copy.deepcopy(bench.manifest)
    base = tmp_path / "base"
    shutil.copytree(os.path.join(HERE, "traffic"), base / "traffic")
    shutil.copytree(os.path.join(HERE, "layer_metrics"),
                    base / "layer_metrics")
    old = manifest["workloads"][0]
    cfg = dict(bench.config(old["config"]), name="new-config")
    cfg_path = tmp_path / "new-config.json"
    cfg_path.write_text(json.dumps(cfg))
    mix = dict(bench.traffic(old["traffic"]), name="new-mix",
               outstanding_rounds=5)
    (base / "traffic" / "new-mix.json").write_text(json.dumps(mix))
    metric = {"what": "the demux span, median per round",
              "reader": "ledger_span", "params": {"spans": ["demux"]}}
    (base / "layer_metrics" / "demux_ms.json").write_text(json.dumps(metric))
    manifest["configs"].append({**manifest["configs"][0], "name": "new-config",
                                "file": os.path.relpath(cfg_path, ROOT)})
    manifest["workloads"].append({**old, "name": "new-cell",
                                  "config": "new-config",
                                  "traffic": "new-mix"})
    manifest["per_layer"].append(
        {"name": "demux_ms", "unit": "ms", "better": "lower",
         "source": "program_span", "layer": "engine host side",
         "moves": "ops_per_s", "workloads": ["new-cell"]})
    # the new cell also reports a metric that is there: its name goes
    # into BENCHMARK.json, and no metric file changes
    for m in manifest["per_layer"]:
        if m["name"] == "round_ms":
            m["workloads"].append("new-cell")
    for m in manifest["end_to_end"]:
        if old["name"] in m.get("workloads", []):
            m["workloads"].append("new-cell")
    new = Benchmark(manifest, ROOT, str(base))
    new.check_files()
    assert new.traffic("new-mix")["outstanding_rounds"] == 5
    assert [m["name"] for m, _ in new.per_layer("new-cell")] == [
        "round_ms", "demux_ms"]
    assert load_kind("readers", "ledger_span").read


def test_benchmark_json_meets_the_contract_limits():
    m = Benchmark.load().manifest
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert m["paths"] == ["benchmarks"] and 1 <= m["run_seconds"] <= 51
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(m["workloads"]) // 2)
    for e in m["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.25
        assert e["source"] in ("host_clock", "device_trace")
    assert any(e["name"] == "setup_s" for e in m["end_to_end"])
    for w in m["workloads"]:
        assert len(w["why"]) <= 200
