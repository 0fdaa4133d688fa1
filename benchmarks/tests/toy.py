"""The real BENCHMARK.json with its configurations swapped for toy
geometries and its traffic read from tests/data: a rehearsal of the
harness itself on the CPU."""

from __future__ import annotations

import copy
import os
import shutil

from benchmarks.lib.manifest import HERE, ROOT, Benchmark

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TOY_CONFIG = {"chipshare-2p20": "toy-1dev", "host4-sharded-2p22": "toy-4dev"}


def toy_bench(base) -> Benchmark:
    """``base`` (a fresh directory) gets the toy traffic files and the
    real per-layer metric files."""
    manifest = copy.deepcopy(Benchmark.load().manifest)
    for c in manifest["configs"]:
        c["file"] = os.path.join("benchmarks", "tests", "data", "configs",
                                 TOY_CONFIG[c["name"]] + ".json")
    if not os.path.isdir(os.path.join(base, "traffic")):
        shutil.copytree(os.path.join(DATA, "traffic"),
                        os.path.join(base, "traffic"))
        shutil.copytree(os.path.join(HERE, "layer_metrics"),
                        os.path.join(base, "layer_metrics"))
    return Benchmark(manifest, ROOT, str(base))
