"""A checkout's BENCHMARK.json with its configurations swapped for toy
geometries and its traffic read from tests/data: a rehearsal of the
harness itself on the CPU.

Both are found by name, as the harness finds the real ones: a
configuration's toy geometry is ``tests/data/configs/<configuration
name>.json`` and a mix's toy parameters ``tests/data/traffic/<mix
name>.json``. So a PR that adds a configuration or a mix adds its toy
file beside it and edits nothing here."""

from __future__ import annotations

import copy
import os
import shutil

from benchmarks.lib.manifest import ROOT, Benchmark, ManifestError

TOY_DATA = os.path.join("benchmarks", "tests", "data")


def toy_bench(base, root: str = ROOT) -> Benchmark:
    """The benchmark of the checkout at ``root`` at toy size. ``base``
    (a fresh directory) gets the toy traffic files and the real
    per-layer metric files."""
    manifest = copy.deepcopy(Benchmark.load(root).manifest)
    for c in manifest["configs"]:
        c["file"] = os.path.join(TOY_DATA, "configs", c["name"] + ".json")
        if not os.path.isfile(os.path.join(root, c["file"])):
            raise ManifestError(
                f"configuration {c['name']!r} has no toy geometry for the "
                f"CPU rehearsal: add {c['file']}")
    if not os.path.isdir(os.path.join(base, "traffic")):
        shutil.copytree(os.path.join(root, TOY_DATA, "traffic"),
                        os.path.join(base, "traffic"))
        shutil.copytree(os.path.join(root, "benchmarks", "layer_metrics"),
                        os.path.join(base, "layer_metrics"))
    return Benchmark(manifest, root, str(base))
