#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process that owns the cell's chips. It refuses to start unless
JAX's first device is a TPU and the machine holds exactly the chips the
cell asks for (exit 2, no result line; no platform override is set or
read). Every line of stdout is one JSON object; the last is the result.
See benchmarks/README.md.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    from benchmarks.lib.manifest import Benchmark

    bench = Benchmark.load()
    cell = bench.cell(args.workload)
    bench.check_files()

    from benchmarks.lib import harness

    why_not = harness.chips_missing(cell)
    if why_not:
        print(f"benchmarks/run.py: {why_not}. Nothing was run.",
              file=sys.stderr)
        return 2
    cache = harness.prepare_process()

    harness.say(phase="env", compile_cache=cache,
                compile_cache_entries=len(os.listdir(cache))
                if os.path.isdir(cache) else 0)
    with harness.scratch_dir(args.workload) as scratch:
        result = harness.run_cell(bench, args.workload, args.seed,
                                  args.seconds, bool(args.trace),
                                  T_PROCESS_START, scratch)
    print(json.dumps(result), flush=True)
    # the last lines of standard error: each number compared, its limit
    for name, x in result["compared"].items():
        print(f"compared {name} {x['value']} limit {x['limit']}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
