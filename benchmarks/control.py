#!/usr/bin/env python3
"""The control of ``correct``, and a dozen seeds of the program, in one
process on the chip. The benchmark's own runs never run this.

    python3 benchmarks/control.py --workload <name> --seeds 1,2,3 --seconds 6

One served bus is built as ``run.py`` builds it; each seed then gets a
short window of the cell's own traffic at the cell's own load. After
each window the whole log is replayed twice: once as ``run.py`` does
(the program's numbers, which must all be 0), and once with the control
in the program's place. The system states no precision, so the control
breaks one guarantee the configuration states: it is the plain oracle
with the 62-message mailbox cap not enforced. It must come out as not
correct on every seed (``ops_wrong`` > 0). Exit 0 only if both hold.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def control_in_place(guarantees: dict):
    """``answered`` for ``compare.replay``: the uncapped oracle's answers
    where the engine's would stand."""
    from benchmarks.lib import wire as W
    from benchmarks.lib.oracle import Oracle

    ctl = Oracle(guarantees["max_messages"], guarantees["max_recipients"],
                 mailbox_cap=1 << 62)

    def answered(entry):
        forced = [d.record.msg_id if r.request_type == W.CREATE
                  and d.status_code == W.SUCCESS else None
                  for r, d in zip(entry["reqs"], entry["resps"])]
        forced += [None] * (len(entry["reqs"]) - len(forced))
        return ctl.handle_batch(entry["reqs"], entry["now"], forced)

    return answered


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    from benchmarks.lib import compare, harness
    from benchmarks.lib.manifest import Benchmark

    bench = Benchmark.load()
    cell_entry = bench.cell(args.workload)
    why_not = harness.chips_missing(cell_entry)
    if why_not:
        print(f"benchmarks/control.py: {why_not}. Nothing was run.",
              file=sys.stderr)
        return 2
    harness.prepare_process()
    seeds = [int(s) for s in args.seeds.split(",")]
    sound = caught = 0
    with harness.scratch_dir("control-" + args.workload) as scratch:
        cell = harness.Cell(bench, args.workload, seeds[0], scratch)
        try:
            for seed in seeds:
                obs = cell.drive(seed, args.seconds, False,
                                 ident_seed=seeds[0])
                correct, failed, rep = cell.judge(obs)
                ctl = compare.replay(
                    cell.log.entries, cell.config["guarantees"],
                    answered=control_in_place(cell.config["guarantees"]))
                # replay() marked each round anew: this seed's rounds alone
                wrong = sum(e["ok"].count(False) for e in obs["all_rounds"])
                harness.say(phase="control", seed=seed,
                            program_correct=correct, program_failed=failed,
                            program_ops_compared=rep["ops_compared"],
                            control_ops_wrong=wrong, limit=0,
                            control_first_wrong=ctl["first_wrong"],
                            rounds_in_window=len(obs["rounds"]))
                sound += bool(correct)
                caught += wrong > 0
        finally:
            cell.close()
    print(json.dumps({"seeds": len(seeds), "program_correct": sound,
                      "control_not_correct": caught,
                      "device": harness.device_info()}), flush=True)
    return 0 if sound == caught == len(seeds) else 1


if __name__ == "__main__":
    sys.exit(main())
