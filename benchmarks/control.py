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
correct on every seed (``ops_wrong`` > 0).

A cell whose log holds expiry sweeps gets a third replay, with a second
control in the program's place: the plain oracle whose ``expire`` does
nothing, which breaks the configuration's ``expiry`` guarantee (a
drained mailbox's slot is free after the sweep; a record older than the
TTL is gone). It too must come out as not correct on every seed: its
recipient count stands where the engine's would (``recipient_count_gap``
> 0), beside its answers and the records it removed (where the cell's
driver makes records come due behind the window, ``sweep_evicted_gap``,
``message_count_gap`` and ``ops_wrong`` > 0 too). Exit 0 only if all of
it holds.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class InPlace:
    """``answered`` for ``compare.replay``: a control oracle where the
    engine would stand. Its answers to a round (under the ids the
    engine gave), the records it removes at a sweep, and at the end its
    own counts of messages and recipients (``oracle``)."""

    def __init__(self, oracle):
        self.oracle = oracle

    def __call__(self, entry):
        from benchmarks.lib import wire as W

        if entry.get("kind") == "sweep":
            return self.oracle.expire(entry["now"], entry["period"])
        forced = [d.record.msg_id if r.request_type == W.CREATE
                  and d.status_code == W.SUCCESS else None
                  for r, d in zip(entry["reqs"], entry["resps"])]
        forced += [None] * (len(entry["reqs"]) - len(forced))
        return self.oracle.handle_batch(entry["reqs"], entry["now"], forced)


def control_in_place(guarantees: dict) -> InPlace:
    """The oracle with the mailbox cap not enforced."""
    from benchmarks.lib.oracle import Oracle

    return InPlace(Oracle(guarantees["max_messages"],
                          guarantees["max_recipients"], mailbox_cap=1 << 62))


def no_reclaim_in_place(guarantees: dict) -> InPlace:
    """The oracle whose ``expire`` does nothing: no record leaves with
    its TTL and no drained mailbox gives its slot back."""
    from benchmarks.lib.oracle import Oracle

    class NoReclaim(Oracle):
        def expire(self, now, period):
            return 0

    return InPlace(NoReclaim(guarantees["max_messages"],
                             guarantees["max_recipients"],
                             guarantees["mailbox_cap"]))


def no_reclaim_numbers(entries, guarantees: dict) -> dict:
    """The replay with the no-reclaim control in the program's place:
    the numbers of ``correct`` that a sweep can move, the control's
    counts standing where ``engine.health()``'s would."""
    from benchmarks.lib import compare

    ctl = no_reclaim_in_place(guarantees)
    rep = compare.replay(entries, guarantees, answered=ctl)
    return {"ops_wrong": rep["ops_wrong"],
            "message_count_gap":
                abs(len(ctl.oracle.records) - rep["oracle_messages"]),
            "recipient_count_gap":
                abs(len(ctl.oracle.mailboxes) - rep["oracle_recipients"]),
            "sweep_evicted_gap": rep["sweep_evicted_gap"]}


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
    sound = caught = swept = reclaim_caught = 0
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
                            program_compared=cell.compared,
                            control_ops_wrong=wrong, limit=0,
                            control_first_wrong=ctl["first_wrong"],
                            rounds_in_window=len(obs["rounds"]),
                            sweeps_in_window=len(obs["sweeps"]))
                sound += bool(correct)
                caught += wrong > 0
                if rep["sweeps"]:
                    numbers = no_reclaim_numbers(cell.log.entries,
                                                 cell.config["guarantees"])
                    not_correct = not compare.verdict(numbers)[0]
                    harness.say(phase="control_no_reclaim", seed=seed,
                                sweeps=rep["sweeps"], limit=0, **numbers)
                    swept += 1
                    reclaim_caught += not_correct
        finally:
            cell.close()
    print(json.dumps({"seeds": len(seeds), "program_correct": sound,
                      "control_not_correct": caught,
                      "seeds_with_sweeps": swept,
                      "no_reclaim_control_not_correct": reclaim_caught,
                      "device": harness.device_info()}), flush=True)
    return 0 if (sound == caught == len(seeds)
                 and reclaim_caught == swept) else 1


if __name__ == "__main__":
    sys.exit(main())
