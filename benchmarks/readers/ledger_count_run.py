"""Per-layer metrics from a count of the program's ledger that is
coarse round by round (``readers/ledger_count.py`` has the counts and
where they come from): the collector's CPU seconds a cycle
(``cycle_cpu_s``) and what is taken from them (``cycle_blocked_s``). The
chip's hosts account a thread's CPU time by the timer tick, 10 ms, so
one cycle's count is a multiple of the tick, off by up to one, and the
median over rounds is the tick's multiple nearest the truth (40.0000 ms
in two runs; my chip runs, PR 39). ``params``: ``count`` names the
count, ``scale`` multiplies the result. The rounds that began inside the
window are taken ``RUN`` at a time in ``seq`` order, each run gives its
mean, and the median over the runs is returned (a stall of the machine
moves one run, not the result). Nothing when fewer than ``RUN`` rounds
carry the count (a program that keeps none)."""

from __future__ import annotations

import statistics

#: consecutive rounds averaged: 8 rounds of 30-40 ms of CPU in ticks of
#: 10 read to a tick's eighth
RUN = 8


def read(params: dict, obs: dict):
    t0, t1 = (t * 1e6 for t in obs["window"])
    count = params["count"]
    values = [ev["args"][count] for ev in sorted(
        (ev for ev in obs["ledger"]
         if ev.get("ph") == "X" and ev["name"] == "grapevine/round"
         and t0 <= ev["ts"] <= t1 and count in ev["args"]),
        key=lambda ev: ev["args"]["seq"])]
    means = [statistics.fmean(values[i:i + RUN])
             for i in range(0, len(values) - RUN + 1, RUN)]
    if not means:
        return None
    return statistics.median(means) * params.get("scale", 1.0)
