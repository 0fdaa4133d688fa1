"""Per-layer metrics from the span ledger the program keeps for every
round (``obs/tracer.py`` ``RoundTracer``, read through its public
``chrome_trace()``: events ``grapevine/<span>`` with ``ts`` and ``dur``
in microseconds on the ``perf_counter`` clock, ``args.seq`` the round).
``params``: ``spans`` are summed per round; the median over the rounds
that began inside the window is returned, in ms."""

from __future__ import annotations

import statistics


def read(params: dict, obs: dict):
    want = {f"grapevine/{s}" for s in params["spans"]}
    t0, t1 = (t * 1e6 for t in obs["window"])
    per_round: dict[int, float] = {}
    inside: set[int] = set()
    for ev in obs["ledger"]:
        if ev.get("ph") != "X":
            continue
        seq = ev["args"]["seq"]
        if ev["name"] == "grapevine/round" and t0 <= ev["ts"] <= t1:
            inside.add(seq)
        if ev["name"] in want:
            per_round[seq] = per_round.get(seq, 0.0) + ev["dur"]
    sums = [v for seq, v in per_round.items() if seq in inside]
    if not sums:
        return None
    return statistics.median(sums) / 1e3
