"""Per-layer metrics from the RoundLog: what the rounds resolved inside
the window held and when. ``params``: ``quantity`` is ``fill_pct`` (mean
real ops per round over the batch size), ``period_ms`` (median time
between consecutive rounds' resolved answers) or ``resolve_ms`` (median
dispatch -> resolved answers of one round)."""

from __future__ import annotations

import statistics


def read(params: dict, obs: dict):
    rounds = obs["rounds"]
    q = params["quantity"]
    if q == "fill_pct":
        if not rounds:
            return None
        return (100.0 * statistics.fmean(len(e["reqs"]) for e in rounds)
                / obs["batch_size"])
    if q == "period_ms":
        ts = [e["t_resolved"] for e in rounds]
        if len(ts) < 2:
            return None
        return 1e3 * statistics.median(b - a for a, b in zip(ts, ts[1:]))
    if q == "resolve_ms":
        if not rounds:
            return None
        return 1e3 * statistics.median(
            e["t_resolved"] - e["t_dispatch"] for e in rounds)
    raise ValueError(f"roundlog reader: unknown quantity {q!r}")
