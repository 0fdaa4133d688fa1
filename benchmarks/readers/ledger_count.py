"""Per-layer metrics from the counts the program keeps beside each
round's spans (``obs/tracer.py`` ``ROUND_COUNTS``, read through
``chrome_trace()``: the ``args`` of the ``grapevine/round`` event).
``params``: ``count`` names the count; ``per`` optionally names a second
one to divide by, round by round (``queue_wait_sum_s`` per ``ops``);
``scale`` multiplies the result (1000 for seconds to ms). The median
over the rounds that began inside the window is returned; nothing when
no round carries the count (a program that keeps none)."""

from __future__ import annotations

import statistics


def read(params: dict, obs: dict):
    t0, t1 = (t * 1e6 for t in obs["window"])
    count, per = params["count"], params.get("per")
    values = []
    for ev in obs["ledger"]:
        if (ev.get("ph") != "X" or ev["name"] != "grapevine/round"
                or not t0 <= ev["ts"] <= t1):
            continue
        args = ev["args"]
        if count not in args or (per and not args.get(per)):
            continue
        values.append(args[count] / args[per] if per else args[count])
    if not values:
        return None
    return statistics.median(values) * params.get("scale", 1.0)
