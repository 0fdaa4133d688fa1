"""Per-layer metrics from the expiry sweeps in the benchmark's own log
(``lib/roundlog.py``: ``RoundLog`` wraps ``engine.expire``; a sweep's
entry holds ``t_start``, taken when ``expire`` was called, and ``t_end``,
when it returned, on the host's clock). Only the sweeps called inside
the window are read: the driver's own in set-up, which may compile, lies
before it. ``params``: ``quantity`` is

- ``wall_ms``: the median of ``expire`` called -> returned, ms: the
  wait for the rounds in flight, the program's enqueue, the device's
  pass (the cell's end-to-end ``sweep_stall_ms`` is the mean of the
  same over every sweep of the window).

Nothing where no sweep was called inside the window (a cell whose
driver does not sweep)."""

from __future__ import annotations

import statistics


def read(params: dict, obs: dict):
    t_open, t_end = obs["window"]
    sweeps = [s for s in obs.get("sweeps", ())
              if t_open <= s["t_start"] <= t_end]
    if not sweeps:
        return None
    q = params["quantity"]
    if q == "wall_ms":
        return 1e3 * statistics.median(s["t_end"] - s["t_start"]
                                       for s in sweeps)
    raise ValueError(f"sweep_log reader: unknown quantity {q!r}")
