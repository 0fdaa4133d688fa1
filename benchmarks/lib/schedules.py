"""Seeded open-loop arrival schedules.

The arithmetic of ``grapevine_tpu/load/generators.py`` ``bursty_onoff``
with two changes the benchmark needs. The OFF phase has a rate of its
own. And every phase holds a *fixed* number of arrivals (its rate times
its length, rounded) placed as sorted uniforms: a Poisson count would
make the seed change the load. The instants themselves come from
``--seed``, so where arrivals fall against the round cadence differs
from run to run as it does between users, and the spread that the
latency bounds are set from holds that in.
"""

from __future__ import annotations

import hashlib

import numpy as np


def onoff_schedule(mean_rate: float, on_factor: float, off_factor: float,
                   duty: float, period_s: float, duration_s: float,
                   seed: int) -> dict:
    """Arrival offsets (seconds from the window's start, sorted) and one
    uniform draw per arrival that the client maps onto the op mix."""
    if not 0.0 < duty < 1.0:
        raise ValueError("duty must be in (0, 1)")
    rng = np.random.default_rng(seed)
    parts = []
    carry = 0.0
    t0 = 0.0
    while t0 < duration_s:
        for factor, share in ((on_factor, duty), (off_factor, 1.0 - duty)):
            t1 = min(t0 + share * period_s, duration_s)
            want = mean_rate * factor * (t1 - t0) + carry
            n = int(want)
            carry = want - n  # fractions roll into the next phase
            parts.append(np.sort(rng.uniform(t0, t1, n)))
            t0 = t1
            if t0 >= duration_s:
                break
    t = np.concatenate(parts) if parts else np.empty(0, np.float64)
    return {"t_s": t, "u": rng.random(len(t))}


def fingerprint(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()
