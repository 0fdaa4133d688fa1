"""Per-layer metrics from what the client children recorded per op
(``[due, sent, answered, digest, error, handed]``, ``time.monotonic``
seconds).
``params``: ``quantity`` is ``late_ms`` (send time minus due time,
reduced by ``reduce``: ``p99`` or ``median``) or ``service_ms`` (median
client latency from send time minus the median enqueue -> settle seconds
of the ops the scheduler saw in the window: a difference of medians, no
pairing of ops)."""

from __future__ import annotations

import statistics

from ..lib.stats import percentile


def read(params: dict, obs: dict):
    records = obs["observed"].get("records")
    if not records:
        return None
    q = params["quantity"]
    if q == "late_ms":
        late = [(r[1] - r[0]) * 1e3 for r in records]
        if params.get("reduce", "p99") == "median":
            return statistics.median(late)
        return percentile(late, 99.0)
    if q == "service_ms":
        t0, t1 = obs["window"]
        waits = [dt for t, dt in obs["submit_waits"] if t0 <= t <= t1]
        if not waits:
            return None
        client = statistics.median(r[2] - r[1] for r in records)
        return (client - statistics.median(waits)) * 1e3
    raise ValueError(f"client_latency reader: unknown quantity {q!r}")
