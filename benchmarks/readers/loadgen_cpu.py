"""Per-layer metrics from the CPU seconds of the load generator's own
thread, which shares the collector's interpreter lock: an in-process
driver stamps ``time.thread_time()`` (CPU time of the calling thread: it
does not count waiting for the lock or for a round) per wave, and hands
``observed["loadgen_cpu"]`` = ``[[s inside the program's submit_nowait,
s of everything else], ...]``, one pair per wave of the window.
``params``: ``quantity`` is ``submit_ms`` (the program's ingress: a
later PR may shorten it) or ``own_ms`` (request building, learning the
answers' ids, the log and its collections: the benchmark's own, which
no PR to the program can shorten). The mean over the waves, one wave
per round, in ms: the thread clock ticks in steps of 10 ms on the
machines with the chip (a wave reads 0, 10 or 20 ms; my chip run, PR
27), so only the sum over a window's ~200 waves says anything, and a
median would be a multiple of the tick. Nothing where the driver takes
no such stamps (a cell loaded from client processes)."""

from __future__ import annotations

import statistics

_COLUMN = {"submit_ms": 0, "own_ms": 1}


def read(params: dict, obs: dict):
    waves = obs["observed"].get("loadgen_cpu")
    if not waves:
        return None
    q = params["quantity"]
    if q not in _COLUMN:
        raise ValueError(f"loadgen_cpu reader: unknown quantity {q!r}")
    return 1e3 * statistics.fmean(w[_COLUMN[q]] for w in waves)
