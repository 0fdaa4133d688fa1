"""Observers at the program's two boundaries the benchmark reads.

``RoundLog`` wraps the engine's ``handle_queries_async`` (the
scheduler -> engine boundary, as ``chip_smoke.py``'s RoundLog does):
every round's requests in slot order, its clock, its responses and the
host-clock times of dispatch and of the resolved answers. Round
composition is decided by the scheduler, not by the seed, so this is
what the oracle replay needs. It wraps ``expire`` too: an expiry sweep
is an event of the log like a round (``kind`` ``"sweep"``), in the order
the engine ran it among the rounds. Dispatch, resolve and sweep run
inside benchmark-owned ``TraceAnnotation`` spans, which put the host's
side of each on the profiler's clock.

The order is exact by construction. The engine applies rounds and sweeps
one at a time, each under its own lock, in the order they get it; a
round's dispatch is called from the collector thread and a sweep from
another. The log has one lock of its own, held around each wrapped call
*and* the append of its entry, so whichever call gets the log's lock
first also gets the engine's first (the engine's nests inside, and
nothing else calls either method) and is appended first. An entry
appended after the call returned, outside any lock, can land behind the
round that was dispatched the moment the engine's lock came free. A
dispatch that meets a running sweep waits at the log's lock for what it
would have waited at the engine's; what it did before that wait without
the log, packing its batch (``pack_ms``), it now does after it.

``SubmitLog`` wraps the scheduler's ``submit_nowait`` and records each
op's enqueue -> settle seconds (the part of a client's latency that is
not gRPC, session AEAD or codec).
"""

from __future__ import annotations

import threading
import time


def annotation(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


class RoundLog:
    class _Pending:
        def __init__(self, inner, entry, log):
            self._inner, self._entry, self._log = inner, entry, log

        def __getattr__(self, name):
            return getattr(self._inner, name)

        def resolve(self):
            with annotation("bench/resolve"):
                resps = self._inner.resolve()
            e = self._entry
            e["t_resolved"] = time.perf_counter()
            e["resps"] = resps
            for watch in self._log.watchers:
                watch(e)
            if self._log.on_resolved is not None:
                self._log.on_resolved(e)
            return resps

    def __init__(self, engine):
        #: rounds and sweeps in the engine's order (perf_counter seconds).
        #: A round: kind "round", reqs, now, resps, t_dispatch,
        #: t_resolved. A sweep: kind "sweep", now, period (as the engine
        #: resolved it), evicted, t_start (expire called), t_end (returned)
        self.entries: list[dict] = []
        #: called on the collector thread with each resolved round: the
        #: driver's loop, and before it whatever else a driver hangs here
        self.on_resolved = None
        self.watchers: list = []
        self._order = threading.Lock()
        dispatch, expire = engine.handle_queries_async, engine.expire

        def recorded(reqs, now):
            entry = {"kind": "round", "reqs": list(reqs), "now": int(now),
                     "resps": None, "t_dispatch": time.perf_counter(),
                     "t_resolved": None}
            with self._order:
                with annotation("bench/dispatch"):
                    pending = dispatch(reqs, now)
                self.entries.append(entry)
            return self._Pending(pending, entry, self)

        def swept(now, period=None):
            entry = {"kind": "sweep", "now": int(now),
                     "period": int(engine.config.expiry_period
                                   if period is None else period),
                     "evicted": None, "t_start": time.perf_counter(),
                     "t_end": None}
            with self._order:
                with annotation("bench/sweep"):
                    entry["evicted"] = int(expire(now, period))
                entry["t_end"] = time.perf_counter()
                self.entries.append(entry)
            return entry["evicted"]

        engine.handle_queries_async = recorded
        engine.expire = swept

    def rounds(self, since: int = 0) -> list[dict]:
        """The rounds among ``entries[since:]``."""
        return [e for e in self.entries[since:] if e["kind"] == "round"]

    def sweeps(self, since: int = 0) -> list[dict]:
        """The sweeps among ``entries[since:]``."""
        return [e for e in self.entries[since:] if e["kind"] == "sweep"]


class SubmitLog:
    def __init__(self, scheduler):
        #: enqueue -> settle seconds of every settled op, with the
        #: perf_counter time it was enqueued at
        self.waits: list[tuple[float, float]] = []
        self._pending: set = set()
        self._lock = threading.Lock()
        inner = scheduler.submit_nowait

        def recorded(req, auth=None):
            t0 = time.perf_counter()
            fut = inner(req, auth)
            with self._lock:
                self._pending.add(fut)
            fut.add_done_callback(
                lambda f: self._note(f, t0, time.perf_counter() - t0))
            return fut

        scheduler.submit_nowait = recorded

    def _note(self, fut, t0, dt):
        with self._lock:
            self._pending.discard(fut)
            self.waits.append((t0, dt))

    def fail_pending(self) -> int:
        """An op whose round lost its answer would block its gRPC
        handler thread for ever, and the process with it at exit: give
        every op still unanswered an error. Returns how many."""
        with self._lock:
            stuck = [f for f in self._pending if not f.done()]
        for fut in stuck:
            fut.set_exception(RuntimeError("benchmark: never answered"))
        return len(stuck)
