"""Observers at the program's two boundaries the benchmark reads.

``RoundLog`` wraps the engine's ``handle_queries_async`` (the
scheduler -> engine boundary, as ``chip_smoke.py``'s RoundLog does):
every round's requests in slot order, its clock, its responses and the
host-clock times of dispatch and of the resolved answers. Round
composition is decided by the scheduler, not by the seed, so this is
what the oracle replay needs. Dispatch and resolve run inside
benchmark-owned ``TraceAnnotation`` spans, which put the host's side of
each round on the profiler's clock.

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
            if self._log.on_resolved is not None:
                self._log.on_resolved(e)
            return resps

    def __init__(self, engine):
        #: rounds in the engine's order: kind, reqs, now, resps,
        #: t_dispatch, t_resolved (perf_counter seconds)
        self.entries: list[dict] = []
        #: called on the collector thread with each resolved entry
        self.on_resolved = None
        inner = engine.handle_queries_async

        def recorded(reqs, now):
            entry = {"kind": "round", "reqs": list(reqs), "now": int(now),
                     "resps": None, "t_dispatch": time.perf_counter(),
                     "t_resolved": None}
            with annotation("bench/dispatch"):
                pending = inner(reqs, now)
            self.entries.append(entry)
            return self._Pending(pending, entry, self)

        engine.handle_queries_async = recorded


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
