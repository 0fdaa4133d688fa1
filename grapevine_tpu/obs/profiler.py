"""Programmatic ``jax.profiler`` capture for a live engine.

The TPU profiler is the only instrument that can split device time
inside the fused round program (the host phase timers stop at the
``evict`` wait; the ``jax.named_scope`` annotations compiled into the
round only become visible in a profiler capture). This module makes a
capture a runtime operation of the serving process: ``/profile?ms=N``
(obs/httpd.py) starts a ``jax.profiler`` trace on the live process,
sleeps N milliseconds while the engine keeps serving, stops the trace,
and returns the capture directory. Load the result in Perfetto /
TensorBoard next to ``/trace``'s round spans.

Gated and bounded by design: the endpoint exists only when the operator
passed ``--profile-enable`` (a capture costs real overhead and writes
device-level traces to disk — not something an exposed scrape port
should trigger), one capture runs at a time (a second request gets 409
rather than corrupting the active session), and the duration is clamped
to ``max_ms``.

Leak stance: the profiler records *phase-level* annotations
(``grapevine/<phase>`` TraceAnnotations and the ``DEVICE_SCOPES``
named_scopes — obs/phases.py) and XLA op timings, all functions of
(capacity, batch size); request payloads and identities never enter
trace metadata. The Python tracer is off: a capture holds the program's
own spans and the device's ops, not every Python call. The capture
directory itself stays operator-local — the endpoint returns its path,
never its contents.
"""

from __future__ import annotations

import os
import threading
import time


class ProfilerBusy(RuntimeError):
    """A capture is already in progress (one at a time by design)."""


class ProfilerGate:
    """Serialized, duration-clamped ``jax.profiler`` capture trigger."""

    def __init__(self, outdir: str | None = None, max_ms: int = 60_000):
        import tempfile

        self.outdir = outdir or os.path.join(
            tempfile.gettempdir(), f"grapevine-profile-{os.getpid()}"
        )
        self.max_ms = max_ms
        self._lock = threading.Lock()
        self._n = 0

    def capture(self, ms: int = 1000) -> dict:
        """Run one profiler capture of ``ms`` milliseconds (clamped to
        [1, max_ms]); returns ``{"trace_dir", "ms"}``. Raises
        :class:`ProfilerBusy` if a capture is already running."""
        ms = max(1, min(int(ms), self.max_ms))
        if not self._lock.acquire(blocking=False):
            raise ProfilerBusy(
                "a profiler capture is already in progress; retry when "
                "it completes"
            )
        try:
            import jax.profiler

            self._n += 1
            trace_dir = os.path.join(self.outdir, f"capture-{self._n:04d}")
            os.makedirs(trace_dir, exist_ok=True)
            # Python tracer off, as the benchmark's captures are: with
            # it on, 4 s of the served bus is 1.5 M events and slows
            # the rounds being captured (PERF.md section 4). The
            # program's own host spans are TraceAnnotations and stay.
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            try:
                time.sleep(ms / 1e3)
            finally:
                jax.profiler.stop_trace()
            return {"trace_dir": trace_dir, "ms": ms}
        finally:
            self._lock.release()
