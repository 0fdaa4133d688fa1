"""Round-phase names, wall-clock phase timers, and device trace scopes.

Phase timing is the observability Path ORAM work actually runs on
(Palermo, arXiv:2411.05400, breaks rounds down by phase), and it is safe
here *only* at batch granularity: every phase covers the whole
fixed-size round, so its duration is a function of (capacity, batch
size), never of which ops or whose ops are inside (the timing leakage
stance of testing/leakcheck.py:timing_twosample_z).

Host-side phases (histograms + ``jax.profiler`` annotations):

- ``assembly``  — scheduler collection window (server/scheduler.py)
- ``verify``    — batched sr25519 signature verification
- ``dispatch``  — host pack + device round enqueue (engine/batcher.py)
- ``evict``     — device round completion wait: the ORAM fetch / apply /
                  evict / write-back program measured from the host
                  (per-stage device splits are in the profiler trace via
                  the ``jax.named_scope`` annotations, not in metrics —
                  the host cannot time inside one XLA program)
- ``demux``     — device→wire response unpacking
- ``sweep``     — expiry sweep (engine/expiry.py)
- ``journal``   — sealed batch-journal append + fsync (engine/journal.py)
- ``checkpoint``— sealed whole-state checkpoint write (engine/checkpoint.py)
- ``replay``    — startup journal replay (recovery; engine/batcher.py)
- ``sort``      — the round's bounded-key sort workload, measured by
                  calibration (GrapevineEngine.calibrate_sort_phase):
                  the host cannot time inside the fused round program,
                  but every sort in the round is shape-static and
                  data-independent (oblivious), so a standalone run of
                  the SAME jitted sort program at the round's geometry
                  IS the per-round sort cost — /metrics separates it
                  from the rest of the ``evict`` phase without touching
                  the hot path. Labelled batch-level by construction
                  (geometry only, never request data).
- ``posmap``    — per-round position-resolution cost, measured the same
                  calibration way (GrapevineEngine.calibrate_posmap_phase
                  runs the round's exact lookup_and_remap workload —
                  all three ORAM rounds' batch lookups — standalone at
                  the round geometry): under a recursive position map
                  (oram/posmap.py) this is the internal ORAM's rounds,
                  under a flat one the private gather/scatter pair, so
                  /trace and the flight recorder attribute position
                  handling separately from ``oram_evict``. Also a
                  device_phase scope inside the jit'd round for TPU
                  profiler captures.

Device-side scopes (``device_phase``): named_scope annotations compiled
into the jit'd round so TPU profiler captures (profile_tpu.py, the
live ``/profile`` endpoint) attribute HLO time to
fetch/apply/evict/writeback per tree.
"""

from __future__ import annotations

import contextlib
import time

#: canonical phase label values — the registry declares exactly these,
#: so a typo'd phase name raises instead of minting a new series
PHASES = ("assembly", "verify", "dispatch", "evict", "demux", "sweep",
          "journal", "checkpoint", "replay", "sort", "posmap", "flush")

#: fixed histogram boundaries for phase durations (seconds). Spans the
#: measured range: ~100 µs host phases at B=8 up to multi-second expiry
#: sweeps at 2^24 capacity (PERF.md / BIGRUN_r4.md).
PHASE_BUCKETS = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
    0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
)

#: fixed boundaries for stash occupancy samples (entries; geometry-
#: independent absolutes — stash_size is 96 by default, configurable)
STASH_BUCKETS = (0.0, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 48.0, 64.0, 96.0, 128.0)


@contextlib.contextmanager
def phase_timer(histogram, phase: str, annotate: bool = True):
    """Time a host-side phase into ``histogram{phase=...}``.

    Also emits a ``jax.profiler.TraceAnnotation`` so host phases line up
    with device HLO spans in a TPU profiler capture. The annotation is a
    TraceMe — nanoseconds when no trace is active — and is batch-level
    by construction (the name is the static phase, never request data).
    """
    ann = None
    if annotate:
        try:
            import jax.profiler

            ann = jax.profiler.TraceAnnotation(f"grapevine/{phase}")
            ann.__enter__()
        except Exception:  # profiler unavailable: timing still works
            ann = None
    t0 = time.perf_counter()
    try:
        yield
    finally:
        dt = time.perf_counter() - t0
        if ann is not None:
            ann.__exit__(None, None, None)
        if histogram is not None:
            histogram.observe(dt, phase=phase)


def device_phase(name: str):
    """``jax.named_scope`` wrapper for phases *inside* jit'd programs.

    Pure trace-time metadata: names the HLO ops so profiler captures
    attribute device time per ORAM stage; compiles to nothing.
    """
    import jax

    return jax.named_scope(f"grapevine/{name}")
