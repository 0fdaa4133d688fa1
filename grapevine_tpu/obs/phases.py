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
                  (per-stage device splits come from a profiler capture
                  reduced by the ``DEVICE_SCOPES`` below, not from
                  metrics — the host cannot time inside one XLA program)
- ``demux``     — device→wire response unpacking
- ``sweep``     — expiry sweep (engine/expiry.py)
- ``journal``   — sealed batch-journal append + fsync (engine/journal.py)
- ``checkpoint``— sealed whole-state checkpoint write (engine/checkpoint.py)
- ``replay``    — startup journal replay (recovery; engine/batcher.py)

Device-side scopes (``device_phase``): ``jax.named_scope`` annotations
compiled into the jit'd programs, so a profiler capture (the benchmark's
``--trace 1`` run, the live ``/profile`` endpoint, profile_tpu.py)
carries for every device op the path of scopes it was traced under
(``.../grapevine/round_a_mailbox/grapevine/oram_fetch/grapevine/
cipher_decrypt/...``). ``DEVICE_SCOPES`` is the fixed list; every op of
the round program sits under at least one of them
(tests/test_device_scopes.py), and ``benchmarks/lib/xplane_scopes.py``
reduces a capture by them. Scopes nest: a round scope holds the four
stage scopes, a stage scope holds leaf scopes.
"""

from __future__ import annotations

import contextlib
import time

#: canonical phase label values — the registry declares exactly these,
#: so a typo'd phase name raises instead of minting a new series
PHASES = ("assembly", "verify", "dispatch", "evict", "demux", "sweep",
          "journal", "checkpoint", "replay")

#: canonical device scope names — ``device_phase`` refuses any other, so
#: a typo'd or per-op scope name raises at trace time instead of minting
#: a path no reader knows (the ``PHASES`` stance, for the device side)
DEVICE_SCOPES = (
    # the round program, top level (engine/round_step.py)
    "request_unpack",      # batch columns -> masks, keys, bucket hashes,
                           # allocation candidates, round B's index
    "round_a_mailbox", "round_b_records", "round_c_mailbox",
    "freelist_counters",   # free_top / recipients / seq / freelist push
    "respond",             # response assembly (engine/responses.py)
    "transcript",          # the public leaf transcript's assembly
    # the four stages of one tree round (oram/round.py, path_oram.py)
    "oram_fetch", "oram_apply", "oram_evict", "oram_writeback",
    # leaves of oram_fetch
    "dedup",               # first/last-occurrence masks
    "posmap",              # position lookup + remap (oram/posmap.py)
    "path_index",          # leaf -> heap bucket ids, owner map
    "path_gather",         # tree rows -> path working set
    "psum_assembly",       # the mesh's all-reduce of the gathered rows
    "cipher_decrypt",
    "cache_read",          # tree-top cache planes -> working set
    # leaves of oram_evict
    "oram_evict_sort",     # the working set's sort by leaf
    "stash_compact",       # leftover rows -> stash
    # leaves of oram_writeback
    "cipher_encrypt",
    "path_scatter",        # path working set -> tree rows
    "cache_write",         # working set -> tree-top cache planes
    # other programs
    "sweep_records", "sweep_mailbox",
    # bounded-key sorts (oblivious/radix.py), one scope per digit pass
    "radix_rank", "radix_group_sort",
) + tuple(f"radix_pass_s{shift}" for shift in range(64))

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


def trace_span(span: str):
    """Only the ``grapevine/<span>`` profiler annotation, for a ledger
    span whose duration the caller takes from its own stamps (the
    scheduler's ``assembly`` and ``settle``): the round ledger and a
    capture then show the same spans, on the profiler's clock."""
    return phase_timer(None, span)


def device_phase(name: str):
    """``jax.named_scope`` wrapper for phases *inside* jit'd programs.

    Pure trace-time metadata: names the HLO ops so profiler captures
    attribute device time per ORAM stage; compiles to nothing.
    """
    import jax

    if name not in DEVICE_SCOPES:
        raise ValueError(
            f"device_phase: {name!r} is not a device scope (see "
            "obs/phases.py DEVICE_SCOPES) — a scope is a stage of the "
            "round program, never an operation"
        )
    return jax.named_scope(f"grapevine/{name}")
