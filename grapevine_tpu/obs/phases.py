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
- ``dispatch``  — the round's lock section in ``handle_queries_async``:
                  journal append, the jitted round's enqueue, and the
                  checkpoint when one falls due (the host pack is its
                  own span, ``pack``, before the lock)
- ``evict``     — device round completion wait: the ORAM fetch / apply /
                  evict / write-back program measured from the host
                  (per-stage device splits come from a profiler capture
                  reduced by the ``DEVICE_SCOPES`` below, not from
                  metrics — the host cannot time inside one XLA program)
- ``demux``     — device→wire response unpacking
- ``sweep``     — expiry sweep (engine/expiry.py): the device's pass,
                  enqueue to ready; before it in ``expire``, on the
                  caller's thread: ``sweep_lock`` (``expire`` called to
                  the engine's lock held: the rounds and the checkpoint
                  ahead of it) and ``sweep_journal`` (the sweep's journal
                  frame sealed, written and fsynced; 0 with no state
                  directory)
- ``journal``   — sealed batch-journal append + fsync (engine/journal.py)
- ``checkpoint``— sealed whole-state checkpoint write (engine/checkpoint.py)
                  and, inside it on the same thread, its three parts:
                  ``checkpoint_read`` (a block, device to host),
                  ``checkpoint_seal`` (staged and encrypted) and
                  ``checkpoint_write`` (the waits for the file thread's
                  MAC + write, then fsync, rename, directory fsync)
- ``replay``    — startup journal replay (recovery; engine/batcher.py)

The collector thread's further spans (``SPAN_NAMES``; no histogram, the
round ledger and the capture only): ``hold``, ``stage``, ``pack``,
``verify_prep`` / ``verify_native`` inside ``verify``, ``observe``,
``release``, ``settle`` and ``cycle``, one pass of the collector that
holds all the others. Every one of them is taken by :func:`span`, the
one primitive: annotation, wall clock, histogram and ledger entry in one
enter and one leave (and the thread's CPU clock for the cycle, its waits
and its native call: ``CPU_SPANS``), with each span's *own* time (its
wall less its children's) summed per name into the open cycle, so the collector's
timeline is a partition and ``cycle_counts`` can say how much of a cycle
waited by design, how much worked, how much of the work waited for the
GIL or a lock, and how much no span names. (Where the kernel accounts
a thread's CPU time by the timer tick — 10 ms on the chip's hosts — one
cycle's CPU reads a multiple of the tick: read CPU and blocked time as
means over rounds, not round by round.)

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
import sys
import threading
import time

from .registry import TelemetryLeakError

#: canonical phase label values — the registry declares exactly these,
#: so a typo'd phase name raises instead of minting a new series
PHASES = ("assembly", "verify", "dispatch", "evict", "demux", "sweep",
          "journal", "checkpoint", "replay",
          "checkpoint_read", "checkpoint_seal", "checkpoint_write",
          "sweep_lock", "sweep_journal")

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
)

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


#: the collector thread's spans of one round, in the order a round meets
#: them (obs/tracer.py keeps exactly these in every ledger and says what
#: each covers)
ROUND_SPANS = (
    "cycle", "assembly", "hold", "verify", "verify_prep", "verify_native",
    "stage", "pack", "dispatch", "journal", "checkpoint", "evict", "demux",
    "observe", "settle", "release",
)

#: every name :func:`span` takes: a round's spans and the histogram
#: phases outside a round (``sweep``, ``replay``). A span is a phase of
#: a fixed-shape round, never an op: any other name raises
#: ``TelemetryLeakError``
SPAN_NAMES = frozenset(PHASES) | frozenset(ROUND_SPANS)

#: every name :func:`trace_span` takes, annotations outside any round:
#: start-up's ``state_init``, the engine tier's ``ingress`` (a handler
#: thread's), ``asleep``, the collector with nothing queued and
#: nothing in flight, between two cycles, and ``leakmon``, one round
#: audited on the leak monitor's own thread (obs/leakmon.py)
ANNOTATION_NAMES = frozenset(("state_init", "ingress", "asleep", "leakmon"))

#: the collector thread's OS name (15 bytes): what ``top -H`` shows, and
#: the name of its line in a profiler capture, by which a reader tells
#: the collector's annotations from other threads' (every other thread
#: of the process is ``python3``)
COLLECTOR_THREAD = "gv-collector"

#: collector states that wait by design: for arrivals (``assembly``),
#: for the round in flight (``hold``), for the device (``evict``)
WAIT_SPANS = frozenset(("assembly", "hold", "evict"))

#: the foreign call of a round's signature check: no GIL held, k
#: threads; neither waiting nor this thread's Python
NATIVE_SPANS = frozenset(("verify_native",))

#: the spans that read the thread's CPU clock: the cycle, and inside it
#: the states that are not the collector's own Python. What is left of
#: the cycle's CPU is the working states', which is all ``cycle_counts``
#: asks; a read is a system call (``CLOCK_THREAD_CPUTIME_ID`` has no
#: vDSO path), and with a pair in every span the serial host path of a
#: round that runs alone grew by 0.7 ms (PERF.md §6, PR 39)
CPU_SPANS = WAIT_SPANS | NATIVE_SPANS | {"cycle"}

_tls = threading.local()
_annotation_cls = None


def _annotation(name: str):
    """An entered ``jax.profiler.TraceAnnotation`` (a TraceMe:
    nanoseconds when no trace is active), or None where there is no
    profiler to annotate for: a process that has not imported jax (a
    frontend, a hostpipe worker) is never made to."""
    global _annotation_cls
    if _annotation_cls is None:
        if "jax" not in sys.modules:
            return None
        try:
            from jax.profiler import TraceAnnotation as _annotation_cls
        except Exception:  # profiler unavailable: timing still works
            _annotation_cls = False
    if not _annotation_cls:
        return None
    ann = _annotation_cls(name)
    ann.__enter__()
    return ann


class Span:
    """One open span of the calling thread (:func:`span`). After
    ``end()``: ``start`` (perf_counter) and ``wall`` seconds, ``cpu``
    seconds (``time.thread_time``) for a ``CPU_SPANS`` name, and for a
    ``cycle`` the own wall of everything that ran inside it, by name
    (``own``)."""

    __slots__ = ("name", "ledger", "histogram", "parent", "start", "wall",
                 "cpu", "native_s", "own", "gil_s", "_cycle", "_c0",
                 "_kids_wall", "_idle_cpu", "_ann")

    def __init__(self, name: str, ledger, histogram):
        self.name, self.ledger, self.histogram = name, ledger, histogram
        #: set inside a ``NATIVE_SPANS`` span to the foreign call's own
        #: elapsed seconds: the span's wall less this is the wait to get
        #: the GIL back, and counts as blocked
        self.native_s = None
        self.own = {} if name == "cycle" else None
        self.cpu = None
        #: in a cycle: the wait for the GIL after its native calls, and
        #: the CPU read over its waiting states and native calls
        self.gil_s = self._idle_cpu = 0.0
        self._kids_wall = 0.0

    def begin(self) -> "Span":
        self.parent = parent = getattr(_tls, "top", None)
        self._cycle = None
        if parent is not None:
            self._cycle = parent if parent.own is not None else parent._cycle
            if self.ledger is None:
                # a child with no ledger of its own writes where its
                # parent does (verify_prep and verify_native, entered
                # deep under the scheduler's verify)
                self.ledger = parent.ledger
        _tls.top = self
        self._ann = _annotation(f"grapevine/{self.name}")
        self.start = time.perf_counter()
        if self.name in CPU_SPANS:
            self._c0 = time.thread_time()
        return self

    def end(self) -> "Span":
        name = self.name
        if name in CPU_SPANS:
            self.cpu = time.thread_time() - self._c0
        wall = self.wall = time.perf_counter() - self.start
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        parent = _tls.top = self.parent
        own_wall = wall - self._kids_wall
        if parent is not None:
            parent._kids_wall += wall
        cycle = self._cycle
        if cycle is not None:
            # the span's own time, by name, in the cycle it ran in
            cycle.own[name] = cycle.own.get(name, 0.0) + own_wall
            if self.cpu is not None:
                cycle._idle_cpu += self.cpu
            if self.native_s is not None:
                cycle.gil_s += max(0.0, own_wall - self.native_s)
        if self.histogram is not None and name in PHASES:
            self.histogram.observe(wall, phase=name)
        ledger = self.ledger
        if ledger is not None:
            seen = ledger.get(name)
            # a name entered twice for one round (stage; a failing
            # round's second native call) reads as one span from the
            # first start, its durations added
            ledger[name] = ((self.start, wall) if seen is None
                            else (seen[0], seen[1] + wall))
        return self

    __enter__ = begin

    def __exit__(self, *exc) -> None:
        self.end()

    def cycle_counts(self) -> dict:
        """A closed ``cycle``'s counts (obs/tracer.py ROUND_COUNTS):
        its wall is ``cycle_wait_s`` + the working states' wall +
        ``cycle_unspanned_s``, and the working wall is their CPU +
        ``cycle_blocked_s`` + the native crossing's own time
        (``verify_native`` less ``cycle_native_wait_s``, the part of
        ``cycle_blocked_s`` spent getting the GIL back after it). The
        working states' CPU is the cycle's less what was read over its
        waits and native calls (the few instructions between two spans
        count as theirs)."""
        wait = working = 0.0
        for name, own_wall in self.own.items():
            if name in WAIT_SPANS:
                wait += own_wall
            elif name not in NATIVE_SPANS:
                working += own_wall
        return {
            "cycle_wait_s": wait,
            "cycle_cpu_s": max(0.0, self.cpu),
            "cycle_blocked_s": max(
                0.0, working - (self.cpu - self._idle_cpu) + self.gil_s),
            "cycle_native_wait_s": self.gil_s,
            "cycle_unspanned_s": max(0.0, self.wall - self._kids_wall),
        }


def span(name: str, ledger: dict | None = None, histogram=None) -> Span:
    """The one span primitive of the host side: ``with span(name,
    ledger, histogram):`` does, once, the ``grapevine/<name>``
    ``TraceAnnotation`` (so the span lines up with device ops in a
    capture), the ``perf_counter`` pair, the ``time.thread_time`` pair
    for a ``CPU_SPANS`` name, the ``histogram{phase=name}`` observation where the name is a
    ``PHASES`` member, and the ``ledger[name] = (start_s, dur_s)``
    entry of the round's span ledger. Spans of one thread nest: each
    knows its parent, and its own time is its wall less its children's,
    so inside an open ``cycle`` every instant belongs to the innermost
    open span. ``begin()`` / ``end()`` are the with-block's two halves,
    for a span that does not fit one (``cycle``, ``assembly``)."""
    if name not in SPAN_NAMES:
        raise TelemetryLeakError(
            f"span: {name!r} is not a round phase (allowed: "
            f"{sorted(SPAN_NAMES)}) — a span is a phase of a fixed-shape "
            "round, never an operation"
        )
    return Span(name, ledger, histogram)


def reset_thread_spans() -> None:
    """Forget the calling thread's open spans: a collector revived
    after a crash starts its timeline empty."""
    _tls.top = None


def name_thread(name: str) -> None:
    """Give the calling thread its OS name (Linux ``PR_SET_NAME``; a
    no-op where there is none). The profiler reads it at the thread's
    first annotation, so it is set before any."""
    try:
        import ctypes

        ctypes.CDLL(None).prctl(15, name.encode()[:15], 0, 0, 0)
    except (OSError, AttributeError):  # not Linux: threads stay unnamed
        pass


@contextlib.contextmanager
def trace_span(name: str):
    """Only the ``grapevine/<name>`` profiler annotation, for a host
    span outside the round (``ANNOTATION_NAMES``): nothing is timed or
    recorded."""
    if name not in ANNOTATION_NAMES:
        raise TelemetryLeakError(
            f"trace_span: {name!r} is not an annotation of the host side "
            f"(allowed: {sorted(ANNOTATION_NAMES)})"
        )
    ann = _annotation(f"grapevine/{name}")
    try:
        yield
    finally:
        if ann is not None:
            ann.__exit__(None, None, None)


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
