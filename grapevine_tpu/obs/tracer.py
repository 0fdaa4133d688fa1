"""Round-trace profiler: a fixed ring of per-round span ledgers.

The flight recorder (obs/flightrec.py) answers *what* the engine was
doing (fill, detector stats); this module answers *where the time went*
— the question that sizes ROADMAP items 1-2 (tree-top caching, pipelined
rounds) before anyone builds them. Each committed round contributes one
span ledger assembled from the spans the host side takes with its one
primitive (obs/phases.py ``span``: assembly, verify with ``verify_prep``
and ``verify_native`` inside it, stage, pack, dispatch with journal and
checkpoint inside it, evict, demux, observe, settle, release, and
``cycle``, the
collector's whole pass that holds all of them), the scheduler's queue
wait and hold, and the two device
windows: ``device``, the round's own device time as the host can know
it, and ``inflight``, dispatch to observed ready with the rounds queued
ahead of it), plus a handful of per-round and per-cycle counts, kept in
a fixed ring like
the flight recorder and exported two ways:

- ``chrome_trace()`` — Chrome trace-event JSON (the ``/trace`` endpoint,
  obs/httpd.py), loadable directly in Perfetto / chrome://tracing, with
  host spans, the device windows and the queue wait on separate tracks
  so the host/device overlap is visible per round. Rounds alternate
  between two lanes per track (tid = lane): the pipelined scheduler
  keeps up to two rounds in flight, and the trace-event format requires
  complete (``X``) events on one tid to nest or stay disjoint —
  consecutive overlapping rounds on a single track would misrender.
  The ``grapevine/round`` event carries the round's counts as ``args``;
- ``grapevine_round_bubble_ratio`` — a derived gauge: the windowed mean
  fraction of each round's wall clock the host spends *blocked* on the
  device (the ``evict`` wait over the whole round span). This is the
  number that sizes the pipelined-round refactor (Palermo,
  arXiv:2411.05400, motivates protocol/hardware pipelining from exactly
  this phase-overlap accounting). It reads the ``evict`` and ``round``
  spans, not ``device``: at pipeline depth 1 read it as the host/device
  balance ``b`` below; at depth 2 a round's span runs from its
  collection window to its answers, two rounds later, so ``b`` reads
  about a third of what a serial round would show, and the balance is
  ``device`` (the round's own device time, which tiles the wall clock
  when the device sets the pace) against ``cycle`` less ``cycle_wait_s``
  (the collector's pass less its waiting; OPERATIONS.md §12). With one
  device,
  double-buffered rounds take
  ``max(host, device)`` instead of today's ``host + device``, so the
  steady-state speedup is ``1 / max(b, 1-b)`` — maximal (≈2×) at
  ``b ≈ 0.5``, and ≈1× at *both* extremes: near 0 the host path is the
  bottleneck (scale frontends / host pipeline instead), near 1 the
  round is device-bound and there is no second device to overlap with
  (attack the device round itself — tree-top caching, ROADMAP item 1).

Leak stance — the PR-1/2 contract, enforced structurally: a span is a
*phase*, never an operation. ``record_round()`` validates every ledger
against the fixed span-name allowlist (obs/phases.py ``SPAN_NAMES``:
the canonical phases and the collector's further spans; plus the derived
``device``/``inflight``/``queue``/``round`` windows)
and rejects anything else with :class:`TelemetryLeakError`; a span
value is exactly a ``(start, duration)`` pair of floats. A count is one
of :data:`ROUND_COUNTS` and a sum over the whole round (how many ops,
their queue wait added up, how many rounds were dispatched ahead),
checked the same way. There is no field in which an op type, a client
identity, or a per-op timestamp *could* travel — every span covers the
whole fixed-size round, so its timing is a function of (capacity, batch
size), never of the ops inside (obs/phases.py).

Shape stability: every recorded ledger is normalized to carry exactly
:data:`STABLE_SPANS` — configurations without durability contribute
zero-duration ``journal``/``checkpoint`` spans rather than omitting
them, so trace consumers (and the A/B tooling diffing two configs) see
the same JSON shape everywhere.

Timestamps are ``time.perf_counter`` seconds (one clock domain across
the scheduler and batcher call sites); the Chrome export converts to
microseconds as the trace-event format requires.

Span pairing: collector-side spans (assembly/verify/stage/queue) are
stamped onto the round's own handle (engine/batcher.py
PendingRound.note_span), so a ledger always describes exactly one round
even under the pipelined scheduler — there is no cross-round staging
here. ``observe``, ``settle`` and ``release`` end after ``resolve()``
has recorded the ledger, and at depth 1 so does the ``cycle`` the round was
dispatched in, with its counts: they are added to the recorded round by
its ``seq`` (:meth:`RoundTracer.amend_round`): one ledger per round,
still. A ledger's spans are the ROUND's; a cycle's counts are sums over
the collector's pass that dispatched the round, in which an older
round's evict, demux, observe and settle ran.

Thread-safety: one lock around the ring; ``record_round()`` and
``amend_round()`` run on the collector thread (PendingRound.resolve,
BatchScheduler._settle), ``chrome_trace()`` on the metrics scrape
thread.
"""

from __future__ import annotations

import json
import math
import threading

from .phases import PHASES, ROUND_SPANS
from .registry import TelemetryLeakError, TelemetryRegistry

#: spans assembled on the host side of every round, in the order a
#: round meets them (obs/phases.py ``ROUND_SPANS``, the one list of
#: them). ``hold`` is the
#: time the dispatch rule deferred the round behind one in flight, from
#: the close of its window or its first op's arrival to the moment its
#: ops were taken, 0 for a round that was not held: a window from
#: stamps, which holds the settle of the round it waited for (the
#: collector's own ``hold`` state, the waiting and the poll alone, is in
#: ``cycle_wait_s``). ``verify_prep`` / ``verify_native``, inside
#: ``verify``: the call's arguments joined under the GIL, and the
#: foreign call. ``stage``: the scheduler's list work between its other
#: spans (the round's ops taken off the queue, the death-guard's list,
#: the requests, the enqueue stamps and counts). ``pack``: validation
#: and ``pack_batch``, before the engine lock. ``observe``: ``resolve()``
#: from the end of ``demux`` to its return, the observability's own cost
#: on the collector thread. ``release``: the settled round's device
#: arrays dropped, after ``settle``, where the handle's last reference
#: used to die (each deletion releases the GIL: the span is mostly the
#: wait to get it back from the threads the settle woke). ``settle``: the ``set_result`` fan-out after
#: ``resolve()`` returned. ``cycle``: the collector's pass that
#: dispatched the round, top of the loop to top of the loop; consecutive
#: rounds' cycles tile the collector's time
HOST_SPANS = ROUND_SPANS

#: windows derived from stamps rather than timed in place: ``queue`` =
#: enqueue of the round's oldest admitted op -> dispatch; ``inflight`` =
#: dispatch -> observed ready (rounds queued ahead on the device
#: included); ``device`` = the round's own device time as the host can
#: know it, from max(end of its dispatch, the previous round's observed
#: ready) to its own observed ready (an expiry sweep the
#: device ran since the round before can fall inside it:
#: ``device_exact`` is 0 then);
#: ``round`` = collection window -> answers unpacked
DERIVED_SPANS = ("queue", "inflight", "device", "round")

#: every recorded ledger carries exactly these spans (missing ones are
#: normalized to zero duration at the round start) — the stable shape
#: contract consumers rely on across durability/impl configs
STABLE_SPANS = HOST_SPANS + DERIVED_SPANS

#: names a ledger may mention at all: the stable set plus any canonical
#: phase (sweep/replay appear in recovery ledgers)
ALLOWED_SPAN_NAMES = frozenset(STABLE_SPANS) | frozenset(PHASES)

#: per-round counts a ledger may carry beside its spans, each a sum or
#: a size over the whole round: ``ops`` admitted, ``rejected`` by batch
#: verification, ``queue_wait_sum_s`` = sum over the admitted ops of
#: (dispatch - enqueue), ``rounds_ahead`` = rounds dispatched and
#: unresolved at this dispatch, ``device_exact`` = 1 when the device was
#: still running this round and the one before each time the host
#: arrived to wait, and ran no sweep between the two (so
#: ``device`` is this round's own device time, not an upper bound),
#: ``verify_chunks`` = chunk checks the round's first signature pass
#: ran side by side (1 = one inline call): a function of how many ops
#: the round took and of the host's cores, never of an op.
#: The ``cycle_*`` counts are sums over the collector's pass that
#: dispatched the round (obs/phases.py ``Span.cycle_counts``), each a
#: function of the round's shape and the host: ``cycle_wait_s`` = own
#: wall of the states that wait by design (assembly, hold, evict);
#: ``cycle_cpu_s`` = the collector thread's CPU seconds over the cycle;
#: ``cycle_blocked_s`` = over every other state but the native
#: crossing, own wall less CPU (the cycle's CPU less what was read over
#: its waits and the crossing), plus what the crossing's stamps read
#: beyond the call's own clock: what the collector waited for the GIL
#: or a lock; ``cycle_native_wait_s`` = the part of that spent getting
#: the GIL back after the native call (the crossing's stamps less the
#: call's own clock); ``cycle_unspanned_s`` = the cycle's wall that no span
#: inside it names. So ``cycle`` = ``cycle_wait_s`` + working wall +
#: ``cycle_unspanned_s``, and working wall = CPU + ``cycle_blocked_s`` +
#: the native crossing. The ``journal_*`` counts are the parts of the
#: round's ``journal`` span (engine/journal.py ``last_append``):
#: ``journal_seal_s`` sealing the frame, ``journal_fsync_s`` in the
#: fsync barrier, ``journal_bytes`` the frame on disk, a function of
#: the batch size alone; all 0 on an engine with no state directory
ROUND_COUNTS = ("ops", "rejected", "queue_wait_sum_s", "rounds_ahead",
                "device_exact", "verify_chunks", "cycle_wait_s",
                "cycle_cpu_s", "cycle_blocked_s", "cycle_native_wait_s",
                "cycle_unspanned_s", "journal_seal_s", "journal_fsync_s",
                "journal_bytes")


def _check_span(name: str, value) -> tuple[float, float]:
    if name not in ALLOWED_SPAN_NAMES:
        raise TelemetryLeakError(
            f"round tracer: span name {name!r} is not a round phase "
            f"(allowed: {sorted(ALLOWED_SPAN_NAMES)}) — a span is a "
            "phase, never an operation; per-op span names are how the "
            "access-pattern side channel would reopen in a trace dump"
        )
    try:
        start, dur = value
        start = float(start)
        dur = float(dur)
    except (TypeError, ValueError):
        raise TelemetryLeakError(
            f"round tracer: span {name!r} must be a (start_s, duration_s)"
            " pair of numbers — there is no field for payload data by "
            "design"
        ) from None
    if not (math.isfinite(start) and math.isfinite(dur)) or dur < 0:
        raise TelemetryLeakError(
            f"round tracer: span {name!r} has non-finite or negative "
            f"bounds ({start!r}, {dur!r})"
        )
    return start, dur


def _check_count(name: str, value) -> float:
    if name not in ROUND_COUNTS:
        raise TelemetryLeakError(
            f"round tracer: count {name!r} is not a round count "
            f"(allowed: {list(ROUND_COUNTS)}) — a count is a sum over "
            "the whole round, never a fact about one op"
        )
    if isinstance(value, bool) or not isinstance(value, (int, float)) \
            or not math.isfinite(value) or value < 0:
        raise TelemetryLeakError(
            f"round tracer: count {name!r} must be a finite number "
            f">= 0, got {value!r}"
        )
    return value


class RoundTracer:
    """Fixed-size ring of schema-checked per-round span ledgers."""

    def __init__(
        self,
        capacity: int = 512,
        registry: TelemetryRegistry | None = None,
        bubble_window: int = 64,
    ):
        if capacity <= 0:
            raise ValueError("tracer ring capacity must be positive")
        self.capacity = capacity
        self.bubble_window = max(1, bubble_window)
        self._lock = threading.Lock()
        self._ring: list[dict] = [None] * capacity  # type: ignore[list-item]
        self._n = 0  # total rounds ever recorded
        self._g_bubble = self._c_rounds = self._g_retained = None
        if registry is not None:
            self._g_bubble = registry.gauge(
                "grapevine_round_bubble_ratio",
                "windowed mean fraction of round wall clock the host is "
                "blocked waiting on the device (evict wait / round "
                "span). Double-buffered-round speedup ceiling = "
                "1/max(b, 1-b): ~2x at b~0.5, ~1x at both extremes "
                "(~0 host-bound, ~1 device-bound)")
            self._c_rounds = registry.counter(
                "grapevine_trace_rounds_total",
                "rounds recorded into the trace ring")
            self._g_retained = registry.gauge(
                "grapevine_trace_ring_rounds",
                "round ledgers currently retained in the trace ring")

    # -- recording ------------------------------------------------------

    def record_round(self, spans: dict, counts: dict | None = None) -> int:
        """Append one round's ledger and return its ``seq``; raises
        TelemetryLeakError unless every span and count fits the
        phase-level schema. Missing STABLE_SPANS are normalized to zero
        duration so the trace shape is identical with and without
        durability (journal/checkpoint), a scheduler, and across impls."""
        if not isinstance(spans, dict):
            raise TelemetryLeakError(
                "round tracer: a ledger must be a {span: (start, dur)} dict")
        merged: dict[str, tuple[float, float]] = {}
        for name, value in spans.items():
            merged[name] = _check_span(name, value)
        checked = {name: _check_count(name, value)
                   for name, value in (counts or {}).items()}
        # anchor for normalized zero-duration spans: the round span's
        # start, else the earliest recorded start, else 0
        anchor = merged.get("round", (None, 0.0))[0]
        if anchor is None:
            anchor = min((s for s, _ in merged.values()), default=0.0)
        for name in STABLE_SPANS:
            merged.setdefault(name, (anchor, 0.0))
        with self._lock:
            self._n += 1
            seq = self._n
            self._ring[(seq - 1) % self.capacity] = {
                "seq": seq,
                "spans": merged,
                "counts": checked,
            }
            retained = min(self._n, self.capacity)
            bubble = self._bubble_locked()
        if self._c_rounds is not None:
            self._c_rounds.inc()
            self._g_retained.set(retained)
            self._g_bubble.set(bubble)
        return seq

    def amend_round(self, seq: int, spans: dict | None = None,
                    counts: dict | None = None) -> bool:
        """Add spans that end after the ledger was recorded (``observe``,
        the scheduler's ``settle``, a depth-1 round's ``cycle``) and
        counts known only then (the cycle's) to round ``seq``, under
        the same schema. False when the ring has already let that round
        go."""
        checked = {name: _check_span(name, value)
                   for name, value in (spans or {}).items()}
        counted = {name: _check_count(name, value)
                   for name, value in (counts or {}).items()}
        with self._lock:
            entry = self._ring[(seq - 1) % self.capacity]
            if entry is None or entry["seq"] != seq:
                return False
            entry["spans"].update(checked)
            entry["counts"].update(counted)
        return True

    # -- derived signals ------------------------------------------------

    @staticmethod
    def _entry_bubble(entry: dict) -> float | None:
        spans = entry["spans"]
        _, round_dur = spans.get("round", (0.0, 0.0))
        _, evict_dur = spans.get("evict", (0.0, 0.0))
        if round_dur <= 0.0:
            return None
        return max(0.0, min(1.0, evict_dur / round_dur))

    def _recent_locked(self, k: int) -> list[dict]:
        n = min(self._n, self.capacity)
        out = []
        for i in range(max(0, n - k), n):
            out.append(self._ring[(self._n - n + i) % self.capacity])
        return out

    def _bubble_locked(self) -> float:
        ratios = [
            r for r in (
                self._entry_bubble(e)
                for e in self._recent_locked(self.bubble_window)
            )
            if r is not None
        ]
        return sum(ratios) / len(ratios) if ratios else 0.0

    def bubble_ratio(self) -> float:
        """Windowed mean host-blocked fraction (the exported gauge)."""
        with self._lock:
            return self._bubble_locked()

    # -- export ---------------------------------------------------------

    #: rounds alternate across this many lanes per track: the pipelined
    #: scheduler holds at most two rounds in flight (round k settles
    #: before round k+2 dispatches), and complete ("X") events sharing a
    #: tid must nest or stay disjoint per the trace-event format —
    #: adjacent rounds overlap, alternate rounds cannot
    _LANES = 2
    #: which track a span rides: 0 host phases, 1 device windows
    #: (``inflight`` holds ``device``), 2 queue wait, 3 the collector's
    #: cycles (round k's cycle holds round k-2's evict and settle, so it
    #: cannot ride a lane of round spans)
    _TRACK = {"device": 1, "inflight": 1, "queue": 2, "cycle": 3}
    _TRACK_NAMES = ("host round phases", "device window", "queue wait",
                    "collector cycle")

    def chrome_trace(self) -> dict:
        """The retained rounds as Chrome trace-event JSON (Perfetto-
        loadable): complete ("X") events in microseconds, host spans on
        tids 1-2, the device windows (``inflight`` holding ``device``)
        on tids 3-4, the queue wait on tids 5-6 and the collector's
        cycles on tids 7-8 of one process (round seq picks the lane).
        The ``grapevine/round`` event's ``args`` carry the round's
        counts beside its ``seq``."""
        with self._lock:
            entries = self._recent_locked(self.capacity)
            bubble = self._bubble_locked()
            total = self._n
        events: list[dict] = [
            {"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
             "args": {"name": "grapevine-engine"}},
        ]
        for lane in range(self._LANES):
            for track, label in enumerate(self._TRACK_NAMES):
                events.append(
                    {"name": "thread_name", "ph": "M", "pid": 1,
                     "tid": 1 + track * self._LANES + lane,
                     "args": {"name": f"{label} (lane {lane})"}})
        for entry in entries:
            seq = entry["seq"]
            lane = seq % self._LANES
            for name, (start, dur) in sorted(
                entry["spans"].items(), key=lambda kv: (kv[1][0], kv[0])
            ):
                args = {"seq": seq}
                if name == "round":
                    args.update(entry["counts"])
                events.append({
                    "name": f"grapevine/{name}",
                    "cat": "round",
                    "ph": "X",
                    "ts": int(start * 1e6),
                    "dur": max(0, int(dur * 1e6)),
                    "pid": 1,
                    "tid": 1 + self._TRACK.get(name, 0) * self._LANES + lane,
                    "args": args,
                })
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "rounds_recorded_total": total,
                "rounds_retained": len(entries),
                "bubble_ratio": round(bubble, 6),
            },
        }

    def chrome_trace_json(self) -> str:
        return json.dumps(self.chrome_trace())
