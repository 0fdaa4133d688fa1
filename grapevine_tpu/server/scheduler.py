"""Cross-connection request batcher (+ batched signature verification).

The north-star component the reference never needed (its enclave
serialized per-op ECALLs; SURVEY.md §2c): concurrent gRPC handler threads
submit single operations, and a collector thread packs them into
fixed-size engine rounds — up to ``batch_size`` ops or ``max_wait_ms``,
whichever first. Under-full rounds are dummy-padded by the engine, so the
device cadence carries no information about load bursts beyond the round
count itself.

Challenge-signature verification rides the same batching: the round's
signatures are checked by random-linear-combination multi-scalar
multiplications (session/ristretto.py:batch_verify — SURVEY.md §2b
"consider batch verify"), one per chunk of the round, the chunks side by
side on the host's cores (``verify_lanes``) inside one native call; only
a failing round pays per-item verification to identify offenders, which
are rejected without reaching the engine.

The collector is a staged pipeline (PR 10): it keeps up to
``pipeline_depth`` dispatched rounds in a bounded in-flight ledger and
settles them oldest-first, so at depth 2 round k+2's collection window,
batch verification, and journal fsync all overlap rounds k and k+1 on
the device (engine/batcher.py module docstring has the stage contract;
OPERATIONS.md §16 the ordering/durability argument). Depth 1 is
bit-for-bit the pre-PR-10 dispatch-then-settle loop.

The depth is for FULL rounds. A window that closes short of a batch
while a round is in flight does not dispatch (``hold`` in
``_run_inner``): a part-empty round queued behind another answers
nobody sooner, it only puts a whole device round between the next ops
and theirs. The queue stays open until it holds a batch (dispatched at
once, the rounds in flight staying in flight) or until the last round
in flight has settled (what has gathered then leaves without a further
window). So the cadence is a function of two public aggregates and
nothing else: the queue's depth against the batch size and the number
of rounds in flight — load, never content (SECURITY.md, "round cadence
and size").
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import TYPE_CHECKING

from ..obs.phases import (
    COLLECTOR_THREAD, name_thread, reset_thread_spans, span as _plain_span,
    trace_span)
from ..session import schnorrkel
from ..wire.records import QueryRequest, QueryResponse

if TYPE_CHECKING:
    # a frontend imports this module for its exceptions; importing the
    # engine would start a JAX backend in a process that owns no chip
    from ..engine.batcher import GrapevineEngine

#: (pub, context, message, signature) as taken by the scheme's verify
AuthItem = tuple[bytes, bytes, bytes, bytes]


#: the least signatures a chunk check is given a thread for: below it
#: Pippenger's bucket pass costs more additions than it saves (a chunk
#: of 256 already pays 1.3x the additions per signature of one call for
#: 2,048) and starting the thread costs more than the check
MIN_VERIFY_CHUNK = 256

#: cores left to the collector's own Python and the ingress beside it
#: when a round's chunk checks are spread over the rest
_CORES_KEPT_BACK = 2


def verify_lanes() -> int:
    """How many chunk checks of a round may run at once: the cores this
    process may use less the two its Python needs. From what the process
    can see; nothing configures it."""
    try:
        cores = len(os.sched_getaffinity(0))
    except AttributeError:  # not every platform has affinity masks
        cores = os.cpu_count() or 1
    return max(1, cores - _CORES_KEPT_BACK)


def round_counts(enqueued: list[float], taken: int, t_dispatch: float,
                 rounds_ahead: int, verify_chunks: int) -> dict:
    """The per-round counts the scheduler adds to a round's ledger
    (obs/tracer.py ROUND_COUNTS): ``enqueued`` are the perf_counter
    enqueue stamps of the ops admitted to the round, ``taken`` how many
    ops the window took off the queue (the rest failed verification),
    ``rounds_ahead`` how many rounds were dispatched and unresolved at
    this dispatch, ``verify_chunks`` how many chunk checks the round's
    first verification pass ran (1 when it was one inline call)."""
    return {
        "ops": len(enqueued),
        "rejected": taken - len(enqueued),
        "queue_wait_sum_s": sum(max(0.0, t_dispatch - t) for t in enqueued),
        "rounds_ahead": rounds_ahead,
        "verify_chunks": verify_chunks,
    }


def _round_ready(pending) -> bool:
    """Whether ``resolve()`` would find the device done with this
    round. A handle that cannot say (a test's bare fake) is taken to be
    ready: its ``resolve()`` is then the wait, as it was."""
    probe = getattr(pending, "ready", None)
    return True if probe is None else bool(probe())


def _release(pending) -> None:
    """Let a settled round's device arrays go, under the ``release``
    span (a test's bare fake holds none)."""
    release = getattr(pending, "release", None)
    if release is not None:
        release()


class AuthFailure(Exception):
    """The request's challenge signature did not verify."""


class SchedulerShutdown(RuntimeError):
    """The op was settled (or refused) because the scheduler is
    draining: the explicit shutdown error clients get instead of a
    silently dropped future. The serving layers map it to gRPC
    UNAVAILABLE so clients retry elsewhere."""


class BatchScheduler:
    def __init__(
        self,
        engine: "GrapevineEngine",
        max_wait_ms: float = 8.0,
        idle_gap_ms: float = 2.0,
        clock=None,
        scheme=None,
        restart_on_crash: bool = False,
        pipeline_depth: int | None = None,
    ):
        self.engine = engine
        self.max_wait = max_wait_ms / 1000.0
        self.idle_gap = idle_gap_ms / 1000.0
        self.clock = clock or (lambda: int(time.time()))
        #: round-pipeline depth — max dispatched-but-unsettled rounds
        #: the collector keeps in flight (the bounded in-flight ledger;
        #: engine/batcher.py module docstring, OPERATIONS.md §16).
        #: Default: the engine's resolved ``config.pipeline_depth``
        #: (stub engines in tests have none → 1, the serial program);
        #: the explicit parameter exists for the bench's depth A/B.
        depth = (
            pipeline_depth
            if pipeline_depth is not None
            else getattr(engine, "pipeline_depth", 1)
        )
        if int(depth) < 1:
            raise ValueError(f"pipeline_depth must be >= 1, got {depth}")
        self.pipeline_depth = int(depth)
        #: signature scheme module (sign/verify/batch_verify); default is
        #: the reference-compatible sr25519 (session/schnorrkel.py)
        self.scheme = scheme or schnorrkel
        #: optional multiprocess verify fan-out (server/hostpipe.py):
        #: when GrapevineServer runs a host pipeline it plants the pool
        #: here, and the round's first-pass batch_verify is offered to
        #: the worker processes first. None = the in-process chunks.
        self.hostpipe = None
        #: how many chunk checks of a round may run side by side
        self._verify_lanes = verify_lanes()
        #: chunk checks the last round's first verification pass ran
        self._verify_chunks = 1
        #: optional SLO-adaptive window policy (server/adaptive.py),
        #: planted by the serving layer after observability attaches;
        #: None = the static max_wait/idle_gap/full-batch window
        self.adaptive = None
        #: batch-level telemetry sink (engine/metrics.py on an
        #: obs.TelemetryRegistry); the scheduler records into the
        #: engine's registry so /metrics serves one merged view
        self.metrics = getattr(engine, "metrics", None)
        #: the collector's one way of timing anything (obs/phases.py
        #: ``span``): behind the engine's phase histogram where there is
        #: one (a test's stub engine has none)
        self._span = getattr(self.metrics, "span", None) or _plain_span
        #: (request, auth, future, perf_counter enqueue time)
        self._queue: list[
            tuple[QueryRequest, AuthItem | None, Future, float]
        ] = []
        self._inflight: list[Future] = []
        self._last_enqueue = 0.0
        #: perf_counter enqueue time of the current queue head — the age
        #: of the oldest waiting op is the healthz stall signal
        #: (obs/httpd). Every stamp the scheduler takes is perf_counter:
        #: one clock for its deadlines, the round ledger and the SLO
        self._head_enqueue = 0.0
        #: perf_counter dispatch time of the round currently in flight on
        #: the device, None when none is. A wedge inside resolve() (the
        #: device never returning) empties the queue but freezes this —
        #: stall_age() must see it, or healthz serves 200 while every
        #: in-flight client hangs on fut.result() forever
        self._inflight_since: float | None = None
        self._cv = threading.Condition()
        self._closed = False
        #: explicit close() vs crash-closure: restart_on_crash revives
        #: the collector only for the latter
        self._shutdown = False
        self._restart_on_crash = restart_on_crash
        #: consecutive crashes without a successfully settled round in
        #: between; past the cap the collector stays dead so /healthz
        #: flips and the orchestrator replaces the process — supervised
        #: restart must not convert a persistent fault (disk full,
        #: wedged device) into a "healthy" server failing every request
        self._crash_streak = 0
        self.max_crash_streak = 8
        self._worker = threading.Thread(target=self._run, daemon=True)
        self._worker.start()

    def submit(
        self, req: QueryRequest, auth: AuthItem | None = None
    ) -> QueryResponse:
        """Block until the op's round commits; returns its response.

        With ``auth`` set, the signature is verified as part of the
        round's batch; raises AuthFailure (and the op never reaches the
        engine) if it does not verify."""
        return self.submit_nowait(req, auth).result()

    def submit_nowait(
        self, req: QueryRequest, auth: AuthItem | None = None
    ) -> Future:
        """Enqueue one op and return its Future without waiting: the
        one-item case of :meth:`submit_many` (both are ``_enqueue``).

        The open-loop entry point (grapevine_tpu/load): an arrival
        joins the queue at its scheduled time regardless of how earlier
        ops are faring, so overload latency is *measured* (the queue
        grows and enqueue→settle waits stretch) instead of silently
        self-throttled by a blocked caller. The Future resolves to the
        op's QueryResponse, or raises AuthFailure / SchedulerShutdown /
        the round's error exactly as ``submit`` would."""
        fut: Future = Future()
        t_enq = time.perf_counter()
        self._enqueue(((req, auth, fut, t_enq),), t_enq)
        return fut

    def submit_many(self, items) -> list[Future]:
        """Enqueue ``items`` — ``(req, auth)`` pairs, ``auth`` an
        AuthItem or None — in order and return their Futures in the
        same order, without waiting. What arrives as one message (the
        engine tier's batched ingress, server/tier.py) enters as one
        call: one lock take, one notify, one depth sample and one
        arrival note for all of it, where a call per op paid each of
        them per op. Every op of the call carries the same enqueue
        stamp, taken before the lock as one op's is."""
        t_enq = time.perf_counter()
        entries = [(req, auth, Future(), t_enq) for req, auth in items]
        if entries:
            self._enqueue(entries, t_enq)
        return [e[2] for e in entries]

    def _enqueue(self, entries, t_enq: float) -> None:
        """Queue ``(request, auth, future, t_enq)`` entries in order
        under one take of the lock. ``t_enq``, the caller's stamp for
        all of them, is perf_counter: the SLO's enqueue→settle anchor,
        the ledger's queue wait and the window's idle-gap deadline (one
        clock domain with the batcher's round spans)."""
        with self._cv:
            if self._closed:
                raise SchedulerShutdown("scheduler closed")
            if not self._queue:
                self._head_enqueue = t_enq
            self._queue += entries
            depth = len(self._queue)
            self._last_enqueue = t_enq
            if self.metrics is not None:
                self.metrics.observe_queue_depth(depth)
            self._cv.notify()
        wl = getattr(self.engine, "workload", None)
        if wl is not None:
            # outside the cv: a couple of registry samples must never
            # extend the collector's critical section
            wl.note_arrival(depth, len(entries))

    # -- health probes (obs/httpd.py's /healthz) ------------------------

    def worker_alive(self) -> bool:
        """False once the collector thread has died (crash or close)."""
        return self._worker.is_alive()

    def stall_age(self) -> float:
        """Seconds the oldest un-delivered op has been waiting: the max
        of the queue head's wait and the in-flight round's age. A
        healthy collector drains the head within max_wait + one device
        round and settles an in-flight round promptly, so a growing
        stall age means the engine thread has wedged — whether the ops
        are still queued or already on the device (the healthz
        trip-wire)."""
        now = time.perf_counter()
        with self._cv:
            q_age = now - self._head_enqueue if self._queue else 0.0
        t = self._inflight_since  # benign unlocked float read
        return max(q_age, now - t if t is not None else 0.0)

    def _run(self):
        """Collector loop wrapper: a crash in the loop must not strand
        blocked submitters (ADVICE r3: submit() waits on fut.result()
        with no timeout — a dead worker meant a hung client forever).
        Fail every queued and in-flight future and count the crash;
        with ``restart_on_crash`` the loop is revived in place (the
        supervised-restart mode — the thread never reads as dead),
        otherwise re-raise so the death is loud in logs and subsequent
        submits fail immediately."""
        while True:
            try:
                self._run_inner()
                return
            except BaseException as exc:
                with self._cv:
                    self._closed = True
                    stranded = [fut for _, _, fut, _ in self._queue]
                    self._queue.clear()
                    self._cv.notify_all()
                stranded += self._inflight
                for fut in stranded:
                    if not fut.done():
                        fut.set_exception(
                            RuntimeError(f"scheduler worker died: {exc!r}")
                        )
                crash_counter = getattr(
                    self.metrics, "record_worker_crash", None
                )
                if crash_counter is not None:
                    crash_counter()
                self._crash_streak += 1
                if (
                    not self._restart_on_crash
                    or self._shutdown
                    or self._crash_streak > self.max_crash_streak
                ):
                    raise
                import logging

                logging.getLogger("grapevine_tpu.scheduler").exception(
                    "collector crashed (streak %d/%d); supervised "
                    "restart (--worker-restart)",
                    self._crash_streak, self.max_crash_streak,
                )
                # jittered backoff so a hot fault loop cannot spin the
                # core; capped well under the healthz stall threshold
                time.sleep(min(5.0, 0.1 * (2 ** (self._crash_streak - 1))))
                self._inflight = []
                self._inflight_since = None
                with self._cv:
                    self._closed = self._shutdown

    def _run_inner(self):
        bs = self.engine.ecfg.batch_size
        depth = self.pipeline_depth
        #: the bounded in-flight ledger: (PendingRound, live futures,
        #: perf_counter dispatch time) in dispatch order. After a dispatch
        #: the collector settles the ledger down to ``depth`` rounds, so
        #: at depth 2 round k+2's collection window, verification, and
        #: journal fsync all run while rounds k and k+1 are still on the
        #: device; at depth 1 the sequence is bit-for-bit the pre-PR-10
        #: dispatch-then-settle loop. The bound is enforced AFTER
        #: dispatch on purpose (dispatch-then-settle IS the depth-1
        #: legacy ordering): depth+1 rounds are transiently dispatched-
        #: but-unresolved for the duration of each settle wait — size
        #: device resp/transcript buffer residency as depth+1 rounds,
        #: not depth (config.py knob docstring, OPERATIONS.md §16).
        #: Rounds always settle oldest-first (= dispatch = journal
        #: order), so responses, tracer ledgers, and leakmon hand-offs
        #: stay in round order at every depth.
        ledger: deque = deque()
        span = self._span
        name_thread(COLLECTOR_THREAD)
        reset_thread_spans()
        #: the open ``cycle`` span — one pass of this loop, top to top,
        #: holding every other span the collector takes — and the round
        #: dispatched in it. A pass that dispatches nothing (a drain, an
        #: all-rejected chunk) leaves its cycle open, so its time folds
        #: into the next round's; consecutive rounds' cycles tile the
        #: collector's time. Asleep with nothing queued and nothing in
        #: flight the collector is in no cycle.
        cycle, cycle_round = span("cycle").begin(), None
        #: the round dispatched last. Its device arrays are let go
        #: (``release``) once it is settled AND the collector has taken
        #: the next round's ops: where the handle's last reference used
        #: to die, so no span and no window changed its meaning when the
        #: deletion got a name. An older round's go as it is settled.
        pending = None

        def settle_head():
            pending_h, live_h, t_h = ledger.popleft()
            # the round being settled is the oldest in flight — its
            # dispatch time anchors the stall signal while we block
            self._inflight_since = t_h
            self._settle(pending_h, live_h)
            self._crash_streak = 0  # a settled round = recovered
            self._inflight_since = ledger[0][2] if ledger else None
            if pending_h is not pending:
                _release(pending_h)

        def hold(w_gap, w_target):
            """The dispatch rule for a short queue behind a round in
            flight: keep the queue open until it holds ``w_target`` ops
            (the caller dispatches at once, the ledger as it is) or
            until the ledger has settled empty (the caller dispatches
            what has gathered, without a further window). Watches both
            at the window's own idle gap: an arrival notifies ``_cv``,
            the head round's readiness is polled. With nothing queued
            no op is deferred and there is nothing to watch: the settle
            is the wait, as it always was, and the wave that follows a
            full round does not wake the collector once per op. Reads
            the queue's LENGTH and the ledger's, never an entry of
            either. The ``hold`` span is the waiting and the poll: it
            closes before the head round is settled."""
            while ledger:
                with span("hold"), self._cv:
                    while (0 < len(self._queue) < w_target
                           and not self._closed
                           and not _round_ready(ledger[0][0])):
                        self._cv.wait(timeout=w_gap)
                    if len(self._queue) >= w_target or self._closed:
                        return
                settle_head()

        while True:
            if cycle_round is not None:
                # the pass that dispatched a round has ended: so has its
                # cycle, and the next begins at the same instant
                done, cycle = cycle.end(), span("cycle").begin()
                if getattr(cycle_round, "note_cycle", None) is not None:
                    cycle_round.note_cycle(done.start, done.wall,
                                           done.cycle_counts())
                cycle_round = None
            #: this pass's spans of the round it dispatches, handed to
            #: the round's handle after the dispatch
            staged: dict = {}
            # the look at the queue is staging: behind a wave of
            # per-op submits the collector waits for this lock as long
            # as the wave lasts (each submit takes it, and none yields)
            look = span("stage", staged).begin()
            with self._cv:
                if not (self._queue or self._closed or ledger):
                    # asleep with nothing queued and nothing in flight
                    # the collector is in no cycle, and in no span
                    look.end()
                    cycle.end()
                    # (in a capture the sleep still has a name)
                    with trace_span("asleep"):
                        while not self._queue and not self._closed:
                            self._cv.wait()
                    staged.clear()
                    cycle = span("cycle").begin()
                    look = span("stage", staged).begin()
                drained = self._closed and not self._queue and not ledger
                has_work = bool(self._queue)
                depth0 = len(self._queue)
            look.end()
            if drained:
                cycle.end()
                return
            # per-round window decision OUTSIDE the cv (the burn-rate
            # scans and registry samples must never extend the
            # collector's critical section — the note_arrival stance).
            # Inputs are public aggregates only: the queue DEPTH (an
            # integer), the arrival EWMA and the SLO burn rates — never
            # queue contents (server/adaptive.py; CI seeds the
            # contents-dependent mutants).
            w_wait, w_gap, w_target = self.max_wait, self.idle_gap, bs
            if has_work and self.adaptive is not None:
                w_wait, w_gap, w_target = self.adaptive.decide(depth0)
            # the window's span opens before the lock is asked for and
            # closes with the window, inside the lock
            window = None
            if has_work:
                window = span("assembly", staged).begin()
            else:
                # no window was opened (a drain pass, or ops that came
                # while the collector held): the round still starts here
                staged["assembly"] = (time.perf_counter(), 0.0)
            with self._cv:
                if window is not None:
                    # Quiescence-based collection: a client wave
                    # re-arrives staggered over several ms after the
                    # previous round's responses land (decrypt → decode
                    # → sign → resubmit), so a fixed short window caught
                    # only the fastest few (measured 26% occupancy at 8
                    # clients). Keep the window open while arrivals are
                    # still trickling in (inter-arrival gap < idle_gap),
                    # capped at the window's wait total; a lone client
                    # still commits after the idle gap. The wait runs
                    # while the device executes the previous round (see
                    # below), so it costs no device idle time under load.
                    deadline = window.start + w_wait
                    hit_cap = False
                    while (len(self._queue) < w_target
                           and not self._closed):
                        now = time.perf_counter()
                        wait_until = min(
                            deadline, self._last_enqueue + w_gap
                        )
                        if now >= wait_until:
                            hit_cap = now >= deadline
                            break
                        self._cv.wait(timeout=wait_until - now)
                    window.end()
                    if (self.metrics is not None and hit_cap
                            and len(self._queue) < bs):
                        # window closed by the max_wait cap, not by
                        # quiescence or a full batch: arrivals are
                        # starving mid-wave (the stall signal)
                        self.metrics.record_stall()
                # the dispatch rule: a short queue behind a round in
                # flight waits for that round (hold's docstring); with
                # none in flight, or a batch in the queue, nothing is
                # deferred and the window's own lock take is the pop's
                held = bool(ledger and len(self._queue) < w_target
                            and not self._closed)
                # the ledger's ``hold`` is a window from these two
                # stamps and the queue's, not a span of the collector
                t_h0 = t_take = time.perf_counter()
                if not held:
                    with span("stage", staged):
                        chunk, backlog = self._take(bs, t_take)
            if held:
                hold(w_gap, w_target)
                with self._cv:
                    t_take = time.perf_counter()
                    # the deferred ops have waited since the hold began
                    # or since the first of them came
                    t_h0 = max(t_h0, self._head_enqueue)
                    with span("stage", staged):
                        chunk, backlog = self._take(bs, t_take)

            if pending is not None and not (ledger
                                            and ledger[-1][0] is pending):
                # the last round was settled inside this pass's hold
                _release(pending)
            pending, live = (None, [])
            with span("stage", staged):
                # everything the death-guard must fail if we crash from
                # here: the rounds still in flight on the device plus
                # the chunk just popped off the queue (no longer
                # reachable from _queue)
                self._inflight = [
                    f for _, lv, _ in ledger for _, f in lv
                ] + [f for _, _, f, _ in chunk]
            if chunk:
                with span("verify", staged):
                    live = self._verify_chunk(chunk)
                if live:
                    try:
                        with span("stage", staged):
                            reqs = [r for r, _ in live]
                        # async dispatch: the device starts this round
                        # while we resolve the previous one and collect
                        # the next — PERF.md's dispatch/compute overlap
                        t_disp = time.perf_counter()
                        pending = self.engine.handle_queries_async(
                            reqs, self.clock()
                        )
                        cycle_round = pending
                        # (getattr: test fakes return bare objects)
                        if getattr(pending, "note_span", None) is not None:
                            with span("stage", staged):
                                # how long the dispatch rule deferred
                                # this round; 0 for one that was not held
                                staged["hold"] = (t_h0, t_take - t_h0)
                                self._stamp_round(
                                    pending, staged, chunk, live, backlog,
                                    t_disp, len(ledger))
                            for name, (start, dur) in staged.items():
                                pending.note_span(name, start, dur)
                    except Exception as exc:  # pragma: no cover - defensive
                        for _, fut in live:
                            if not fut.done():
                                fut.set_exception(exc)
                        live = []
            if pending is not None:
                ledger.append((pending, live, t_disp))
                self._inflight_since = ledger[0][2]
                # the pipeline bound: settle oldest-first down to depth,
                # so the NEXT collection window opens with exactly
                # ``depth`` rounds overlapping it
                while len(ledger) > depth:
                    settle_head()
            elif ledger:
                # nothing dispatched this pass (drain, or an all-rejected
                # chunk; an idle tail settles inside the hold): settle
                # the oldest round so its clients are answered promptly
                # and close() can drain
                settle_head()

    def _stamp_round(self, pending, staged: dict, chunk, live, backlog: int,
                     t_disp: float, rounds_ahead: int) -> None:
        """What the collector knows of a round rides the round's handle
        itself, so the tracer and the SLO pair it with THIS round even
        while the pipeline overlaps the next window: the backlog, the
        oldest admitted op's enqueue stamp, the ``queue`` span (into
        ``staged``, with the pass's other spans) and the counts."""
        # post-dispatch backlog: the queue-depth sample obs/workload.py
        # histograms at round cadence (and flightrec records)
        pending.set_queue_depth(backlog)
        # anchor on the ops that actually entered the round: an
        # auth-rejected op's queue wait is not a commit latency, and
        # letting it in would hand an attacker (garbage signatures are
        # their cheapest input) a lever on the SLO burn rate
        enq_by_fut = {f: t for _, _, f, t in chunk}
        enqs = [enq_by_fut[f] for _, f in live]
        oldest = min(enqs)
        pending.set_enqueued_at(oldest)
        # the ledger's queue wait: the oldest admitted op's as a span,
        # every admitted op's as one sum — a round's aggregate, never
        # one op's wait (obs/tracer.py)
        staged["queue"] = (oldest, max(0.0, t_disp - oldest))
        pending.note_counts(**round_counts(
            enqs, len(chunk), t_disp, rounds_ahead, self._verify_chunks))

    def _take(self, bs: int, now: float):
        """Pop the next round's entries, at most ``bs``, off the queue
        (the caller holds ``_cv``); returns them and how many stay."""
        chunk, self._queue = self._queue[:bs], self._queue[bs:]
        if self._queue:
            # remaining head has been waiting since roughly now (it
            # arrived while this round was collected)
            self._head_enqueue = now
        if chunk and self.metrics is not None:
            self.metrics.observe_queue_depth(len(self._queue))
        return chunk, len(self._queue)

    def _batch_verify_fanout(self, items) -> bool:
        """First-pass batch verify: the round's items as k contiguous
        chunks, each its own random-linear-combination equation with its
        own randomisers (so soundness per signature is what one equation
        gives), side by side on k threads inside the scheme's one native
        call; the answer is the conjunction. k follows from the item
        count and the cores the process may use (``verify_lanes``), with
        a least chunk size: a thin round is one inline call. A False
        answer hands off to the inline bisect below, one chunk at a
        time — failure is the attacker-funded path and does not deserve
        the parallel hardware. With a hostpipe pool attached that is
        asked first; any pool fault degrades to the in-process check
        rather than rejecting honest traffic."""
        self._verify_chunks = 1
        if self.hostpipe is not None:
            from .hostpipe import HostPipeError

            try:
                return self.hostpipe.verify_parallel(items)
            except HostPipeError:
                pass  # degraded pool: verified correctness beats speed
        k = max(1, min(self._verify_lanes, len(items) // MIN_VERIFY_CHUNK))
        self._verify_chunks = k
        return bool(self.scheme.batch_verify(items, chunks=k))

    def _verify_chunk(self, chunk):
        """Batch signature verification; returns surviving (req, fut)."""
        # --- one multi-scalar multiplication for the round ------------
        with self._span("verify_prep"):
            authed = [i for i, (_, a, _, _) in enumerate(chunk)
                      if a is not None]
            items = [chunk[i][1] for i in authed]
        rejected: set[int] = set()
        if authed and not self._batch_verify_fanout(items):
            # bisect to the offenders: O(bad · log n) batch checks, so
            # one client spraying garbage signatures cannot force
            # per-item verification of every honest request
            stack = [authed]
            while stack:
                idxs = stack.pop()
                mid = len(idxs) // 2
                for half in (idxs[:mid], idxs[mid:]):
                    if not half:
                        continue
                    if len(half) == 1:
                        i = half[0]
                        if not self.scheme.verify(*chunk[i][1]):
                            rejected.add(i)
                            chunk[i][2].set_exception(
                                AuthFailure("bad challenge signature")
                            )
                    elif not self.scheme.batch_verify(
                        [chunk[i][1] for i in half]
                    ):
                        stack.append(half)
        if authed and self.metrics is not None:
            self.metrics.record_auth(failures=len(rejected))
        return [
            (req, fut)
            for i, (req, _, fut, _) in enumerate(chunk)
            if i not in rejected
        ]

    def _settle(self, pending, live):
        """Resolve a dispatched round and deliver its responses. The
        ``settle`` span covers the ``set_result`` fan-out (each call
        wakes one waiting handler thread); it ends after ``resolve()``
        recorded the round's ledger, so it is added to that ledger
        afterwards (PendingRound.note_settle). The round's device arrays
        are the collector loop's to let go (``release``)."""
        try:
            resps = pending.resolve()
            with self._span("settle") as settled:
                # the round's settle stamp, taken once and handed to
                # every handler on its future: what a handler waits
                # after it is the wake-up, not the round
                # (server/service.py)
                t_s0 = settled.start
                for (_, fut), resp in zip(live, resps):
                    fut.settled_at = t_s0
                    fut.set_result(resp)
            if getattr(pending, "note_settle", None) is not None:
                pending.note_settle(t_s0, settled.wall)
        except Exception as exc:  # pragma: no cover - defensive
            for _, fut in live:
                if not fut.done():
                    fut.set_exception(exc)

    def close(self):
        """Graceful drain: stop admitting, settle queued-but-undispatched
        ops with an explicit SchedulerShutdown (never silently dropped —
        the serving layer maps it to gRPC UNAVAILABLE so clients retry
        elsewhere), and let the worker finish the round already on the
        device before joining."""
        with self._cv:
            self._shutdown = True
            self._closed = True
            undispatched = [fut for _, _, fut, _ in self._queue]
            self._queue.clear()
            self._cv.notify_all()
        for fut in undispatched:
            if not fut.done():
                fut.set_exception(
                    SchedulerShutdown(
                        "scheduler draining: op was queued but not yet "
                        "dispatched; retry against a serving replica"
                    )
                )
        self._worker.join(timeout=5)
