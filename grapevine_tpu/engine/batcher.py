"""Host-side batching: wire records ↔ device SoA arrays, and the engine facade.

The request batcher is the TPU analog of the reference's per-request
enclave ECALL path (SURVEY.md §2c): N client operations are packed into
one fixed-size jit'd access round; under-full batches are padded with
dummy operations (request_type 0) that perform the identical ORAM access
pattern, preserving the fixed cadence.

Hard protocol errors (zero auth identity, UPDATE with zero id — the
reference's fail-fast gRPC errors, grapevine.proto:60-64,95) are raised
here on the host before anything reaches the device, exactly as the
reference rejects them before the oblivious path.

Pipelined round execution (PR 10, ROADMAP item 2): a round passes
through four stages — assemble (validate + pack, lock-free), journal
(sealed append + fsync, under the engine lock), dispatch (async jit
enqueue with the donated state, under the same lock hold), resolve
(device wait + demux, lock-free). ``handle_queries_async`` composes the
first three and returns the :class:`PendingRound` whose ``resolve()`` is
stage four; callers (``handle_queries`` here, the BatchScheduler, the
chaos harness) keep up to ``config.pipeline_depth`` rounds in flight
between dispatch and resolve, so round k+1's host assembly and journal
fsync overlap round k's device execution — with two donated engine
states rotating through XLA's buffer donation, steady-state cadence
approaches ``max(host, fsync, device)`` instead of their sum. The
durability ordering is depth-independent: journal-append (and its
fsync barrier) strictly precedes the same round's dispatch, and rounds
journal and dispatch inside one lock hold, so replay order is journal
order — never completion order (OPERATIONS.md §16).
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from collections.abc import Mapping

import jax
import numpy as np

from ..config import DurabilityConfig, GrapevineConfig
from ..obs.phases import PHASES, trace_span
from ..testing import faults
from ..wire import constants as C
from ..wire.records import QueryRequest, QueryResponse, Record
from ..wire.validate import validate_request  # noqa: F401  (re-export —
# moved to the jax-free wire package so hostpipe workers can validate
# without importing the engine; existing callers import it from here)
from .expiry import expiry_sweep
from .state import (
    EngineConfig,
    EngineState,
    ID_WORDS,
    KEY_WORDS,
    PAYLOAD_WORDS,
    init_engine,
)
from .metrics import EngineMetrics
from .round_step import engine_round_step
from .step import engine_step

_log = logging.getLogger(__name__)


def pack_batch(reqs: list[QueryRequest], batch_size: int, now: int) -> dict:
    """Pack ≤batch_size validated requests into device arrays, dummy-padded.

    Columnar: one ``b"".join`` + ``frombuffer`` per field instead of a
    per-request assignment loop — at B=2048 the loop was ~14 ms of host
    time per round, on par with the device round itself (PERF.md)."""
    n = len(reqs)
    if n > batch_size:
        raise ValueError("too many requests for one batch")
    b = batch_size

    def col(words: int, chunks) -> np.ndarray:
        arr = np.zeros((b, words), np.uint32)
        if n:
            arr[:n] = np.frombuffer(b"".join(chunks), "<u4").reshape(n, words)
        return arr

    rt = np.zeros((b,), np.uint32)
    rt[:n] = [r.request_type for r in reqs]
    return {
        "req_type": rt,
        "auth": col(KEY_WORDS, (r.auth_identity for r in reqs)),
        "msg_id": col(ID_WORDS, (r.record.msg_id for r in reqs)),
        "recipient": col(KEY_WORDS, (r.record.recipient for r in reqs)),
        "payload": col(PAYLOAD_WORDS, (r.record.payload for r in reqs)),
        # u64 clock as two u32 lanes (wire timestamps are u64; no 2106
        # rollover on the device path either)
        "now": np.uint32(int(now) & 0xFFFFFFFF),
        "now_hi": np.uint32((int(now) >> 32) & 0xFFFFFFFF),
    }


def unpack_responses(resp: dict, n: int) -> list[QueryResponse]:
    """Columnar device→wire conversion: one ``tobytes`` per field, rows
    sliced out of the flat buffer (bytes slicing is C-speed; the old
    per-row ``tobytes`` loop was ~8 ms at B=2048)."""
    status = np.asarray(resp["status"])[:n].tolist()
    ts_lanes = np.asarray(resp["timestamp"])[:n].astype(np.uint64)
    ts = (ts_lanes[:, 0] | (ts_lanes[:, 1] << np.uint64(32))).tolist()

    def rows(name: str, words: int) -> list[bytes]:
        flat = np.ascontiguousarray(
            np.asarray(resp[name])[:n], dtype="<u4"
        ).tobytes()
        sz = words * 4
        return [flat[i * sz : (i + 1) * sz] for i in range(n)]

    mids = rows("msg_id", ID_WORDS)
    snds = rows("sender", KEY_WORDS)
    rcps = rows("recipient", KEY_WORDS)
    pls = rows("payload", PAYLOAD_WORDS)
    return [
        QueryResponse(
            record=Record(
                msg_id=mids[i],
                sender=snds[i],
                recipient=rcps[i],
                timestamp=int(ts[i]),
                payload=pls[i],
            ),
            status_code=int(status[i]),
        )
        for i in range(n)
    ]


class PendingRound:
    """Handle to a dispatched-but-unsynced round; ``resolve()`` blocks."""

    __slots__ = ("_engine", "_resp", "_n", "_t0", "_t1", "_transcript",
                 "_batch", "_spans", "_counts", "_seq", "_enq", "_qdepth",
                 "_behind_other")

    def __init__(self, engine, resp, n, t0, transcript=None, batch=None,
                 spans=None, t1=None, behind_other=False, counts=None):
        self._engine = engine
        self._resp = resp
        self._n = n
        #: perf_counter at the start of the jit'd round's enqueue, and
        #: when the enqueue call returned (the end of this round's
        #: dispatch: the earliest the device could have started it)
        self._t0 = t0
        self._t1 = t0 if t1 is None else t1
        #: an expiry sweep went to the device since the round
        #: before: its time lies between the two rounds' ready stamps,
        #: so this round's ``device`` span is an upper bound
        #: (device_exact 0)
        self._behind_other = behind_other
        #: leak-monitor hand-off (engine.leakmon set): the round's public
        #: transcript (still a device array — the copy happens on the
        #: monitor thread) plus the host-side batch dict its key groups
        #: derive from
        self._transcript = transcript
        self._batch = batch
        #: {phase: (start_s, dur_s)} spans recorded so far on the
        #: perf_counter clock (dispatch/journal/checkpoint) — the round
        #: tracer's ledger accumulates here, and the leak monitor's
        #: phase durations derive from it
        self._spans = spans
        #: per-round counts for the tracer ledger (obs/tracer.py
        #: ROUND_COUNTS): the journal's from dispatch, the rest stamped
        #: by the scheduler (note_counts); and the ledger's seq once
        #: resolve() has recorded it
        self._counts = counts
        self._seq = None
        #: perf_counter enqueue time of the round's OLDEST op, stamped
        #: by the scheduler (set_enqueued_at) — the SLO's enqueue→settle
        #: anchor; None on the direct (schedulerless) path
        self._enq = None
        #: scheduler queue depth at dispatch (ops left waiting after
        #: this round's chunk was taken) — the workload telemetry's
        #: backlog sample (obs/workload.py); None on the direct path
        self._qdepth = None

    def set_enqueued_at(self, t_enq: float) -> None:
        """Stamp the oldest op's enqueue time (perf_counter seconds);
        must be called before ``resolve()``."""
        self._enq = t_enq

    def set_queue_depth(self, depth: int) -> None:
        """Stamp the post-dispatch scheduler backlog (an aggregate of
        the queue, never of any op in it); must be called before
        ``resolve()``."""
        self._qdepth = int(depth)

    def note_span(self, name: str, start_s: float, dur_s: float) -> None:
        """Add a collector-side span (assembly/verify/stage) to this round's
        ledger — exact pairing even under the pipelined scheduler, where
        a staged hand-off would attach round k+1's window to round k.
        Must be called before ``resolve()``."""
        if self._spans is None:
            self._spans = {}
        self._spans[name] = (start_s, dur_s)

    def note_counts(self, **counts) -> None:
        """Add per-round counts (obs/tracer.py ROUND_COUNTS: sums and
        sizes over the whole round, never a fact about one op) to this
        round's ledger. Must be called before ``resolve()``."""
        if self._counts is None:
            self._counts = {}
        self._counts.update(counts)

    def note_settle(self, start_s: float, dur_s: float) -> None:
        """Add the scheduler's ``settle`` span, which ends after
        ``resolve()`` recorded the ledger: the recorded round is amended
        by its seq, so there is one ledger per round, still."""
        self._amend({"settle": (start_s, dur_s)})

    def note_cycle(self, start_s: float, dur_s: float, counts: dict) -> None:
        """Add the collector's ``cycle`` this round was dispatched in,
        and the cycle's counts. The cycle ends at the top of the
        collector's next pass: before ``resolve()`` at depth 2 (noted on
        the handle), after it at depth 1 (the recorded round is
        amended)."""
        if self._seq is None:
            self.note_span("cycle", start_s, dur_s)
            self.note_counts(**counts)
        else:
            self._amend({"cycle": (start_s, dur_s)}, counts)

    def _amend(self, spans: dict, counts: dict | None = None) -> None:
        tracer = self._engine.tracer
        if tracer is not None and self._seq is not None:
            tracer.amend_round(self._seq, spans, counts)

    def ready(self) -> bool:
        """True once the device has finished this round, so that
        ``resolve()`` would not wait for it; never blocks. What the
        scheduler polls while it holds a short queue behind this
        round."""
        return self._resp is None or not _still_running(self._resp)

    def resolve(self) -> list[QueryResponse]:
        m = self._engine.metrics
        # "evict" = device round completion measured from the host: the
        # jit'd fetch/apply/evict/write-back program finishes inside this
        # wait (per-stage device splits come from a profiler capture
        # reduced by obs/phases.py DEVICE_SCOPES — the host cannot time
        # inside one XLA program)
        waited = _still_running(self._resp)
        spans = dict(self._spans or {})
        with m.span("evict", spans):
            jax.block_until_ready(self._resp)
        with m.span("demux", spans):
            out = unpack_responses(self._resp, self._n)
        # everything below is the observability's own cost on the
        # collector thread: one span, added to the recorded round by seq
        with m.span("observe") as observed:
            self._observe(spans, waited)
        self._amend({"observe": (observed.start, observed.wall)})
        return out

    def release(self) -> None:
        """Drop the round's device arrays (its answers are unpacked),
        under the ``release`` span: the scheduler calls it where the
        handle's last reference used to die (server/scheduler.py
        ``_run_inner``), so the deletion has a name and no older span
        or window changed its meaning for it. Each deletion lets go of
        the GIL, and beside awake ingress threads the collector waits a
        switch interval to get it back, several times a round: 16-17 ms
        of a 70 ms cycle on the chip after the settle has woken them,
        0.1 ms before it has (PERF.md §5-6)."""
        with self._engine.metrics.span("release") as released:
            self._resp = None
        self._amend({"release": (released.start, released.wall)})

    def _observe(self, spans: dict, waited: bool) -> None:
        """Derive the round's windows from its spans and hand the round
        to whatever watches rounds: metrics, the tracer's ring, the SLO,
        workload and cost monitors, the leak monitor's queue."""
        eng = self._engine
        # observed ready: where the wait for the device ended
        t_dm = sum(spans["evict"])
        t_done = sum(spans["demux"])
        # recorded duration = dispatch → results delivered. Under the
        # pipelined scheduler this includes the next round's collection
        # window (resolve runs after the next dispatch), i.e. it is the
        # round *commit latency* a client observes, not pure device time
        bs = eng.ecfg.batch_size
        eng.metrics.record_round(self._n, bs, t_done - self._t0)
        # the collection window opens the round (its dispatch, where no
        # scheduler opened one); the queue wait of its oldest op may
        # reach back before it and stays out of the span, and so do the
        # collector's cycle and its look at the queue (``stage``), which
        # come a little before the window
        r0 = spans.get("assembly", spans["dispatch"])[0]
        # the two device windows (obs/tracer.py DERIVED_SPANS), emitted
        # on EVERY config so the trace JSON shape is stable. "inflight"
        # = async enqueue → readiness OBSERVED at resolve, the rounds
        # dispatched ahead included. "device" = this round's own time:
        # the device runs rounds in dispatch order, so it started this
        # one when the previous one was ready or when this one's enqueue
        # returned, whichever came last; rounds resolve in dispatch
        # order on one thread, so the previous ready stamp is the
        # engine's last. Exact when the device was still running each
        # time the host arrived to wait and ran no other program (a
        # sweep) in between (device_exact), else an upper bound.
        spans["inflight"] = (self._t0, t_dm - self._t0)
        prev_ready, prev_waited = eng._last_ready
        d0 = min(max(self._t1, prev_ready), t_dm)
        spans["device"] = (d0, t_dm - d0)
        eng._last_ready = (t_dm, waited)
        spans["round"] = (r0, t_done - r0)
        counts = dict(self._counts or {})
        counts["device_exact"] = int(
            waited and prev_waited and not self._behind_other)
        tracer = eng.tracer
        if tracer is not None:
            # a few dict ops + schema check; the ring is lock-cheap
            self._seq = tracer.record_round(spans, counts)
        slo = eng.slo
        if slo is not None:
            # enqueue→settle commit latency, worst op in the batch: the
            # scheduler stamped the oldest op's enqueue; the direct path
            # anchors at dispatch start (no queue wait to account)
            slo.observe(t_done - (self._enq if self._enq is not None else r0))
        wl = getattr(eng, "workload", None)
        if wl is not None:
            # batch fill + dispatch-time backlog + per-phase utilization
            # from this round's span ledger (obs/workload.py) — a few
            # histogram/gauge samples on the collector thread
            wl.observe_round(self._n, bs, self._qdepth, spans)
        cmn = getattr(eng, "costmon", None)
        if cmn is not None:
            # device span vs the modeled roofline floor (obs/costmon.py)
            # — two gauge sets per round
            cmn.observe_round(spans)
        lm = eng.leakmon
        if lm is not None and self._transcript is not None:
            # one non-blocking queue put; detectors run on the monitor's
            # own thread (obs/leakmon.py), never on the round path.
            # the flightrec phase schema is the canonical PHASES
            # (+ round): the derived windows and the collector's
            # further spans stay tracer-only
            phases = {k: d for k, (_, d) in spans.items()
                      if k in PHASES or k == "round"}
            lm.submit_round(self._batch, self._transcript, self._n, bs,
                            phases, queue_depth=self._qdepth)


def _sweep_clock(now: int, period: int) -> tuple:
    """The sweep program's three scalars: the clock's low word, the
    period, the clock's high word."""
    return (np.uint32(int(now) & 0xFFFFFFFF), np.uint32(period),
            np.uint32((int(now) >> 32) & 0xFFFFFFFF))


#: the journal's counts of a round on an engine with no state directory
_NO_JOURNAL = {"seal_s": 0.0, "fsync_s": 0.0, "bytes": 0}


def _still_running(resp) -> bool:
    """True when the device has not finished the round yet: the host
    arrived first and its wait is the device's remaining time."""
    return not all(x.is_ready() for x in jax.tree.leaves(resp))


def _build_state(build) -> tuple[EngineState, float]:
    """``build()``'s state, waited for, under the host span
    ``grapevine/state_init``, and the seconds it took. A bus of 2^21
    messages zeroes 10 GB of trees here; a server's set-up is mostly
    this and the first compile."""
    t0 = time.perf_counter()
    with trace_span("state_init"):
        state = jax.block_until_ready(build())
    return state, time.perf_counter() - t0


class GrapevineEngine:
    """The in-process oblivious engine: the TPU analog of the enclave.

    Thread-safe facade owning device state; the gRPC server calls
    ``handle_queries`` with decrypted, authenticated requests and the
    expiry timer calls ``expire``.
    """

    def __init__(self, config: GrapevineConfig | None = None, seed: int = 0,
                 durability: DurabilityConfig | Mapping | None = None):
        """``durability``: a ``DurabilityConfig``, or a mapping of its
        fields (what a JSON configuration file holds)."""
        self.config = config or GrapevineConfig()
        self.ecfg = EngineConfig.from_config(self.config)
        #: bucket-axis sharding (config.py ``shards``; parallel/mesh.py):
        #: at shards > 1 the step and sweep dispatch through the shard_map'd
        #: programs on a mesh over the first N devices. The adapters
        #: below keep the single-chip call signatures (ecfg, state, ...)
        #: so every dispatch/replay site stays shard-agnostic —
        #: bit-identical results are the mesh contract, so nothing
        #: downstream (journal, checkpoint, leakmon, oracle suites) can
        #: tell the difference.
        self._mesh = None
        #: per-leaf NamedSharding of the live state at shards > 1 (None
        #: single-chip): a restored checkpoint is placed shard by shard
        #: through these, never staged whole on the first device
        state_shardings = None
        if self.config.shards > 1:
            from ..parallel import (
                init_sharded_engine, make_mesh, make_sharded_step,
                make_sharded_sweep,
            )

            devs = jax.devices()
            if len(devs) < self.config.shards:
                raise ValueError(
                    f"shards={self.config.shards} but only {len(devs)} "
                    "JAX device(s) are visible — the bucket trees shard "
                    "one contiguous heap range per device"
                )
            self._mesh = make_mesh(devs[: self.config.shards])
            # created directly sharded: a mesh exists because one chip
            # cannot hold the trees, so they must never be staged on one
            self._init_state = lambda: init_sharded_engine(
                self.ecfg, self._mesh, seed)
            self.state, state_init_s = _build_state(self._init_state)
            state_shardings = jax.tree.map(lambda x: x.sharding, self.state)
            sstep = make_sharded_step(self.ecfg, self._mesh)
            step_fn = lambda _ecfg, state, batch: sstep(state, batch)  # noqa: E731
            self._step = step_fn
            #: the jit that holds the round's executable
            #: (:meth:`compiled_round_memory`)
            self._step_jit = sstep
            ssweep = make_sharded_sweep(self.ecfg, self._mesh)
            self._sweep = lambda _ecfg, state, *clock: ssweep(state, *clock)
            #: the jit that holds the sweep's executable
            #: (:meth:`_warm_sweep`)
            self._sweep_jit = ssweep
        else:
            #: builds the empty state: at construction, and again when a
            #: restart in place (:meth:`recover`) finds none on the device
            self._init_state = lambda: init_engine(self.ecfg, seed)
            self.state, state_init_s = _build_state(self._init_state)
            step_fn = (engine_round_step if self.config.commit == "phase"
                       else engine_step)
            # donate the state: trees update in place (no per-round copy;
            # the placement kernel aliases the value plane through)
            self._step = self._step_jit = jax.jit(
                step_fn, static_argnums=(0,), donate_argnums=(1,)
            )
            self._sweep = self._sweep_jit = jax.jit(
                expiry_sweep, static_argnums=(0,), donate_argnums=(1,)
            )
        #: (perf_counter when the last resolved round was observed
        #: ready, whether the host had to wait for it): what the next
        #: round's own ``device`` span starts from (PendingRound.resolve)
        self._last_ready: tuple[float, bool] = (0.0, False)
        #: a sweep program was enqueued since the last round's
        #: dispatch: the next round's ``device`` span is not its own time
        self._other_device_work = False
        self._lock = threading.Lock()
        #: resolved round-pipeline depth: the max dispatched-but-
        #: unresolved rounds a driver keeps in flight (config.py knob;
        #: module docstring). Deliberately NOT part of EngineConfig —
        #: the checkpoint/journal fingerprint must not cover it, because
        #: a journal written at depth 2 replays bit-identically on a
        #: depth-1 engine (replay order is journal order at every
        #: depth; tests/test_pipeline.py pins the cross-depth restore).
        #: Auto: 2 on the TPU (the device round is the long pole —
        #: overlapping host work and the journal fsync behind it is the
        #: whole win; not measured on the chip), 1 on the CPU — there the
        #: extra in-flight round buys no overlap but costs up to one
        #: full device round of open-loop commit latency (measured on
        #: the CPU)
        if self.config.pipeline_depth is not None:
            self.pipeline_depth = self.config.pipeline_depth
        else:
            from ..config import on_tpu

            self.pipeline_depth = 2 if on_tpu() else 1
        self.metrics = EngineMetrics()
        layout = self.round_layout()
        self.metrics.set_round_layout(layout)
        self.metrics.set_mesh_psum_bytes(self.mesh_psum_bytes())
        self.metrics.set_dma_placed_rows(self.dma_placed_rows())
        #: shapes of the first batch this engine dispatched, None until
        #: then (the jit's own cache is shared by every engine of the
        #: process and cannot say whose program it holds); the
        #: compiler's memory report of the round is read once, at the
        #: first health read after it
        self._dispatched_shapes = None
        self._compiled_memory_read = False
        self.metrics.set_state_size(
            state_init_s,
            sum(x.nbytes for x in jax.tree.leaves(self.state)))
        _log.info(
            "round layout (dense_levels, fetched_bucket_rows, "
            "perpath_bucket_rows per oram_round): %s",
            ", ".join(f"{t}={v}" for t, v in layout.items()),
        )
        #: streaming obliviousness auditor (obs/leakmon.py), attached by
        #: the serving layer when --leakmon is on; None = no monitoring
        self.leakmon = None
        #: round-trace profiler (obs/tracer.py) and commit-latency SLO
        #: tracker (obs/slo.py), attached by the serving layer; None =
        #: rounds are not traced / measured against an SLO
        self.tracer = None
        self.slo = None
        #: workload telemetry (obs/workload.py): batch fill / queue
        #: depth / arrival-rate / utilization signals, attached by the
        #: serving layer or the load harness; None = not sampled
        self.workload = None
        #: cost observatory (obs/costmon.py): static grapevine_cost_*
        #: ledger gauges plus the per-round roofline residual, attached
        #: by the serving layer; None = rounds are not scored
        self.costmon = None
        #: crash safety (engine/checkpoint.py): with a DurabilityConfig,
        #: every admitted batch is journaled before dispatch and the
        #: whole state checkpointed every N records; construction runs
        #: recovery (checkpoint load + deterministic journal replay), so
        #: a freshly built engine already holds the pre-crash state
        self.durability = None
        if self.config.expiry_period > 0:
            # before the recovery, which may meet a sweep frame
            self._warm_sweep()
        if durability is not None:
            from .checkpoint import DurabilityManager

            self.durability = DurabilityManager(
                durability, self.ecfg, registry=self.metrics.registry,
                state_shardings=state_shardings, span=self.metrics.span,
            )
            self.recover()

    def recover(self) -> None:
        """The path a restart takes, whole: the state directory's newest
        checkpoint loaded into the state that is on the device (the
        empty one, which is built first where there is none), then the
        journal's tail replayed through the jitted round and sweep. The
        constructor calls it, once; it can be called again on an engine
        that was :meth:`abandon`-ed, which is how a run that cannot let
        its process die exercises a restart on the engine it timed.

        One owner of the device state: the engine's lock is held
        throughout, the state goes into the load and comes back out of
        the replay, and between the two the engine holds none. So the
        device never holds a second state, and a load or a replay that
        raises leaves an engine with no state: nothing half-loaded can
        serve."""
        with self._lock:
            state, self.state = self.state, None
            if state is None:
                state, _ = _build_state(self._init_state)
            with self.metrics.span("replay"):
                state = self.durability.recover(state, self._replay_record)
                jax.block_until_ready(state.free_top)
            self.state = state

    def abandon(self) -> None:
        """What a SIGKILL leaves of a durable engine, without the
        process dying: the journal's handle dropped with no sync, no
        final checkpoint, no drain, and the device state deleted. The
        engine serves nothing until :meth:`recover`."""
        with self._lock:
            self.durability.abandon()
            state, self.state = self.state, None
            for leaf in jax.tree.leaves(state):
                leaf.delete()

    def _replay_record(self, state: EngineState, rec) -> EngineState:
        """Apply one journal record through the same jitted programs the
        live path uses — replay IS re-execution, so recovered state is
        bit-identical by the engine's own determinism."""
        from .journal import KIND_ROUND

        if rec.kind == KIND_ROUND:
            state, _resp, _transcript = self._step(self.ecfg, state, rec.batch)
            return state
        return self._sweep(
            self.ecfg, state,
            np.uint32(rec.now), np.uint32(rec.period), np.uint32(rec.now_hi),
        )

    def _warm_sweep(self) -> None:
        """Compile the sweep program (or load it from the compile
        cache) for the state this engine holds, running nothing: jax
        keeps the executable on the lowering the jit's own calls find,
        so neither the first :meth:`expire` of a served bus, one
        ``expiry_period / 10`` after start and under the engine's lock
        with every Query waiting behind it, nor a sweep frame that a
        recovery replays compiles anything."""
        operands = (self.state, *_sweep_clock(1, 1))
        if self._mesh is None:  # the one-chip jit takes ecfg, statically
            operands = (self.ecfg,) + operands
        self._sweep_jit.lower(*operands).compile()

    def checkpoint_now(self) -> int | None:
        """Force a sealed checkpoint of the current state (the drain
        path: scheduler settled → checkpoint → exit). No-op returning
        None without durability."""
        if self.durability is None:
            return None
        with self._lock:
            with self.metrics.span("checkpoint"):
                return self.durability.checkpoint(self.state)

    def attach_leakmon(self, monitor) -> None:
        """Attach an EngineLeakMonitor; subsequent rounds hand their
        transcripts to it off the jit path (PendingRound.resolve)."""
        self.leakmon = monitor

    def attach_tracer(self, tracer) -> None:
        """Attach a RoundTracer; subsequent rounds append their span
        ledgers to its ring (PendingRound.resolve)."""
        self.tracer = tracer

    def attach_slo(self, slo) -> None:
        """Attach an SloTracker; subsequent rounds observe their
        enqueue→settle commit latency against it."""
        self.slo = slo

    def attach_workload(self, workload) -> None:
        """Attach a WorkloadTelemetry; subsequent rounds observe their
        fill/backlog/utilization and the scheduler notes arrivals."""
        self.workload = workload

    def attach_costmon(self, costmon) -> None:
        """Attach a CostMonitor; subsequent rounds score their device
        span against the modeled roofline floor."""
        self.costmon = costmon

    def handle_queries(
        self, reqs: list[QueryRequest], now: int
    ) -> list[QueryResponse]:
        """Process requests in slot order (padding to full batches).

        Atomicity is **per round**, not per call: the engine lock is
        taken per batch_size chunk, so two concurrent multi-batch calls
        may interleave at round boundaries (each round itself is atomic
        and slot-ordered). This is intended — it is exactly the
        interleaving concurrent gRPC clients produce through the
        scheduler, and the soak suite exercises it; a caller needing a
        multi-round transaction must hold its own lock.

        Multi-chunk calls pipeline: up to ``pipeline_depth`` chunks stay
        dispatched-but-unresolved, so chunk k+1's pack + journal fsync
        overlap chunk k's device execution. Responses come back in
        request order regardless (rounds resolve in dispatch order), and
        depth 1 is bit-for-bit the serial resolve-before-next-dispatch
        program."""
        for r in reqs:  # all-or-nothing: nothing commits if any is malformed
            validate_request(r)
        out: list[QueryResponse] = []
        bs = self.ecfg.batch_size
        depth = max(1, self.pipeline_depth)
        ledger: deque[PendingRound] = deque()
        # resolve everything dispatched even when a dispatch or an
        # earlier resolve raises — an abandoned PendingRound would leave
        # its journal/leakmon/metrics hand-off forever unaccounted. The
        # FIRST exception stays the primary one; the drain never stops
        # on a failed resolve.
        exc0: BaseException | None = None
        try:
            for i in range(0, len(reqs), bs):
                while len(ledger) >= depth:
                    out.extend(ledger.popleft().resolve())
                ledger.append(
                    self.handle_queries_async(reqs[i : i + bs], now)
                )
        except BaseException as exc:
            exc0 = exc
        while ledger:
            try:
                out.extend(ledger.popleft().resolve())
            except BaseException as exc:
                if exc0 is None:
                    exc0 = exc
        if exc0 is not None:
            raise exc0
        return out

    # -- the staged round pipeline (module docstring; OPERATIONS.md §16)

    def _assemble_round(self, reqs: list[QueryRequest], now: int) -> dict:
        """Stage 1 — assemble: validate + pack the wire records into the
        fixed-size device batch. Lock-free host work; under the
        pipelined scheduler this runs while earlier rounds execute."""
        for r in reqs:
            validate_request(r)
        if int(now) <= 0:
            raise ValueError("server clock must be positive")
        bs = self.ecfg.batch_size
        if len(reqs) > bs:
            raise ValueError("async path is one round at a time")
        return pack_batch(reqs, bs, now)

    def _journal_round(self, batch: dict, n_real: int, spans: dict,
                       counts: dict) -> None:
        """Stage 2 — journal: sealed append + fsync barrier (per
        ``journal_fsync_every``) BEFORE the round may dispatch — the
        crash-safety contract. Runs under the engine lock in the same
        hold as stage 3, so journal order IS dispatch order and replay
        order is journal order at every pipeline depth. With a round
        already in flight (pipeline_depth=2) the fsync overlaps its
        device execution instead of serializing with it — the PR-10
        point; the "journal" series isolates what it costs, and
        ``counts`` takes its parts (obs/tracer.py ``journal_*``; zeros
        without a state directory, so every ledger has them)."""
        cost = _NO_JOURNAL
        if self.durability is not None:
            with self.metrics.span("journal", spans):
                self.durability.append_round(batch, n_real)
            cost = self.durability.journal.last_append
        counts.update(journal_seal_s=cost["seal_s"],
                      journal_fsync_s=cost["fsync_s"],
                      journal_bytes=cost["bytes"])
        if faults.active():
            # the pipelined crash window: this round is durable (its
            # frame is fsynced) but not yet dispatched, while the
            # previous round may still be mid-flight on the device
            faults.crash("round.pre_dispatch")

    def _dispatch_round(self, batch: dict):
        """Stage 3 — dispatch: enqueue the jit'd round on the device and
        chain ``self.state`` onto its (donated) output. JAX dispatch is
        asynchronous — this returns at enqueue, and with two rounds in
        flight XLA rotates two donated state buffers. Same lock hold as
        stage 2 (see there)."""
        t0 = time.perf_counter()
        self.state, resp, transcript = self._step(
            self.ecfg, self.state, batch
        )
        if self._dispatched_shapes is None:
            self._dispatched_shapes = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)
        return t0, time.perf_counter(), resp, transcript

    def handle_queries_async(
        self, reqs: list[QueryRequest], now: int
    ) -> "PendingRound":
        """Dispatch one round without waiting for the device.

        Composes pipeline stages 1-3 (assemble → journal+fsync →
        dispatch) and returns the round's handle; ``resolve()`` is stage
        4. JAX dispatch is asynchronous: this returns as soon as the
        round is enqueued, so a caller (the scheduler, or
        ``handle_queries`` on a multi-chunk call) can assemble, verify,
        and journal the *next* round — and keep up to ``pipeline_depth``
        rounds un-resolved — while the device executes this one (the
        dispatch/compute overlap PERF.md's cost model calls for).
        Rounds are serialized by the engine lock; ``resolve()`` blocks
        for the results."""
        span = self.metrics.span
        spans: dict = {}
        counts: dict = {}
        with span("pack", spans):
            batch = self._assemble_round(reqs, now)
        lm = self.leakmon
        with self._lock:
            # "dispatch" = async device enqueue (JAX returns at
            # enqueue; the device round itself lands in "evict"); the
            # host pack runs in stage 1 OUTSIDE the lock, where the
            # pipeline can overlap it, under its own span. With
            # durability on, dispatch also spans the journal barrier —
            # append-before-dispatch is the crash-safety contract, and
            # its fsync is genuinely part of the commit latency (the
            # "journal" series isolates it) — and the checkpoint when
            # one falls due.
            behind_other = self._other_device_work
            self._other_device_work = False
            with span("dispatch", spans):
                self._journal_round(batch, len(reqs), spans, counts)
                t0, t1, resp, transcript = self._dispatch_round(batch)
                if faults.active():
                    faults.crash("round.post_dispatch")
                if (self.durability is not None
                        and self.durability.should_checkpoint()):
                    # blocks this round's slot until the sealed state is
                    # on disk — the RTO/RPO trade
                    # --checkpoint-every-rounds buys. Copying a leaf
                    # to the host waits for every dispatched round
                    # (this one included), so the sealed state is
                    # exactly the journal's seq even with the pipeline
                    # full — the checkpoint is itself a pipeline barrier.
                    with span("checkpoint", spans):
                        self.durability.checkpoint(self.state, spans)
        if lm is None:
            return PendingRound(self, resp, len(reqs), t0, spans=spans, t1=t1,
                                behind_other=behind_other, counts=counts)
        # hand the monitor only the key-material columns: retaining the
        # full batch dict would pin the (B, PAYLOAD_WORDS) payload array
        # in the monitor queue for grouping that never reads it
        key_cols = {
            k: batch[k] for k in ("req_type", "auth", "msg_id", "recipient")
        }
        return PendingRound(
            self, resp, len(reqs), t0,
            transcript=transcript, batch=key_cols, spans=spans, t1=t1,
            behind_other=behind_other, counts=counts,
        )

    def handle_queries_with_transcript(self, reqs, now):
        """Test/bench variant returning the public transcript as well."""
        for r in reqs:
            validate_request(r)
        bs = self.ecfg.batch_size
        if len(reqs) > bs:
            raise ValueError("single batch only")
        # stage-1 pack stays outside the lock, same staging as the
        # async path (analysis/locklint.py flags pack-under-lock)
        batch = pack_batch(reqs, bs, now)
        with self._lock:
            if self.durability is not None:  # same contract as the async path
                self.durability.append_round(batch, len(reqs))
            self.state, resp, transcript = self._step(self.ecfg, self.state, batch)
            return unpack_responses(resp, len(reqs)), np.asarray(transcript)

    def expire(self, now: int, period: int | None = None) -> int:
        """Run the expiry sweep; returns the number of records evicted."""
        period = self.config.expiry_period if period is None else period
        if period <= 0:
            return 0
        span = self.metrics.span
        clock = _sweep_clock(now, period)
        # call -> lock held: the rounds (and the checkpoint) ahead of it
        waiting = span("sweep_lock").begin()
        with self._lock:
            waiting.end()
            before = int(self.state.free_top)
            if self.durability is not None:
                # journal-before-mutate, same as rounds: a crash between
                # append and apply replays the sweep (apply ≡ replay)
                with span("sweep_journal"):
                    self.durability.append_sweep(
                        int(clock[0]), int(clock[2]), int(period))
            with span("sweep"):
                self.state = self._sweep(self.ecfg, self.state, *clock)
                jax.block_until_ready(self.state.free_top)
            self._other_device_work = True
            evicted = int(self.state.free_top) - before
            self.metrics.record_sweep(evicted)
            if self.durability is not None and self.durability.should_checkpoint():
                # sweeps count against the cadence like rounds do — an
                # idle server with expiry on must not grow the journal
                # (and its replay-time RTO) without bound
                with self.metrics.span("checkpoint"):
                    self.durability.checkpoint(self.state)
            return evicted

    def close(self) -> None:
        """Flush and close the durability store (if any)."""
        if self.durability is not None:
            with self._lock:
                self.durability.close()

    # -- metrics (never keyed by client identity; SURVEY.md §5) ---------

    def message_count(self) -> int:
        return self.ecfg.max_messages - int(self.state.free_top)

    def recipient_count(self) -> int:
        return int(self.state.recipients)

    def sample_stash(self) -> dict:
        """Sample stash occupancy of both trees into the metrics gauges;
        returns the per-tree counts so health() reuses them instead of
        re-running the device reductions under the lock.

        Called at scrape/health cadence, not per round: a device
        reduction every round would serialize the dispatch pipeline for
        a gauge that is only read between scrapes (it is also the
        /metrics endpoint's pre-scrape refresh hook, obs/httpd.py)."""
        from ..oram.path_oram import stash_occupancy

        with self._lock:
            state = self.state
            trees = {"rec": state.rec, "mb": state.mb}
            if self.ecfg.rec.posmap is not None:
                # recursive position maps (oram/posmap.py) carry their
                # own internal ORAM whose stash fills under the same
                # pressure — invisible here would mean silent position
                # loss with a green gauge
                trees["rec_pm"] = state.rec.posmap.inner
                trees["mb_pm"] = state.mb.posmap.inner
            counts = {
                name: int(stash_occupancy(tree))
                for name, tree in trees.items()
            }
            if (self._dispatched_shapes is not None
                    and not self._compiled_memory_read):
                self._compiled_memory_read = True
                self.metrics.set_compiled_memory(self.compiled_round_memory())
        for name, n in counts.items():
            self.metrics.observe_stash(name, n)
        self.metrics.observe_device_memory(
            d.memory_stats() or {} for d in state.rec.tree_val.devices())
        return counts

    def compiled_round_memory(self):
        """``memory_analysis()`` of the round executable this engine's
        jit already holds: what the compiler counted for one chip
        (arguments, outputs, what of them is aliased, temporaries,
        generated code). Lowering the jit again for the operands it was
        called with finds jax's own cached lowering and, on it, the
        executable the first round compiled; where it finds none (jax
        keeps it elsewhere, the operands miss the cache) this returns
        None and compiles nothing. Call with the engine's lock held,
        after a round has run."""
        operands = (self.state, self._dispatched_shapes)
        if self._mesh is None:  # the one-chip jit takes ecfg, statically
            operands = (self.ecfg,) + operands
        lowered = self._step_jit.lower(*operands)
        held = getattr(getattr(lowered, "_lowering", None),
                       "_executable", None)
        if held is None:
            return None
        return lowered.compile().memory_analysis()

    def mesh_psum_bytes(self) -> dict:
        """``{tree: bytes}`` one round hands to ``psum`` for each tree
        (oram/path_oram.py ``_path_gather``): every pass all-reduces a
        full-size buffer of the rows it fetches, for the index plane,
        the value plane at its stored width and the nonce plane and,
        under a recursive position map, the leaf plane with the nonces
        once more. The records tree makes one pass a round, the mailbox
        tree two. 0 off a mesh, where nothing is reduced."""
        if self._mesh is None:
            return {"rec": 0, "mb": 0}
        b, d = self.ecfg.batch_size, self.ecfg.mb_choices
        out = {}
        for tree, cfg, n, passes in (("rec", self.ecfg.rec, b, 1),
                                     ("mb", self.ecfg.mb, b * d, 2)):
            words = cfg.bucket_slots + cfg.stored_row_words + 2
            if cfg.posmap is not None:
                words += cfg.bucket_slots + 2
            out[tree] = 4 * passes * cfg.fetched_bucket_rows(n) * words
        return out

    def dma_placed_rows(self) -> dict:
        """``{tree: rows}`` of the value plane one round's write-back
        places by DMA (oram/path_oram.py ``_path_scatter``): every row
        the tree's passes fetch, where the plane stores its rows as
        whole memory tiles (``OramConfig.stored_row_shape``) and the
        backend is a TPU; 0 where the plane keeps XLA's scatter. On a
        mesh each chip places the rows it owns of them."""
        from ..config import on_tpu

        b, d = self.ecfg.batch_size, self.ecfg.mb_choices
        out = {}
        for tree, cfg, n, passes in (("rec", self.ecfg.rec, b, 1),
                                     ("mb", self.ecfg.mb, b * d, 2)):
            by_dma = on_tpu() and len(cfg.stored_row_shape) == 2
            out[tree] = passes * cfg.fetched_bucket_rows(n) if by_dma else 0
        return out

    def round_layout(self) -> dict:
        """``{tree: (dense_levels, fetched_bucket_rows,
        perpath_bucket_rows)}`` of one ``oram_round`` on each tree at
        this engine's geometry: the records round makes B accesses, a
        mailbox round B·D."""
        b, d = self.ecfg.batch_size, self.ecfg.mb_choices
        return {
            tree: (cfg.dense_levels(n), cfg.fetched_bucket_rows(n),
                   cfg.perpath_bucket_rows(n))
            for tree, cfg, n in (("rec", self.ecfg.rec, b),
                                 ("mb", self.ecfg.mb, b * d))
        }

    def health(self) -> dict:
        """Aggregate state + batch-level counters (never per-client)."""
        # per-tree stash occupancy, batch-level (a tree-top cache bug
        # would first show up as silent stash drift — the directed
        # cached↔uncached soak in tests/test_tree_cache.py reads these,
        # and operators get the same early signal)
        occupancy = self.sample_stash()
        with self._lock:
            state = self.state  # one round's state for a consistent snapshot
            overflow = int(state.rec.overflow) + int(state.mb.overflow)
            if self.ecfg.rec.posmap is not None:
                # internal position-ORAM overflow loses k position
                # entries per dropped block — every bit as unhealthy as
                # payload stash loss
                overflow += int(state.rec.posmap.inner.overflow)
                overflow += int(state.mb.posmap.inner.overflow)
            out = {
                "messages": self.ecfg.max_messages - int(state.free_top),
                "recipients": int(state.recipients),
                "stash_overflow": overflow,
                "stash_occupancy": occupancy,
                **self.metrics.snapshot(),
            }
            return out
