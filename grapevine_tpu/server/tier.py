"""Split frontend/engine serving tier — the horizontal host-path story.

One CPython process terminates only so many sessions a second: on the
chip's 13-core host a frontend's GIL saturates at ~680 ops/s (1.53 ms
of CPU an op, of which AEAD open and seal, challenge lockstep, codec
and validation are ``frontend_work_us`` 139 and the rest the
synchronous gRPC handler thread per Query; cell ``backlog-grpc-1chip``,
builder's chip runs, PR 32; OPERATIONS.md §4), while the device engine
targets many times that. The reference never
faced this split (its frontend was C-core gRPC + Rust); here it is
explicit: N **frontend** processes
terminate client sessions (IX handshake, channel AEAD, challenge
lockstep, request unpack + validation) and forward validated ops to ONE
**engine** process, which batch-verifies sr25519 signatures ACROSS
frontends (one Pippenger MSM per round — better batching than any
frontend could do alone) and runs the oblivious rounds on the device.

Trust model: frontends are deployment-internal (same boundary as the
reference's untrusted host runtime). The engine accepts pre-decrypted
requests only from them — bind the engine listener to localhost or a
private network; client-facing confidentiality still ends at the
frontends' AEAD channels. The signature check stays in the ENGINE, so a
compromised frontend cannot forge ops for identities it has never seen
sign (it can only replay what the session layer already allows — same
as the reference's host).

Wire (internal, raw-bytes gRPC like the public API): ops reach the
engine in batches, one message per frontend carrying what gathered
there, never one RPC per op — a Python gRPC handler costs the engine's
GIL more than the session crypto the split took off it (batched, an op
costs the engine's listener ``engine_ingress_us`` 24 at
``submit_batch_ops`` 128 a message; same cell, same runs).

    /grapevine.EngineAPI/SubmitBatch
    request  = n (u32 LE) ‖ n × (packed QueryRequest, constant size
               ‖ challenge, 32 B) — the auth identity and signature
               already travel inside the packed request
    response = n × (status, 1 B ‖ packed QueryResponse, constant size,
               zeros unless the status is OK), in the request's order

A status is gRPC's own code for what the per-op RPC used to answer: OK,
INVALID_ARGUMENT (the entry does not decode or validate: that entry
alone), UNAUTHENTICATED (its signature does not verify: that entry
alone; the op never reaches the engine), UNAVAILABLE (the scheduler is
draining; the op was not admitted). Only a message whose framing cannot
be read fails as a whole. A batch of one is the idle case. Nothing
configures the batching: a frontend's one sender ships what has gathered
whenever it is free to send, so an op that arrives at an idle frontend
leaves at once and batches grow only because the sender was busy.

The public-facing frontend behaves byte-identically to the monolithic
``GrapevineServer`` (same Auth/Query surface), so clients need no
changes and a load balancer can spread them across frontends.
"""

from __future__ import annotations

import logging
import random
import struct
import threading
import time
from concurrent import futures
from concurrent.futures import Future

import grpc

from ..config import GrapevineConfig
from ..obs.phases import trace_span
from ..testing.reference import HardProtocolError
from ..wire import constants as C
from ..wire.records import QueryRequest, QueryResponse
from ..wire.validate import validate_request
from .scheduler import AuthFailure, SchedulerShutdown

log = logging.getLogger("grapevine_tpu.tier")

ENGINE_SERVICE_NAME = "grapevine.EngineAPI"
ENGINE_METHOD = "SubmitBatch"

#: one entry of a batch on the wire, in and out
ENTRY_IN_SIZE = C.QUERY_REQUEST_WIRE_SIZE + C.CHALLENGE_SIZE
ENTRY_OUT_SIZE = 1 + C.QUERY_RESPONSE_WIRE_SIZE
#: an entry's status byte is gRPC's code for the same outcome
ENTRY_OK = grpc.StatusCode.OK.value[0]
ENTRY_INVALID = grpc.StatusCode.INVALID_ARGUMENT.value[0]
ENTRY_UNAVAILABLE = grpc.StatusCode.UNAVAILABLE.value[0]
ENTRY_UNAUTHENTICATED = grpc.StatusCode.UNAUTHENTICATED.value[0]
_NO_RESPONSE = bytes(C.QUERY_RESPONSE_WIRE_SIZE)

#: a frontend holds one op per session at most, so a message is bounded
#: by its session cap (4,096 x 1.1 KB by default): above gRPC's 4 MB
#: default, far under this
_MAX_MESSAGE_BYTES = 64 << 20
_CHANNEL_OPTIONS = (
    ("grpc.max_receive_message_length", _MAX_MESSAGE_BYTES),
    ("grpc.max_send_message_length", _MAX_MESSAGE_BYTES),
)
#: one handler thread per batch in flight, and a frontend keeps one
#: batch in flight: this many frontends are served side by side
_LISTENER_THREADS = 64


def pack_batch(entries) -> bytes:
    """``entries``: (packed QueryRequest, challenge) pairs -> the
    request message."""
    return struct.pack("<I", len(entries)) + b"".join(
        req + challenge for req, challenge in entries)


def unpack_answers(data: bytes, n: int) -> list[tuple[int, bytes]]:
    """The response message -> (status, packed QueryResponse) per entry;
    ValueError unless it answers exactly ``n`` entries."""
    if len(data) != n * ENTRY_OUT_SIZE:
        raise ValueError(
            f"engine answered {len(data)} bytes to a batch of {n}")
    return [(data[o], data[o + 1:o + ENTRY_OUT_SIZE])
            for o in range(0, len(data), ENTRY_OUT_SIZE)]


class EngineListener:
    """Serves ``EngineAPI`` for any owner of a ``BatchScheduler``: the
    engine's ingress, told once. One message is one ``submit_many``, so
    what a frontend gathered costs the scheduler one lock take and the
    collector's GIL one handler, whatever it holds. ``registry`` (the
    scheduler owner's, so /metrics serves it) gets the ingress counters:
    batch-level sums, never a per-op or per-frontend series."""

    def __init__(self, scheduler, registry):
        self.scheduler = scheduler
        self._c_batches = registry.counter(
            "grapevine_engine_submit_batches_total",
            "SubmitBatch messages taken by the engine's listener",
        )
        self._c_ops = registry.counter(
            "grapevine_engine_submit_ops_total",
            "entries of those messages (ops per batch = ops / batches)",
        )
        self._c_ingress_s = registry.counter(
            "grapevine_engine_ingress_seconds_total",
            "handler-thread seconds from a SubmitBatch message's entry "
            "to submit_many returned: framing, unpack, validate, enqueue",
        )
        self._grpc_server: grpc.Server | None = None

    def start(self, address: str = "127.0.0.1:0") -> int:
        """Bind the internal listener (plain host:port — deployment-
        internal; keep it on localhost or a private interface);
        returns the bound port."""
        identity = lambda b: b  # noqa: E731
        handler = grpc.method_handlers_generic_handler(
            ENGINE_SERVICE_NAME,
            {ENGINE_METHOD: grpc.unary_unary_rpc_method_handler(
                self._submit_batch, request_deserializer=identity,
                response_serializer=identity)},
        )
        self._grpc_server = grpc.server(
            futures.ThreadPoolExecutor(max_workers=_LISTENER_THREADS),
            options=_CHANNEL_OPTIONS,
        )
        self._grpc_server.add_generic_rpc_handlers((handler,))
        port = self._grpc_server.add_insecure_port(address)
        if port == 0:
            raise RuntimeError(f"failed to bind engine listener {address}")
        self._grpc_server.start()
        return port

    def stop(self, grace: float = 1.0) -> None:
        if self._grpc_server is not None:
            self._grpc_server.stop(grace).wait()
            self._grpc_server = None

    def _submit_batch(self, message: bytes,
                      context: grpc.ServicerContext) -> bytes:
        t_in = time.perf_counter()
        n = struct.unpack_from("<I", message)[0] if len(message) >= 4 else -1
        if len(message) != 4 + n * ENTRY_IN_SIZE:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT,
                          "bad batch framing")
        status = bytearray([ENTRY_INVALID]) * n
        items, slots = [], []
        with trace_span("ingress"):
            for i in range(n):
                o = 4 + i * ENTRY_IN_SIZE
                try:
                    req = QueryRequest.unpack(
                        message[o:o + C.QUERY_REQUEST_WIRE_SIZE])
                    validate_request(req)
                except (ValueError, HardProtocolError):
                    # same exception scope as the public service's
                    # fail-fast — anything else is an engine bug and
                    # must crash loudly, not masquerade as malformed
                    # traffic
                    continue
                items.append((req, (
                    req.auth_identity,
                    C.GRAPEVINE_CHALLENGE_SIGNING_CONTEXT,
                    message[o + C.QUERY_REQUEST_WIRE_SIZE:o + ENTRY_IN_SIZE],
                    req.auth_signature,
                )))
                slots.append(i)
            try:
                futs = self.scheduler.submit_many(items)
            except SchedulerShutdown:
                # drain: nothing of this message was admitted
                futs = None
        self._c_batches.inc()
        self._c_ops.inc(n)
        self._c_ingress_s.inc(time.perf_counter() - t_in)
        answers = [_NO_RESPONSE] * n
        for k, i in enumerate(slots):
            if futs is None:
                status[i] = ENTRY_UNAVAILABLE
                continue
            try:
                answers[i] = futs[k].result().pack()
                status[i] = ENTRY_OK
            except AuthFailure:
                status[i] = ENTRY_UNAUTHENTICATED
            except SchedulerShutdown:
                # drain settle: UNAVAILABLE is what the frontend stub's
                # bounded retry keys on (and never auth/protocol errors)
                status[i] = ENTRY_UNAVAILABLE
        return b"".join(bytes((st,)) + a for st, a in zip(status, answers))


class EngineServer:
    """The engine tier: one device engine + cross-frontend batching.

    Exposes ``SubmitBatch`` through an :class:`EngineListener`. The
    batches of many frontends land in the shared BatchScheduler, which
    fills device rounds and batch-verifies each round's signatures with
    one MSM — exactly the path the monolithic server uses, so every
    scheduler/engine test covers this tier too.
    """

    def __init__(self, config: GrapevineConfig | None = None, seed: int = 0,
                 max_wait_ms: float | None = None, clock=None, leakmon=None,
                 durability=None, worker_restart: bool = False,
                 trace_ring_size: int = 512, slo=None,
                 profile_enable: bool = False, engine=None,
                 replicate_to: str | None = None, ship_every: int = 1,
                 host_workers: int = 0, adaptive_batch: bool = False):
        from ..engine.batcher import GrapevineEngine
        from ..session import get_signature_scheme
        from .scheduler import BatchScheduler

        self.config = (engine.config if engine is not None
                       else config or GrapevineConfig())
        # durable construction runs recovery before the listener binds;
        # ``engine`` injection lets a promoted StandbyReplica serve its
        # already-warm state in-process — no second recovery, so the
        # "serving inside one checkpoint interval" RTO claim holds
        self.engine = engine or GrapevineEngine(
            self.config, seed=seed, durability=durability
        )
        #: primary-side journal shipping (engine/replication.py) — the
        #: engine tier owns the journal, so it owns the feed
        self.shipper = None
        if replicate_to is not None:
            from ..engine.replication import JournalShipper

            self.shipper = JournalShipper(
                self.engine, replicate_to, ship_every=ship_every
            )
            self.shipper.start()
        #: continuous obliviousness auditing (obs/leakmon.py) — the
        #: engine tier owns the device, so it owns the transcript audit
        self.leakmon = None
        if leakmon is not None:
            from ..obs.leakmon import EngineLeakMonitor

            self.leakmon = EngineLeakMonitor.for_engine(self.engine, leakmon)
            self.engine.attach_leakmon(self.leakmon)
            if self.shipper is not None:
                # ship-cadence detector: the audit verdict folds the
                # shipper's frame-length books (leakmon.py rationale)
                self.leakmon.attach_shipper(self.shipper)
        #: round tracing + commit-latency SLO + optional capture gate —
        #: one shared attach policy (obs.attach_round_observability has
        #: the rationale and the observe-only default contract)
        from ..obs import attach_round_observability

        self.tracer, self.slo, self.profiler = attach_round_observability(
            self.engine, self.engine.metrics.registry,
            trace_ring_size=trace_ring_size, slo=slo,
            profile_enable=profile_enable,
        )
        kwargs = {} if max_wait_ms is None else {"max_wait_ms": max_wait_ms}
        self.scheduler = BatchScheduler(
            self.engine,
            clock=clock,
            scheme=get_signature_scheme(self.config.signature_scheme),
            restart_on_crash=worker_restart,
            **kwargs,
        )
        if adaptive_batch:
            # SLO-adaptive window sizing (server/adaptive.py): planted
            # after observability attaches so the policy reads the same
            # public arrival EWMA and burn rates /metrics exports
            from .adaptive import AdaptiveBatchPolicy

            self.scheduler.adaptive = AdaptiveBatchPolicy(
                self.engine.ecfg.batch_size,
                self.scheduler.max_wait,
                self.scheduler.idle_gap,
                workload=self.engine.workload,
                slo=self.slo,
                registry=self.engine.metrics.registry,
            )
        #: optional verify fan-out pool: the engine tier holds no
        #: sessions, so its hostpipe does nothing but split the round's
        #: batch-verify MSM across worker processes (scheduler.py)
        self.hostpipe = None
        if host_workers:
            from .hostpipe import HostPipeline

            self.hostpipe = HostPipeline(
                host_workers,
                scheme=self.config.signature_scheme,
                restart_on_crash=worker_restart,
                registry=self.engine.metrics.registry,
            )
            self.scheduler.hostpipe = self.hostpipe
        self.listener = EngineListener(
            self.scheduler, self.engine.metrics.registry)
        self.clock = clock or (lambda: int(time.time()))
        self._expiry_stop = threading.Event()
        self._expiry_thread: threading.Thread | None = None
        self._metrics_server = None

    def start(self, address: str = "127.0.0.1:0") -> int:
        """Bind the internal listener and start the expiry sweep;
        returns the listener's port."""
        port = self.listener.start(address)
        if self.config.expiry_period > 0:
            # the engine tier owns the device, so it owns the sweep —
            # the same loop the monolithic server runs (service.py)
            from .service import run_expiry_loop

            self._expiry_thread = threading.Thread(
                target=run_expiry_loop,
                args=(self.engine, self.config, self._expiry_stop, self.clock),
                daemon=True,
            )
            self._expiry_thread.start()
        log.info("engine tier serving on %s", address)
        return port

    def health(self) -> dict:
        return self.engine.health()

    def healthz(self, stall_threshold: float = 30.0) -> tuple[bool, dict]:
        """Engine-tier liveness: collector thread up, oldest queued op
        not waiting past the threshold (same semantics as the monolithic
        server's healthz, server/service.py)."""
        alive = self.scheduler.worker_alive()
        stall = self.scheduler.stall_age()
        age = self.engine.metrics.last_round_age()
        healthy = alive and stall < stall_threshold
        detail = {
            # role tag: the fleet aggregator (obs/fleet.py) folds member
            # healthz docs and needs to tell tiers apart by body alone
            "role": "engine",
            "worker_alive": alive,
            "stall_age_s": round(stall, 3),
            "last_round_age_s": None if age is None else round(age, 3),
        }
        if self.engine.durability is not None:
            detail["durability"] = self.engine.durability.status()
        if self.hostpipe is not None:
            # degraded verify pool: the scheduler degrades to in-process
            # verification (still correct), but the capacity loss should
            # page — same stance as the monolithic server's fold
            detail["host_workers_alive"] = self.hostpipe.alive_count()
            detail["host_workers"] = self.hostpipe.workers
            healthy = healthy and self.hostpipe.alive()
        if self.shipper is not None:
            detail["replication"] = self.shipper.stats()
            # a fatally-fenced shipper means a standby promoted out from
            # under us — this primary must stop serving (split-brain)
            healthy = healthy and self.shipper.fatal is None
        if self.leakmon is not None:
            # same folding as the monolithic server: a SUSPECT transcript
            # is a serving fault — 503 stops routing (cached verdict; the
            # probe path never pays detector math)
            v = self.leakmon.last_verdict()
            detail["leakaudit"] = v["verdict"]
            healthy = healthy and v["verdict"] == "PASS"
        # commit-latency SLO burn-rate verdict (obs/slo.py): breached =
        # stop routing, same as the monolithic server (OPERATIONS.md §12)
        sv = self.slo.verdict()
        detail["slo"] = sv
        healthy = healthy and sv["ok"]
        return healthy, detail

    def start_metrics(self, port: int, host: str = "127.0.0.1",
                      stall_threshold: float = 30.0) -> int:
        """Serve /metrics + /healthz for the engine tier; returns the
        bound port. The engine tier owns the device, so it owns the
        batch/round/stash telemetry — frontends export only their own
        session-layer registry."""
        from ..obs import MetricsServer

        lm = self.leakmon
        self._metrics_server = MetricsServer(
            self.engine.metrics.registry,
            health=lambda: self.healthz(stall_threshold),
            refresh=self.engine.sample_stash,
            host=host,
            port=port,
            leakaudit=lm.verdict if lm is not None else None,
            flightrec=lm.recorder.dump if lm is not None else None,
            trace=self.tracer.chrome_trace,
            profile=(self.profiler.capture if self.profiler is not None
                     else None),
        )
        return self._metrics_server.start()

    def stop(self, grace: float = 1.0, checkpoint: bool = False):
        """Drain the engine tier; with ``checkpoint`` seal the final
        state after the scheduler settles (the SIGTERM path)."""
        self._expiry_stop.set()
        if self._metrics_server is not None:
            self._metrics_server.stop()
            self._metrics_server = None
        self.listener.stop(grace)
        if self.shipper is not None:
            self.shipper.close()
        self.scheduler.close()
        if self.hostpipe is not None:
            self.hostpipe.close()
        if self.leakmon is not None:
            self.leakmon.close()
        if checkpoint:
            self.engine.checkpoint_now()
        self.engine.close()


class _EngineStub:
    """Scheduler-shaped adapter over the engine tier's SubmitBatch RPC,
    so the frontend can reuse GrapevineServer._query verbatim: handler
    threads hand their op to ``submit_nowait`` and wait on its future,
    and ONE sender thread ships what has gathered whenever it is free
    to send — at once when idle, as a batch of everything that arrived
    meanwhile when the last message was still out. No timer holds an op
    for company.

    Every RPC carries a deadline (a wedged engine must fail the clients'
    calls, not hang the frontend's handler threads forever), and
    UNAVAILABLE — the engine restarting, draining, or unreachable — is
    retried a bounded number of times with jittered exponential backoff,
    and only where no entry of the batch was admitted. Nothing else is
    retried: UNAUTHENTICATED / INVALID_ARGUMENT are deliberate
    rejections (retrying them re-spends a challenge), and
    DEADLINE_EXCEEDED is ambiguous — the ops may have committed, and
    SubmitBatch is not idempotent."""

    def __init__(self, address: str, deadline_s: float = 30.0,
                 max_retries: int = 3, backoff_s: float = 0.05,
                 backoff_cap_s: float = 2.0):
        self._grpc = grpc.insecure_channel(address, options=_CHANNEL_OPTIONS)
        identity = lambda b: b  # noqa: E731
        self._rpc = self._grpc.unary_unary(
            f"/{ENGINE_SERVICE_NAME}/{ENGINE_METHOD}",
            request_serializer=identity, response_deserializer=identity,
        )
        self.deadline_s = deadline_s
        self.max_retries = max_retries
        self.backoff_s = backoff_s
        self.backoff_cap_s = backoff_cap_s
        self._c_retries = self._c_batches = self._c_ops = None
        #: ((packed request, challenge), future) of the ops that arrived
        #: since the sender last took the list
        self._gathered: list[tuple[tuple[bytes, bytes], Future]] = []
        self._cv = threading.Condition()
        self._closed = False
        self._sender = threading.Thread(target=self._send_loop, daemon=True)
        self._sender.start()

    def bind_registry(self, registry) -> None:
        """Register the stub's counters on the frontend's telemetry
        registry (counts only — batch-level by construction)."""
        self._c_retries = registry.counter(
            "grapevine_engine_rpc_retries_total",
            "engine-tier SubmitBatch RPCs retried after UNAVAILABLE",
        )
        self._c_batches = registry.counter(
            "grapevine_engine_rpc_batches_total",
            "SubmitBatch messages this frontend sent (retries excluded)",
        )
        self._c_ops = registry.counter(
            "grapevine_engine_rpc_ops_total",
            "ops those messages carried (ops per batch = ops / batches)",
        )

    def submit(self, req: QueryRequest, auth=None) -> QueryResponse:
        return self.submit_nowait(req, auth).result()

    def submit_nowait(self, req: QueryRequest, auth=None) -> Future:
        """Hand one op to the sender; the Future resolves to its
        QueryResponse or raises AuthFailure / SchedulerShutdown (the
        engine is draining) / the RPC's error, as the scheduler's own
        would. ``settled_at`` on it is when the engine's answer reached
        this process."""
        entry = req.pack(), auth[2] if auth else bytes(C.CHALLENGE_SIZE)
        fut: Future = Future()
        with self._cv:
            if self._closed:
                raise SchedulerShutdown("engine stub closed")
            self._gathered.append((entry, fut))
            self._cv.notify()
        return fut

    def _send_loop(self):
        while True:
            with self._cv:
                while not self._gathered and not self._closed:
                    self._cv.wait()
                if not self._gathered:
                    return
                batch, self._gathered = self._gathered, []
            try:
                self._ship(batch)
            except Exception as exc:  # noqa: BLE001 — answer, never strand
                if not isinstance(exc, grpc.RpcError):
                    log.exception("engine SubmitBatch: the sender failed")
                for _, fut in batch:
                    if not fut.done():
                        fut.set_exception(exc)

    def _ship(self, batch) -> None:
        """One message and its answers; on UNAVAILABLE the same entries
        again, while none of them was admitted."""
        if self._c_batches is not None:
            self._c_batches.inc()
            self._c_ops.inc(len(batch))
        message = pack_batch([entry for entry, _ in batch])
        attempt = 0
        while True:
            refused, why = batch, None
            try:
                data = self._rpc(message, timeout=self.deadline_s)
            except grpc.RpcError as e:
                if e.code() != grpc.StatusCode.UNAVAILABLE:
                    raise
                why = e
            else:
                refused = self._answer(batch, unpack_answers(data, len(batch)))
            if not refused:
                return
            if len(refused) < len(batch) or attempt >= self.max_retries:
                # part of the batch was admitted, or the retries are
                # spent: the rest is told what the scheduler would say
                exc = why or SchedulerShutdown("engine tier draining")
                for _, fut in refused:
                    fut.set_exception(exc)
                return
            attempt += 1
            if self._c_retries is not None:
                self._c_retries.inc()
            delay = min(
                self.backoff_cap_s,
                self.backoff_s * (2 ** (attempt - 1)),
            ) * random.uniform(0.5, 1.5)
            log.warning(
                "engine SubmitBatch UNAVAILABLE (%s); retry %d/%d in %.0f ms",
                why.details() if why else "draining", attempt,
                self.max_retries, delay * 1e3,
            )
            time.sleep(delay)

    @staticmethod
    def _answer(batch, answers) -> list:
        """Settle every entry the engine answered for good; returns the
        entries it did not admit (UNAVAILABLE)."""
        refused = []
        # one stamp for the message's answers, left on each future: what
        # a handler waits after it is the wake-up, as on the monolithic
        # server (scheduler._settle)
        t_settled = time.perf_counter()
        for entry, (status, body) in zip(batch, answers):
            fut = entry[1]
            fut.settled_at = t_settled
            if status == ENTRY_OK:
                fut.set_result(QueryResponse.unpack(body))
            elif status == ENTRY_UNAVAILABLE:
                refused.append(entry)
            elif status == ENTRY_UNAUTHENTICATED:
                fut.set_exception(AuthFailure("bad challenge signature"))
            else:
                fut.set_exception(
                    ValueError("the engine refused the entry as malformed"))
        return refused

    def close(self):
        """Stop the sender once it has shipped what gathered, then fail
        anything that raced the close."""
        with self._cv:
            self._closed = True
            self._cv.notify()
        self._sender.join(timeout=self.deadline_s)
        with self._cv:
            stranded, self._gathered = self._gathered, []
        for _, fut in stranded:
            fut.set_exception(SchedulerShutdown("engine stub closed"))
        self._grpc.close()


class FrontendServer:
    """A client-facing session-termination process.

    Byte-identical public surface to the monolithic ``GrapevineServer``
    (Auth + Query, IX handshake, AEAD, lockstep, validation) — but ops
    go to a shared engine tier instead of an in-process engine. Run N
    of these behind a load balancer; each is one CPython process of
    session crypto, and the engine batches across all of them.
    """

    def __init__(self, engine_address: str, config: GrapevineConfig | None = None,
                 attestation=None, clock=None, session_ttl: float = 3600.0,
                 max_sessions: int = 4096, identity=None,
                 host_workers: int = 0, worker_restart: bool = False):
        from .service import GrapevineServer

        # The monolithic server with its scheduler swapped for the
        # engine-tier RPC stub (GrapevineServer's injected-scheduler
        # mode): every session/auth behavior and its tests carry over
        # unchanged, and there is no device engine in this process.
        # ``host_workers`` is where the multiprocess verify/codec
        # pipeline pays off most: the frontend IS the host-crypto tier,
        # so its sessions fan out across worker processes while the
        # engine tier keeps the device.
        stub = _EngineStub(engine_address)
        self._inner = GrapevineServer(
            config=config,
            attestation=attestation,
            clock=clock,
            session_ttl=session_ttl,
            max_sessions=max_sessions,
            identity=identity,
            scheduler=stub,
            host_workers=host_workers,
            worker_restart=worker_restart,
        )
        stub.bind_registry(self._inner.metrics_registry)

    def start(self, listen_uri, tls_cert: bytes | None = None,
              tls_key: bytes | None = None) -> int:
        # expiry sweeps run in the ENGINE process; never start one here
        # (GrapevineServer.start already skips them when engine is None)
        return self._inner.start(listen_uri, tls_cert, tls_key)

    @property
    def identity(self):
        return self._inner.identity

    def health(self) -> dict:
        return self._inner.health()

    def start_metrics(self, port: int, host: str = "127.0.0.1",
                      stall_threshold: float = 30.0) -> int:
        # the frontend's registry carries session-layer telemetry only;
        # round/stash metrics live on the engine tier's endpoint
        return self._inner.start_metrics(port, host, stall_threshold)

    def wait(self):
        self._inner.wait()

    def stop(self, grace: float = 1.0):
        self._inner.stop(grace)
