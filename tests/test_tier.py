"""Split frontend/engine tier (server/tier.py): N session-termination
processes sharing ONE device engine — the horizontal host-path
architecture PERF.md's 1M ops/s budget relies on. In-process here
(separate gRPC servers on loopback), process-separated in deployment;
the wire between tiers is identical either way: ops reach the engine in
batches (``EngineAPI/SubmitBatch``), one message per frontend carrying
what gathered there. The plain reference (``testing/reference.py``) is
independent of the tier: every round the engine ran is replayed on it in
the engine's own slot order."""

from __future__ import annotations

import bisect
import random
import struct
import threading
import time
from collections import Counter

import grpc
import pytest

from grapevine_tpu.config import GrapevineConfig
from grapevine_tpu.server.client import GrapevineClient
from grapevine_tpu.server.scheduler import BatchScheduler, SchedulerShutdown
from grapevine_tpu.server.tier import (
    ENGINE_METHOD,
    ENGINE_SERVICE_NAME,
    ENTRY_INVALID,
    ENTRY_OK,
    ENTRY_UNAUTHENTICATED,
    ENTRY_UNAVAILABLE,
    EngineListener,
    EngineServer,
    FrontendServer,
    pack_batch,
    unpack_answers,
)
from grapevine_tpu.session import get_signature_scheme
from grapevine_tpu.testing.reference import ReferenceEngine
from grapevine_tpu.wire import constants as C
from grapevine_tpu.wire.records import (
    QueryRequest,
    QueryResponse,
    RequestRecord,
)

NOW = 1_700_000_000
SCHEME = get_signature_scheme("schnorrkel")
#: the toy geometry of the benchmark's rehearsal: 2^10 messages, B=16
CFG = GrapevineConfig(max_messages=1024, max_recipients=64, batch_size=16,
                      bucket_cipher_rounds=0)
#: the mix of the cell ``backlog-grpc-1chip`` (``backlog-mixed``'s)
MIX = (("create", 0.40), ("read_id", 0.15), ("read_next", 0.10),
       ("update", 0.13), ("delete_id", 0.12), ("pop_next", 0.10))


class RoundRecorder:
    """Every round at the scheduler -> engine boundary, in the engine's
    order: requests in slot order and the responses they got. ``gate``
    holds a round at its dispatch while it is cleared."""

    def __init__(self, engine):
        self.rounds: list[dict] = []
        self.gate = threading.Event()
        self.gate.set()
        inner = engine.handle_queries_async
        rec = self

        class _Pending:
            def __init__(self, pending, entry):
                self._pending, self._entry = pending, entry

            def __getattr__(self, name):
                return getattr(self._pending, name)

            def resolve(self):
                self._entry["resps"] = self._pending.resolve()
                return self._entry["resps"]

        def recorded(reqs, now):
            rec.gate.wait(30)
            entry = {"reqs": list(reqs), "now": now, "resps": None}
            pending = inner(reqs, now)
            rec.rounds.append(entry)
            return _Pending(pending, entry)

        engine.handle_queries_async = recorded

    def replay(self) -> tuple[ReferenceEngine, dict[bytes, bytes]]:
        """All rounds so far on a fresh reference, each answer equal op
        for op; returns the reference and {packed request: packed
        answer} of what the engine gave."""
        ref = ReferenceEngine(CFG)
        given: dict[bytes, bytes] = {}
        for i, e in enumerate(self.rounds):
            assert e["resps"] is not None and len(e["resps"]) == len(e["reqs"])
            forced = [d.record.msg_id
                      if r.request_type == C.REQUEST_TYPE_CREATE
                      and d.status_code == C.STATUS_CODE_SUCCESS else None
                      for r, d in zip(e["reqs"], e["resps"])]
            want = ref.handle_batch(e["reqs"], e["now"], forced)
            for j, (r, d, w) in enumerate(zip(e["reqs"], e["resps"], want)):
                assert d.pack() == w.pack(), (i, j, r.request_type)
                assert r.pack() not in given, "an op ran twice"
                given[r.pack()] = d.pack()
        return ref, given


@pytest.fixture(scope="module")
def tier():
    engine = EngineServer(CFG, seed=5, clock=lambda: NOW)
    recorder = RoundRecorder(engine.engine)
    eport = engine.start("127.0.0.1:0")
    fe_a = FrontendServer(f"127.0.0.1:{eport}", config=CFG)
    fe_b = FrontendServer(f"127.0.0.1:{eport}", config=CFG)
    pa = fe_a.start("insecure-grapevine://127.0.0.1:0")
    pb = fe_b.start("insecure-grapevine://127.0.0.1:0")
    yield {"engine": engine, "eport": eport, "pa": pa, "pb": pb,
           "frontends": (fe_a, fe_b), "recorder": recorder}
    fe_a.stop()
    fe_b.stop()
    engine.stop()


def _raw_rpc(port):
    chan = grpc.insecure_channel(f"127.0.0.1:{port}")
    identity = lambda b: b  # noqa: E731
    return chan, chan.unary_unary(
        f"/{ENGINE_SERVICE_NAME}/{ENGINE_METHOD}",
        request_serializer=identity, response_deserializer=identity,
    )


_challenge_counter = iter(range(1, 1 << 30))


def _signed(seed_byte: int, request_type=C.REQUEST_TYPE_CREATE,
            recipient=None, payload_byte=0, msg_id=C.ZERO_MSG_ID):
    """(packed request, challenge) as a frontend would forward them; the
    challenge is fresh, so no two packed requests are equal."""
    sk, pub = SCHEME.keygen(bytes([seed_byte]) * 32)
    challenge = struct.pack("<Q", next(_challenge_counter)) * 4
    req = QueryRequest(
        request_type=request_type, auth_identity=pub,
        auth_signature=SCHEME.sign(
            sk, C.GRAPEVINE_CHALLENGE_SIGNING_CONTEXT, challenge),
        record=RequestRecord(
            msg_id=msg_id, recipient=pub if recipient is None else recipient,
            payload=bytes([payload_byte]) * C.PAYLOAD_SIZE))
    return req.pack(), challenge


def _counter(registry, name) -> float:
    return registry.get(name).get()


def test_cross_frontend_crud(tier):
    """Alice on frontend A, Bob on frontend B, one engine: the full
    CRUD contract holds across the tier split."""
    alice = GrapevineClient(
        f"insecure-grapevine://127.0.0.1:{tier['pa']}", identity_seed=b"\x41" * 32
    )
    bob = GrapevineClient(
        f"insecure-grapevine://127.0.0.1:{tier['pb']}", identity_seed=b"\x42" * 32
    )
    alice.auth()
    bob.auth()
    payload = b"tiered".ljust(C.PAYLOAD_SIZE, b"\x00")
    r1 = alice.create(bob.public_key, payload)
    assert r1.status_code == C.STATUS_CODE_SUCCESS
    r2 = bob.read(msg_id=r1.record.msg_id)
    assert r2.status_code == C.STATUS_CODE_SUCCESS
    assert r2.record.payload == payload
    assert r2.record.sender == alice.public_key
    r3 = bob.delete(msg_id=r1.record.msg_id, recipient=bob.public_key)
    assert r3.status_code == C.STATUS_CODE_SUCCESS
    r4 = alice.read(msg_id=r1.record.msg_id)
    assert r4.status_code == C.STATUS_CODE_NOT_FOUND


def test_forged_signature_rejected_at_engine(tier):
    """The sr25519 check lives in the ENGINE tier: a frontend session
    whose client signs garbage gets UNAUTHENTICATED end to end."""
    mallory = GrapevineClient(
        f"insecure-grapevine://127.0.0.1:{tier['pa']}", identity_seed=b"\x66" * 32
    )
    mallory.auth()
    scheme = mallory._scheme

    class Forged:
        keygen = staticmethod(scheme.keygen)

        @staticmethod
        def sign(sk, ctx, msg):
            return b"\x01" * 63 + b"\x81"  # marked, bogus

    mallory._scheme = Forged
    try:
        with pytest.raises(grpc.RpcError) as ei:
            mallory.create(b"\x05" * 32, b"\x00" * C.PAYLOAD_SIZE)
        assert ei.value.code() == grpc.StatusCode.UNAUTHENTICATED
    finally:
        mallory._scheme = scheme
    # the session survives? No: the lockstep challenge advanced on both
    # sides (draw happens before verification), so the NEXT request
    # still verifies — same behavior as the monolithic server.
    r = mallory.create(b"\x05" * 32, b"\x01" * C.PAYLOAD_SIZE)
    assert r.status_code == C.STATUS_CODE_SUCCESS


def test_engine_rejects_malformed_submit(tier):
    """Direct internal-API misuse fails closed: a message whose framing
    cannot be read is INVALID_ARGUMENT as a whole (no entry of it can be
    told from the next)."""
    chan, submit = _raw_rpc(tier["eport"])
    one = C.QUERY_REQUEST_WIRE_SIZE + C.CHALLENGE_SIZE
    for bad in (b"", b"\x00" * 10, b"\xff" * (one + 3),
                struct.pack("<I", 2) + bytes(one),    # says 2, holds 1
                struct.pack("<I", 1) + bytes(one + 1),
                bytes(one)):                          # the per-op format
        with pytest.raises(grpc.RpcError) as ei:
            submit(bad, timeout=10)
        assert ei.value.code() == grpc.StatusCode.INVALID_ARGUMENT
    assert submit(struct.pack("<I", 0), timeout=10) == b""  # empty batch
    chan.close()


def test_rounds_batch_across_frontends(tier):
    """Ops arriving via different frontends share engine rounds: the
    round counter grows by less than one round per op under concurrent
    cross-frontend load (quiescence batching at the engine)."""
    eng = tier["engine"].engine
    rounds0 = eng.metrics.snapshot()["rounds"]
    clients = []
    for i, port in ((0, tier["pa"]), (1, tier["pb"]), (2, tier["pa"]), (3, tier["pb"])):
        c = GrapevineClient(
            f"insecure-grapevine://127.0.0.1:{port}",
            identity_seed=bytes([0x70 + i]) * 32,
        )
        c.auth()
        clients.append(c)
    n_each = 6
    errs = []

    def run(c):
        try:
            for j in range(n_each):
                r = c.create(c.public_key, bytes([j]) * C.PAYLOAD_SIZE)
                assert r.status_code == C.STATUS_CODE_SUCCESS
        except Exception as e:  # pragma: no cover
            errs.append(e)

    ts = [threading.Thread(target=run, args=(c,)) for c in clients]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs
    n_ops = n_each * len(clients)
    rounds = eng.metrics.snapshot()["rounds"] - rounds0
    assert 0 < rounds < n_ops, (rounds, n_ops)


def test_engine_submit_fuzz_fail_closed(tier):
    """Random and mutated submissions to the internal API must fail
    closed — the whole message INVALID_ARGUMENT, or every entry of it
    INVALID_ARGUMENT / UNAUTHENTICATED — never crash the engine tier or
    commit an op."""
    import os

    eng = tier["engine"].engine
    msgs0 = eng.message_count()
    chan, submit = _raw_rpc(tier["eport"])
    rng = random.Random(99)
    one = C.QUERY_REQUEST_WIRE_SIZE + C.CHALLENGE_SIZE
    for i in range(40):
        kind = rng.randrange(4)
        n = rng.randrange(1, 4)
        if kind == 0:  # random bytes, random length
            data = os.urandom(rng.randrange(0, one * 2))
        elif kind == 1:  # well framed, random content (bad sig/type)
            data = struct.pack("<I", n) + os.urandom(n * one)
        elif kind == 2:  # well framed, zeroed (invalid request type)
            data = struct.pack("<I", n) + bytes(n * one)
        else:  # a count that lies about the length
            data = struct.pack("<I", n + rng.randrange(1, 9)) + bytes(n * one)
        try:
            out = submit(data, timeout=10)  # a hang must fail, not wedge
        except grpc.RpcError as e:
            assert e.code() == grpc.StatusCode.INVALID_ARGUMENT, (i, e.code())
            continue
        assert kind in (1, 2), f"fuzz case {i}: unframed bytes were taken"
        for status, body in unpack_answers(out, n):
            assert status in (ENTRY_INVALID, ENTRY_UNAUTHENTICATED), (i, status)
            assert body == bytes(C.QUERY_RESPONSE_WIRE_SIZE)
    assert eng.message_count() == msgs0  # nothing committed
    chan.close()


# -- the tier against the plain reference on the cell's traffic ---------


def _closed_loop_session(port, k, n_identities, n_ops, seed, pubs, errs,
                         answers):
    """One sticky session: one op outstanding, the next built when the
    last answer is opened; names only ids it created itself."""
    rng = random.Random(f"{seed}-session-{k}")
    edges, acc = [], 0.0
    for _, f in MIX:
        acc += f
        edges.append(acc)
    cum, acc = [], 0.0
    for i in range(n_identities):
        acc += 1.0 / (i + 1) ** 0.99
        cum.append(acc)
    try:
        cl = GrapevineClient(f"insecure-grapevine://127.0.0.1:{port}",
                             identity_seed=bytes([k % n_identities + 1]) * 32)
        cl.auth()
        mine: list[tuple[bytes, int]] = []
        for _ in range(n_ops):
            kind = MIX[min(bisect.bisect_right(edges, rng.random()), 5)][0]
            if kind not in ("create", "read_next", "pop_next") and not mine:
                kind = "create"
            payload = rng.randbytes(C.PAYLOAD_SIZE)
            if kind == "create":
                rcp = min(bisect.bisect_right(cum, rng.random() * acc),
                          n_identities - 1)
                r = cl.create(pubs[rcp], payload)
                if r.status_code == C.STATUS_CODE_SUCCESS:
                    mine.append((r.record.msg_id, rcp))
            elif kind == "read_id":
                r = cl.read(rng.choice(mine)[0])
            elif kind == "read_next":
                r = cl.read()
            elif kind == "update":
                mid, rcp = rng.choice(mine)
                r = cl.update(mid, pubs[rcp], payload)
            elif kind == "delete_id":
                mid, rcp = mine.pop(rng.randrange(len(mine)))
                r = cl.delete(mid, pubs[rcp])
            else:
                r = cl.delete()
            answers.append(r.pack())
        cl.close()
    except Exception as e:  # pragma: no cover
        errs.append((k, repr(e)))


def test_two_frontends_equal_the_reference_op_for_op(tier):
    """Two frontends, 64 closed-loop sessions, 2,048 ops of the cell's
    mix from a seed: every answer a client decrypted is the reference's
    answer to that op, replayed in the engine's slot order; message and
    recipient counts are equal; the 62-message cap is enforced. Under
    64 concurrent handlers ops reach the engine more than one a batch."""
    reg = tier["engine"].engine.metrics.registry
    b0 = _counter(reg, "grapevine_engine_submit_batches_total")
    o0 = _counter(reg, "grapevine_engine_submit_ops_total")
    n_sessions, n_identities, n_each = 64, 16, 32
    pubs = [SCHEME.keygen(bytes([i + 1]) * 32)[1] for i in range(n_identities)]
    errs: list = []
    answers: list[bytes] = []
    threads = [threading.Thread(
        target=_closed_loop_session,
        args=(tier["pa" if k % 2 == 0 else "pb"], k, n_identities, n_each,
              2**31 + 7, pubs, errs, answers)) for k in range(n_sessions)]
    # handler threads and each frontend's sender share its gathered
    # list: a short switch interval makes a lost or doubled op likely
    # to show (it would break the one-for-one equality below)
    import sys

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(600)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errs, errs[:3]
    assert len(answers) == n_sessions * n_each >= 2000
    ref, given = tier["recorder"].replay()
    # what the clients decrypted is what the engine gave, one for one
    assert not Counter(answers) - Counter(given.values())
    engine = tier["engine"].engine
    assert engine.message_count() == ref.message_count()
    assert engine.recipient_count() == ref.recipient_count()
    full = sum(
        d.status_code == C.STATUS_CODE_TOO_MANY_MESSAGES_FOR_RECIPIENT
        for e in tier["recorder"].rounds for d in e["resps"])
    assert full > 0, "the hot mailboxes never reached the cap"
    assert max(len(b) for b in ref.mailboxes.values()) == CFG.mailbox_cap
    batches = _counter(reg, "grapevine_engine_submit_batches_total") - b0
    ops = _counter(reg, "grapevine_engine_submit_ops_total") - o0
    assert ops == n_sessions * n_each and ops / batches > 1, (ops, batches)
    for fe in tier["frontends"]:
        freg = fe._inner.metrics_registry
        assert (_counter(freg, "grapevine_engine_rpc_ops_total")
                > _counter(freg, "grapevine_engine_rpc_batches_total") > 0)
        # the frontend's stage counters are split as the monolithic
        # server's are: the stub is future-shaped and stamps the settle
        secs = freg.get("grapevine_service_seconds_total")
        assert all(secs.get(phase=p) > 0
                   for p in ("open", "wait", "wake", "seal"))


def test_a_lone_op_is_a_batch_of_one_and_is_not_held(tier):
    """An op that arrives at an idle frontend leaves at once: one
    message of one entry, no timer waiting for company."""
    freg = tier["frontends"][0]._inner.metrics_registry
    reg = tier["engine"].engine.metrics.registry

    def counts():
        return [_counter(freg, "grapevine_engine_rpc_batches_total"),
                _counter(freg, "grapevine_engine_rpc_ops_total"),
                _counter(reg, "grapevine_engine_submit_batches_total"),
                _counter(reg, "grapevine_engine_submit_ops_total")]

    cl = GrapevineClient(f"insecure-grapevine://127.0.0.1:{tier['pa']}",
                         identity_seed=b"\x51" * 32)
    cl.auth()
    cl.read()  # the session's first Query, off the clock
    before = counts()
    window = tier["engine"].scheduler.max_wait
    t0 = time.perf_counter()
    r = cl.read()
    dt = time.perf_counter() - t0
    assert r.status_code == C.STATUS_CODE_NOT_FOUND
    after = counts()
    assert [a - b for a, b in zip(after, before)] == [1, 1, 1, 1]
    assert _counter(reg, "grapevine_engine_ingress_seconds_total") > 0
    # generous: the scheduler's own window plus a toy round on a loaded
    # CPU; a sender waiting for company would have no bound at all
    assert dt < window + 5.0
    cl.close()


def _statuses(out: bytes, n: int) -> list[int]:
    return [s for s, _ in unpack_answers(out, n)]


@pytest.mark.parametrize("case", ["one_forged", "one_malformed",
                                  "larger_than_a_round", "batch_of_one",
                                  "shutdown_with_a_batch_queued"])
def test_batch_semantics(tier, case):
    """Per entry, as it was per RPC: a bad entry fails alone, every
    other entry is answered as the reference answers it, and no entry is
    reported twice or half."""
    recorder = tier["recorder"]
    engine = tier["engine"]
    chan, submit = _raw_rpc(tier["eport"])
    rounds0 = len(recorder.rounds)
    auth0 = _counter(engine.engine.metrics.registry,
                     "grapevine_auth_failures_total")
    want_status: list[int]
    if case == "one_forged":
        entries = [_signed(0x20 + i, payload_byte=i) for i in range(5)]
        req, challenge = entries[2]
        entries[2] = (req, bytes(32))  # signed another challenge
        want_status = [ENTRY_OK] * 5
        want_status[2] = ENTRY_UNAUTHENTICATED
    elif case == "one_malformed":
        entries = [_signed(0x30 + i, payload_byte=i) for i in range(5)]
        req, challenge = entries[3]
        entries[3] = (struct.pack("<I", 9) + req[4:], challenge)  # no such op
        want_status = [ENTRY_OK] * 5
        want_status[3] = ENTRY_INVALID
    elif case == "larger_than_a_round":
        entries = [_signed(0x40 + i % 40, payload_byte=i)
                   for i in range(2 * CFG.batch_size + 5)]
        want_status = [ENTRY_OK] * len(entries)
    elif case == "batch_of_one":
        entries = [_signed(0x61)]
        want_status = [ENTRY_OK]
    else:
        # a second scheduler on the same engine, its listener beside the
        # tier's: the first message is held at its dispatch, the second
        # waits in the queue, and the scheduler is closed under both
        sched = BatchScheduler(engine.engine, clock=lambda: NOW,
                               scheme=SCHEME)
        from grapevine_tpu.obs import TelemetryRegistry

        listener = EngineListener(sched, TelemetryRegistry())
        chan2, submit2 = _raw_rpc(listener.start("127.0.0.1:0"))
        recorder.gate.clear()
        held = [_signed(0x70 + i, payload_byte=i) for i in range(3)]
        queued = [_signed(0x78 + i, payload_byte=i) for i in range(3)]
        out: dict = {}
        t1 = threading.Thread(target=lambda: out.update(
            held=submit2(pack_batch(held), timeout=60)))
        t1.start()
        deadline = time.monotonic() + 30
        while sched._queue or not sched._inflight:  # the collector took it
            assert time.monotonic() < deadline
            time.sleep(0.01)
        t2 = threading.Thread(target=lambda: out.update(
            queued=submit2(pack_batch(queued), timeout=60)))
        t2.start()
        while len(sched._queue) < 3:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        closer = threading.Thread(target=sched.close)
        closer.start()
        t2.join(30)
        recorder.gate.set()
        t1.join(30)
        closer.join(30)
        assert _statuses(out["queued"], 3) == [ENTRY_UNAVAILABLE] * 3
        # a closed scheduler admits nothing: the whole message at once
        late = submit2(pack_batch([_signed(0x7f)]), timeout=10)
        assert _statuses(late, 1) == [ENTRY_UNAVAILABLE]
        listener.stop()
        chan2.close()
        entries, want_status = held, [ENTRY_OK] * 3
        raw = out["held"]
    if case != "shutdown_with_a_batch_queued":
        raw = submit(pack_batch(entries), timeout=120)
    answered = unpack_answers(raw, len(entries))  # each entry once, whole
    assert [s for s, _ in answered] == want_status
    _, given = recorder.replay()  # every round equals the reference
    new_ops = sum(len(e["reqs"]) for e in recorder.rounds[rounds0:])
    assert new_ops == want_status.count(ENTRY_OK)
    for (req, _), (status, body) in zip(entries, answered):
        if status == ENTRY_OK:
            assert body == given[req]  # and this entry got its own answer
        else:
            assert body == bytes(C.QUERY_RESPONSE_WIRE_SIZE)
            assert req not in given  # it never reached the engine
    if case == "larger_than_a_round":
        assert len(recorder.rounds) - rounds0 >= 3
    forged = want_status.count(ENTRY_UNAUTHENTICATED)
    assert _counter(engine.engine.metrics.registry,
                    "grapevine_auth_failures_total") - auth0 == forged
    chan.close()


class _EchoEngine:
    """No JAX: answers each op with its own payload, and remembers which
    ops shared a round."""

    class ecfg:
        batch_size = 16

    def __init__(self):
        from grapevine_tpu.engine.metrics import EngineMetrics
        from grapevine_tpu.obs.workload import WorkloadTelemetry

        self.metrics = EngineMetrics()
        self.workload = WorkloadTelemetry(self.metrics.registry, 16)
        self.rounds: list[list[bytes]] = []

    def handle_queries_async(self, reqs, now):
        from grapevine_tpu.wire.records import Record

        self.rounds.append([r.record.payload[:2] for r in reqs])
        resps = [QueryResponse(
            record=Record(msg_id=C.ZERO_MSG_ID, sender=C.ZERO_PUBKEY,
                          recipient=C.ZERO_PUBKEY, timestamp=1,
                          payload=r.record.payload),
            status_code=C.STATUS_CODE_SUCCESS) for r in reqs]

        class _Pending:
            def resolve(self):
                return resps

        return _Pending()


def test_submit_many_equals_n_submit_nowait():
    """One call for N items is N calls for one: the same futures in
    order, the same stamps, the same round membership, the same
    arrival and back-pressure counts."""
    n = 40
    reqs = [QueryRequest(
        request_type=C.REQUEST_TYPE_READ, auth_identity=b"\x01" * 32,
        auth_signature=b"\x02" * C.SIGNATURE_SIZE,
        record=RequestRecord(payload=struct.pack("<H", i)
                             + bytes(C.PAYLOAD_SIZE - 2)))
        for i in range(n)]
    seen = {}
    for how in ("many", "each"):
        eng = _EchoEngine()
        # a window that outlasts the submissions: what joins, joins whole
        sched = BatchScheduler(eng, max_wait_ms=3000.0, idle_gap_ms=500.0)
        try:
            with sched._cv:  # the collector waits: the queue is ours
                t0 = time.perf_counter()
                if how == "many":
                    futs = sched.submit_many([(r, None) for r in reqs])
                else:
                    futs = [sched.submit_nowait(r) for r in reqs]
                t1 = time.perf_counter()
                stamps = [t for _, _, _, t in sched._queue]
                assert [f for _, _, f, _ in sched._queue] == futs
                head, last = sched._head_enqueue, sched._last_enqueue
            resps = [f.result(timeout=30) for f in futs]
        finally:
            sched.close()
        assert all(t0 <= t <= t1 for t in stamps) and stamps == sorted(stamps)
        assert head == stamps[0] and last == stamps[-1]
        assert all(hasattr(f, "settled_at") for f in futs)
        reg = eng.metrics.registry
        seen[how] = {
            "answers": [r.record.payload[:2] for r in resps],
            "rounds": eng.rounds,
            "arrivals": _counter(reg, "grapevine_load_arrivals_total"),
            "backpressure": _counter(
                reg, "grapevine_load_backpressure_arrivals_total"),
        }
    assert seen["many"] == seen["each"]
    assert seen["many"]["answers"] == [struct.pack("<H", i) for i in range(n)]
    assert [len(r) for r in seen["many"]["rounds"]] == [16, 16, 8]
    assert seen["many"]["arrivals"] == n
    assert seen["many"]["backpressure"] == n - 16
    # and a closed scheduler refuses the call as it refuses one op
    with pytest.raises(SchedulerShutdown):
        sched.submit_many([(reqs[0], None)])
    assert sched.submit_many([]) == []


def test_a_frontend_process_starts_no_jax_backend():
    """A frontend owns no chip: beside an engine that holds one, a
    process that started a JAX backend would fail or hang. The frontend
    role, as the CLI builds it, must not even import jax or the engine
    (a backend that cannot be initialised proves it)."""
    import os
    import subprocess
    import sys

    script = (
        "import sys\n"
        "from grapevine_tpu.config import GrapevineConfig\n"
        "from grapevine_tpu.server.tier import FrontendServer\n"
        "fe = FrontendServer('127.0.0.1:1', config=GrapevineConfig())\n"
        "fe.start('insecure-grapevine://127.0.0.1:0')\n"
        "fe.start_metrics(0)\n"
        "fe.stop()\n"
        "print('jax' in sys.modules, 'grapevine_tpu.engine' in sys.modules)\n"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = subprocess.run(
        [sys.executable, "-c", script], cwd=root, capture_output=True,
        text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "no-such-backend"})
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.split() == ["False", "False"]


def test_engine_tier_runs_expiry_sweep():
    """The engine tier owns the device, so it owns the expiry sweep
    (the same run_expiry_loop the monolithic server uses)."""
    cfg = GrapevineConfig(
        max_messages=64, max_recipients=16, batch_size=4,
        bucket_cipher_rounds=0, expiry_period=10,
    )
    now = [1_700_000_000]
    engine = EngineServer(cfg, seed=9, clock=lambda: now[0])
    eport = engine.start("127.0.0.1:0")
    fe = FrontendServer(f"127.0.0.1:{eport}", config=cfg)
    port = fe.start("insecure-grapevine://127.0.0.1:0")
    try:
        c = GrapevineClient(
            f"insecure-grapevine://127.0.0.1:{port}", identity_seed=b"\x77" * 32
        )
        c.auth()
        r = c.create(c.public_key, b"\x05" * C.PAYLOAD_SIZE)
        assert r.status_code == C.STATUS_CODE_SUCCESS
        assert engine.engine.message_count() == 1
        now[0] += 1000  # all records now older than the period
        deadline = time.time() + 15  # sweep interval = period/10 = 1 s
        while engine.engine.message_count() and time.time() < deadline:
            time.sleep(0.25)
        assert engine.engine.message_count() == 0, "sweep never evicted"
    finally:
        fe.stop()
        engine.stop()
