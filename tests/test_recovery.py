"""Crash-safe engine: recovery bit-equality, graceful drain, RPC retry,
worker-crash handling, and the durability health surface.

The in-process half of the PR-4 acceptance: checkpoint → restore →
bit-identical state on tier-1; the SIGKILL half (randomized kill points,
multi-incarnation recovery, leakmon-PASS-across-recovery) lives in
tests/test_chaos_recovery.py and tools/chaos_run.py.
"""

import os
import shutil
import threading
import time

import grpc
import pytest

from grapevine_tpu.config import DurabilityConfig, GrapevineConfig
from grapevine_tpu.engine import checkpoint as cp
from grapevine_tpu.engine.batcher import GrapevineEngine
from grapevine_tpu.engine.metrics import EngineMetrics
from grapevine_tpu.server.scheduler import BatchScheduler, SchedulerShutdown
from grapevine_tpu.wire import constants as C
from grapevine_tpu.wire.records import (
    QueryRequest,
    QueryResponse,
    Record,
    RequestRecord,
)

NOW = 1_700_000_000

SMALL = GrapevineConfig(
    max_messages=64, max_recipients=8, mailbox_cap=4,
    batch_size=4, stash_size=64, bucket_cipher_rounds=0,
)


def _key(n: int) -> bytes:
    return bytes([n, n ^ 0x5A]) + b"\x01" * 30


def _req(rt, auth, recipient=C.ZERO_PUBKEY, tag=0):
    return QueryRequest(
        request_type=rt, auth_identity=auth,
        auth_signature=b"\x01" * C.SIGNATURE_SIZE,
        record=RequestRecord(
            msg_id=C.ZERO_MSG_ID, recipient=recipient,
            payload=bytes([tag & 0xFF]) * C.PAYLOAD_SIZE,
        ),
    )


def _drive(engine, n_events: int, t0=NOW):
    """Deterministic mixed workload: creates, zero-id reads, one sweep
    per 5 events."""
    import random

    rng = random.Random(17)
    out = []
    for i in range(n_events):
        if i % 5 == 3:
            engine.expire(t0 + i, period=10_000)
            continue
        reqs = []
        for _ in range(rng.randrange(1, SMALL.batch_size + 1)):
            if rng.random() < 0.6:
                reqs.append(_req(C.REQUEST_TYPE_CREATE,
                                 _key(rng.randrange(1, 5)),
                                 recipient=_key(rng.randrange(1, 5)),
                                 tag=rng.randrange(256)))
            else:
                reqs.append(_req(C.REQUEST_TYPE_READ,
                                 _key(rng.randrange(1, 5))))
        out.append([r.pack() for r in engine.handle_queries(reqs, t0 + i)])
    return out


@pytest.fixture(scope="module")
def durable_run(tmp_path_factory):
    """One durable run: 10 events (rounds + sweeps) with checkpoints
    every 4 records, cleanly closed. Yields (state_dir, final state
    bytes, journal seq) — the module's tests recover from copies."""
    state_dir = str(tmp_path_factory.mktemp("durable"))
    dcfg = DurabilityConfig(state_dir=state_dir, checkpoint_every_rounds=5)
    engine = GrapevineEngine(SMALL, seed=3, durability=dcfg)
    _drive(engine, 12)
    final = cp.state_to_bytes(engine.ecfg, engine.state)
    seq = engine.durability.seq
    ckpt_seq = engine.durability.ckpt_seq
    engine.close()
    assert ckpt_seq > 0, "cadence never checkpointed"
    assert seq > ckpt_seq, "fixture needs a journal tail to replay"
    return state_dir, final, seq


def _copy_dir(src: str, tmp_path) -> str:
    dst = str(tmp_path / "statedir")
    shutil.copytree(src, dst)
    return dst


def test_checkpoint_restore_state_bit_equality(durable_run, tmp_path):
    """The acceptance fast test: recovered state (checkpoint + replayed
    journal tail) is bit-identical to the uninterrupted engine's."""
    state_dir, final, seq = durable_run
    d = _copy_dir(state_dir, tmp_path)
    engine = GrapevineEngine(
        SMALL, seed=3,
        durability=DurabilityConfig(state_dir=d, checkpoint_every_rounds=4),
    )
    assert engine.durability.recovered_from_checkpoint
    assert engine.durability.replayed > 0, "journal tail was not replayed"
    assert engine.durability.seq == seq
    assert cp.state_to_bytes(engine.ecfg, engine.state) == final
    st = engine.durability.status()
    assert st["last_checkpoint_seq"] > 0
    assert st["last_durable_seq"] == seq
    engine.close()


def test_recovery_with_wrong_seed_still_bit_identical(durable_run, tmp_path):
    """The recovered state comes from disk, not from the init seed —
    restoring under a different seed must not matter."""
    state_dir, final, _ = durable_run
    d = _copy_dir(state_dir, tmp_path)
    engine = GrapevineEngine(
        SMALL, seed=999,
        durability=DurabilityConfig(state_dir=d, checkpoint_every_rounds=4),
    )
    assert cp.state_to_bytes(engine.ecfg, engine.state) == final
    engine.close()


def test_torn_journal_tail_recovers_to_previous_record(durable_run, tmp_path):
    """Truncating mid-way into the journal's final frame loses exactly
    that record (it never dispatched durably) — recovery succeeds at
    seq-1 and never half-applies the torn frame."""
    state_dir, _, seq = durable_run
    d = _copy_dir(state_dir, tmp_path)
    segs = [n for n in os.listdir(d) if n.endswith(".wal")]
    assert len(segs) == 1
    path = os.path.join(d, segs[0])
    size = os.path.getsize(path)
    with open(path, "r+b") as fh:
        fh.truncate(size - 30)  # inside the final frame's tag
    engine = GrapevineEngine(
        SMALL, seed=3,
        durability=DurabilityConfig(state_dir=d, checkpoint_every_rounds=4),
    )
    assert engine.durability.seq == seq - 1
    engine.close()


def test_corrupt_checkpoint_rejected_never_half_loaded(durable_run, tmp_path):
    state_dir, _, _ = durable_run
    d = _copy_dir(state_dir, tmp_path)
    ckpt = next(n for n in os.listdir(d) if n.startswith("ckpt-"))
    path = os.path.join(d, ckpt)
    blob = bytearray(open(path, "rb").read())
    blob[len(blob) // 2] ^= 0x10
    with open(path, "wb") as fh:
        fh.write(bytes(blob))
    with pytest.raises(cp.CheckpointError, match="integrity"):
        GrapevineEngine(
            SMALL, seed=3,
            durability=DurabilityConfig(state_dir=d,
                                        checkpoint_every_rounds=4),
        )


def test_wrong_root_key_rejected(durable_run, tmp_path):
    state_dir, _, _ = durable_run
    d = _copy_dir(state_dir, tmp_path)
    with open(os.path.join(d, "root.key"), "wb") as fh:
        fh.write(b"\x42" * 32)
    with pytest.raises(cp.CheckpointError, match="integrity|root key"):
        GrapevineEngine(
            SMALL, seed=3,
            durability=DurabilityConfig(state_dir=d,
                                        checkpoint_every_rounds=4),
        )


def test_geometry_change_rejected(durable_run, tmp_path):
    state_dir, _, _ = durable_run
    d = _copy_dir(state_dir, tmp_path)
    bigger = GrapevineConfig(
        max_messages=128, max_recipients=8, mailbox_cap=4,
        batch_size=4, stash_size=64, bucket_cipher_rounds=0,
    )
    with pytest.raises(cp.CheckpointError, match="fingerprint"):
        GrapevineEngine(
            bigger, seed=3,
            durability=DurabilityConfig(state_dir=d,
                                        checkpoint_every_rounds=4),
        )


# -- graceful drain (scheduler close settles, never drops) --------------


class _StubEcfg:
    batch_size = 4


class _ZeroResponses:
    @staticmethod
    def make(n):
        zero = Record(
            msg_id=C.ZERO_MSG_ID, sender=C.ZERO_PUBKEY,
            recipient=C.ZERO_PUBKEY, timestamp=0,
            payload=b"\x00" * C.PAYLOAD_SIZE,
        )
        return [QueryResponse(record=zero, status_code=C.STATUS_CODE_SUCCESS)
                for _ in range(n)]


class _WedgedEngine:
    """Rounds wedge on resolve until released; ``settling`` fires when
    the collector has actually entered resolve() — the moment later
    submits are guaranteed to stay queued rather than dispatch."""

    def __init__(self):
        self.ecfg = _StubEcfg()
        self.metrics = EngineMetrics()
        self.release = threading.Event()
        self.settling = threading.Event()

    def handle_queries_async(self, reqs, now):
        resps = _ZeroResponses.make(len(reqs))
        release, settling = self.release, self.settling

        class _Pending:
            def resolve(self):
                settling.set()
                release.wait(timeout=30)
                return resps

        return _Pending()


def _submit_async(sched, results, idx):
    def run():
        try:
            results[idx] = sched.submit(_req(C.REQUEST_TYPE_READ, _key(1)))
        except BaseException as exc:  # noqa: BLE001 - recorded for asserts
            results[idx] = exc

    t = threading.Thread(target=run)
    t.start()
    return t


def test_close_settles_queued_ops_with_shutdown_error():
    eng = _WedgedEngine()
    sched = BatchScheduler(eng, max_wait_ms=30.0, idle_gap_ms=5.0)
    results: dict = {}
    try:
        t0 = _submit_async(sched, results, 0)  # dispatches, wedges
        assert eng.settling.wait(timeout=10), "round never reached resolve"
        # these arrive while the collector is blocked settling the
        # wedged round: queued, not yet dispatched when close() lands
        t1 = _submit_async(sched, results, 1)
        t2 = _submit_async(sched, results, 2)
        time.sleep(0.2)
        closer = threading.Thread(target=sched.close)
        closer.start()
        for t in (t1, t2):
            t.join(timeout=10)
        assert isinstance(results[1], SchedulerShutdown)
        assert isinstance(results[2], SchedulerShutdown)
        # the in-flight round still commits: drain settles, not drops
        eng.release.set()
        t0.join(timeout=10)
        closer.join(timeout=10)
        assert isinstance(results[0], QueryResponse)
        with pytest.raises(SchedulerShutdown):
            sched.submit(_req(C.REQUEST_TYPE_READ, _key(1)))
    finally:
        eng.release.set()
        sched.close()


# -- worker crash handling ----------------------------------------------


class _WorkerDeath(BaseException):
    """Escapes the dispatch path's ``except Exception`` defensive guard
    — the genuine worker-killing fault class (a bug in the collector
    itself, a KeyboardInterrupt, an interpreter-level error)."""


class _CrashOnceEngine:
    def __init__(self, crashes: int = 1):
        self.ecfg = _StubEcfg()
        self.metrics = EngineMetrics()
        self.crashes_left = crashes

    def handle_queries_async(self, reqs, now):
        if self.crashes_left:
            self.crashes_left -= 1
            raise _WorkerDeath("injected collector fault")

        resps = _ZeroResponses.make(len(reqs))

        class _Pending:
            def resolve(self):
                return resps

        return _Pending()


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning"
)
def test_worker_crash_counts_and_flips_alive():
    eng = _CrashOnceEngine(crashes=1)
    sched = BatchScheduler(eng, max_wait_ms=20.0, idle_gap_ms=5.0)
    with pytest.raises(RuntimeError, match="worker died"):
        sched.submit(_req(C.REQUEST_TYPE_READ, _key(1)))
    deadline = time.monotonic() + 5
    while sched.worker_alive() and time.monotonic() < deadline:
        time.sleep(0.01)
    # the healthz signal (worker_alive → unhealthy) flips immediately...
    assert not sched.worker_alive()
    # ...and the crash is counted on the telemetry registry
    assert eng.metrics.registry.get("grapevine_worker_crash_total").get() == 1
    with pytest.raises(SchedulerShutdown):
        sched.submit(_req(C.REQUEST_TYPE_READ, _key(1)))


def test_worker_restart_revives_collector():
    eng = _CrashOnceEngine(crashes=1)
    sched = BatchScheduler(eng, max_wait_ms=20.0, idle_gap_ms=5.0,
                           restart_on_crash=True)
    try:
        with pytest.raises(RuntimeError, match="worker died"):
            sched.submit(_req(C.REQUEST_TYPE_READ, _key(1)))
        # supervised restart: the collector revives and serves again
        deadline = time.monotonic() + 5
        resp = None
        while time.monotonic() < deadline:
            try:
                resp = sched.submit(_req(C.REQUEST_TYPE_READ, _key(1)))
                break
            except SchedulerShutdown:
                time.sleep(0.02)
        assert isinstance(resp, QueryResponse)
        assert sched.worker_alive()
        assert (
            eng.metrics.registry.get("grapevine_worker_crash_total").get()
            == 1
        )
    finally:
        sched.close()


# -- engine-tier stub: deadline + bounded UNAVAILABLE retry -------------


def test_engine_stub_retries_unavailable_only():
    from grapevine_tpu.obs import TelemetryRegistry
    from grapevine_tpu.server.tier import _EngineStub

    # an address nothing listens on: immediate UNAVAILABLE per attempt
    stub = _EngineStub("127.0.0.1:1", deadline_s=2.0, max_retries=2,
                       backoff_s=0.01, backoff_cap_s=0.02)
    reg = TelemetryRegistry()
    stub.bind_registry(reg)
    t0 = time.monotonic()
    with pytest.raises(grpc.RpcError) as exc_info:
        stub.submit(_req(C.REQUEST_TYPE_READ, _key(1)))
    assert exc_info.value.code() == grpc.StatusCode.UNAVAILABLE
    assert time.monotonic() - t0 < 30
    assert reg.get("grapevine_engine_rpc_retries_total").get() == 2
    stub.close()


def test_engine_tier_drain_maps_to_unavailable_and_health_surfaces():
    pytest.importorskip("grpc")
    from grapevine_tpu.server.tier import EngineServer, _EngineStub

    import tempfile

    with tempfile.TemporaryDirectory() as d:
        server = EngineServer(
            SMALL, seed=0,
            durability=DurabilityConfig(state_dir=d,
                                        checkpoint_every_rounds=8),
        )
        port = server.start("127.0.0.1:0")
        healthy, detail = server.healthz()
        assert healthy
        assert detail["durability"]["last_checkpoint_seq"] == 0
        assert detail["durability"]["last_durable_seq"] == 0
        # drain: close the scheduler, then every entry of a batch is
        # answered UNAVAILABLE; the stub retries (nothing was admitted)
        # and then says what the scheduler itself would: the frontend's
        # Query handler maps SchedulerShutdown to gRPC UNAVAILABLE
        server.scheduler.close()
        from grapevine_tpu.obs import TelemetryRegistry

        stub = _EngineStub(f"127.0.0.1:{port}", deadline_s=5.0,
                           max_retries=1, backoff_s=0.01)
        reg = TelemetryRegistry()
        stub.bind_registry(reg)
        with pytest.raises(SchedulerShutdown):
            stub.submit(_req(C.REQUEST_TYPE_READ, _key(1)))
        assert reg.get("grapevine_engine_rpc_retries_total").get() == 1
        stub.close()
        server.stop(checkpoint=True)
        # the final drain checkpoint sealed the (untouched) state
        assert any(n.startswith("ckpt-") for n in os.listdir(d))


# -- CLI role matrix for the durability flags ---------------------------


@pytest.mark.parametrize("argv", [
    ["--role", "frontend", "--engine", "h:1", "--state-dir", "/tmp/x"],
    ["--role", "frontend", "--engine", "h:1",
     "--journal-fsync-every", "4"],
    ["--role", "frontend", "--engine", "h:1",
     "--checkpoint-every-rounds", "8"],
])
def test_frontend_rejects_durability_flags(argv):
    from grapevine_tpu.server import cli

    parser = cli.build_parser()
    args = parser.parse_args(argv)
    with pytest.raises(SystemExit, match="does not take"):
        cli._reject_misapplied_flags(parser, args, argv)


@pytest.mark.parametrize("argv", [
    ["--role", "mono", "--state-dir", "/tmp/x", "--journal-fsync-every",
     "4", "--worker-restart"],
    ["--role", "engine", "--state-dir", "/tmp/x",
     "--checkpoint-every-rounds", "16", "--seal-key-file", "/tmp/k"],
])
def test_device_roles_accept_durability_flags(argv):
    from grapevine_tpu.server import cli

    parser = cli.build_parser()
    args = parser.parse_args(argv)
    cli._reject_misapplied_flags(parser, args, argv)  # no raise


def test_durability_config_validation():
    with pytest.raises(ValueError):
        DurabilityConfig(state_dir="")
    with pytest.raises(ValueError):
        DurabilityConfig(state_dir="/tmp/x", checkpoint_every_rounds=0)
    with pytest.raises(ValueError):
        DurabilityConfig(state_dir="/tmp/x", journal_fsync_every=0)
