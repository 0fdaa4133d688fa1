"""The durable deployment at the size it is for (engine/checkpoint.py,
engine/journal.py, engine/batcher.py ``recover`` / ``abandon``): the
native seal pinned byte for byte to the numpy reference, a checkpoint
written and read as a stream (the same bytes as the one-piece
reference, the host holding a few blocks, the device one state), and
the sentence durability adds to the oracle: *the state after a crash and
a restart answers as the oracle that saw every acknowledged round*, with
its control, a recovery that lost acknowledged rounds, caught.

The oracle is the benchmark's (``benchmarks/lib/oracle.py`` through
``compare.replay``), the one the cell ``backlog-durable-1chip-2p21`` is
judged by.
"""

import hashlib
import hmac
import os
import struct
import tracemalloc

import jax
import numpy as np
import pytest

from benchmarks.lib import compare
from grapevine_tpu import native
from grapevine_tpu.config import DurabilityConfig, GrapevineConfig
from grapevine_tpu.engine import checkpoint as cp
from grapevine_tpu.engine import journal as jr
from grapevine_tpu.engine.batcher import GrapevineEngine
from grapevine_tpu.engine.state import EngineConfig, init_engine
from grapevine_tpu.wire import constants as C
from grapevine_tpu.wire.records import QueryRequest, RequestRecord

ROOT = bytes(range(32))
NONCE = bytes(range(100, 112))
HEAD = cp.MAGIC + struct.pack("<I", cp.VERSION)

#: 4.4 MB of state, one plane of 4.2 MB: blocks of 64 KiB cross it 64
#: times and every other leaf is smaller than one
TOY = GrapevineConfig(max_messages=1024, max_recipients=64, mailbox_cap=4,
                      batch_size=8, stash_size=64)
BLOCK = 1 << 16

needs_native = pytest.mark.skipif(
    native.lib is None, reason=f"no native library: {native.load_error}")


@pytest.fixture
def pinned_nonce(monkeypatch):
    """Every seal of the test draws ``NONCE``."""
    monkeypatch.setattr(os, "urandom", lambda n: NONCE[:n])


def _block_bytes(monkeypatch, block) -> None:
    if block is not None:
        monkeypatch.setattr(cp, "STREAM_BLOCK_BYTES", block)


def _reference_seal(domain: bytes, data: bytes, aad: bytes) -> bytes:
    """``seal`` as OPERATIONS.md section 11 states it, in one piece, with
    the numpy stream: ``nonce | ChaCha20(ct) | HMAC-SHA256(aad | nonce |
    ct)`` under the domain's subkeys of the root key."""
    enc, mac = cp._seal_keys(ROOT, domain)
    ct = cp.chacha20_xor(enc, NONCE, data)
    return NONCE + ct + hmac.new(mac, aad + NONCE + ct,
                                 hashlib.sha256).digest()


def _reference_unseal(domain: bytes, blob: bytes, aad: bytes) -> bytes:
    enc, mac = cp._seal_keys(ROOT, domain)
    nonce, ct, tag = blob[:12], blob[12:-32], blob[-32:]
    assert hmac.compare_digest(
        tag, hmac.new(mac, aad + nonce + ct, hashlib.sha256).digest())
    return cp.chacha20_xor(enc, nonce, ct)


def _data(n: int) -> bytes:
    return bytes((i * 131 + 7) & 0xFF for i in range(min(n, 4096))) * (
        n // 4096 + 1)


@pytest.fixture(scope="module")
def ecfg():
    return EngineConfig.from_config(TOY)


@pytest.fixture(scope="module")
def state(ecfg):
    """A state whose planes are not zeros: its leaves' bytes differ
    from block to block."""
    st = init_engine(ecfg, seed=5)
    leaves, treedef = jax.tree.flatten(st)
    rng = np.random.default_rng(1)
    leaves = [jax.numpy.asarray(rng.integers(
        0, 2 if x.dtype == bool else 200, x.shape).astype(x.dtype))
        for x in leaves]
    return jax.tree.unflatten(treedef, leaves)


# -- the fast seal against the reference ---------------------------------

#: 0, around one ChaCha20 block, around the native pass of 8 blocks,
#: around one block of the streaming writer, and a few MB (several
#: threads' parts)
LENGTHS = (0, 1, 63, 64, 65, 511, 512, 513, BLOCK - 1, BLOCK, BLOCK + 1,
           (3 << 20) + 17)


@needs_native
@pytest.mark.parametrize("n", LENGTHS)
def test_native_stream_is_the_reference_stream(n):
    key, data = bytes(range(7, 39)), _data(n)[:n]
    for counter in (0, 1, 1000):
        want = cp.chacha20_xor(key, NONCE, data, counter)
        for threads in (1, 4):
            got = native.chacha20_xor(key, NONCE, counter, data,
                                      threads=threads)
            assert bytes(got) == want, (counter, threads)
    # in place, on a writable buffer
    buf = np.frombuffer(data, np.uint8).copy()
    native.chacha20_xor(key, NONCE, 0, buf, buf, threads=2)
    assert buf.tobytes() == cp.chacha20_xor(key, NONCE, data)


@needs_native
def test_native_stream_refuses_a_counter_past_2_32_and_wrong_lengths():
    key = bytes(32)
    with pytest.raises(ValueError, match="2\\^32"):
        native.chacha20_xor(key, NONCE, (1 << 32) - 1, bytes(65))
    native.chacha20_xor(key, NONCE, (1 << 32) - 1, bytes(64))
    with pytest.raises(ValueError):
        native.chacha20_xor(key[:31], NONCE, 0, b"x")
    with pytest.raises(ValueError):
        native.chacha20_xor(key, NONCE, 0, b"xy", bytearray(3))


@pytest.mark.parametrize("n", LENGTHS)
def test_fast_seal_is_the_reference_seal_and_each_unseals_the_other(
        n, pinned_nonce):
    data = _data(n)[:n]
    fast = cp.seal(ROOT, b"journal", data, aad=b"hdr")
    ref = _reference_seal(b"journal", data, b"hdr")
    assert fast == ref and len(fast) == 12 + n + 32
    assert cp.unseal(ROOT, b"journal", ref, aad=b"hdr") == data
    assert _reference_unseal(b"journal", fast, b"hdr") == data
    if n:
        torn = bytearray(fast)
        torn[12 + n // 2] ^= 1
        with pytest.raises(cp.SealError):
            cp.unseal(ROOT, b"journal", bytes(torn), aad=b"hdr")


def test_without_the_library_the_stream_is_the_reference(monkeypatch):
    monkeypatch.setattr(native, "lib", None)
    data = _data(1000)[:1000]
    assert cp.stream_xor(ROOT, NONCE, data, 3) == cp.chacha20_xor(
        ROOT, NONCE, data, 3)
    view = np.frombuffer(data, np.uint8).copy()
    cp._xor_in_place(ROOT, NONCE, 3, view)
    assert view.tobytes() == cp.chacha20_xor(ROOT, NONCE, data, 3)


# -- the checkpoint as a stream ------------------------------------------


def _reference_file(ecfg, state, seq: int) -> bytes:
    """The parent's ``write_checkpoint``: the state in one piece, sealed
    in one piece, with the numpy stream."""
    payload = struct.pack("<Q", seq) + cp.state_to_bytes(ecfg, state)
    return HEAD + _reference_seal(b"checkpoint", payload, HEAD)


def _leaves_equal(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        x.dtype == y.dtype and x.shape == y.shape
        and np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


@pytest.mark.parametrize("block", (64, 4096, BLOCK, None))
def test_streamed_checkpoint_is_the_one_piece_file_and_loads_equal(
        tmp_path, ecfg, state, block, pinned_nonce, monkeypatch):
    """Byte-identical to the parent's file for a fixed nonce at every
    block size (64 B: every leaf in pieces; None: the deployment's 32
    MiB, one block), and loaded back leaf for leaf, into planes made
    there and into a state that is given."""
    _block_bytes(monkeypatch, block)
    path = cp.write_checkpoint(str(tmp_path), ROOT, ecfg, state, 42)
    with open(path, "rb") as fh:
        assert fh.read() == _reference_file(ecfg, state, 42)
    seq, loaded = cp.load_checkpoint(path, ROOT, ecfg)
    assert seq == 42 and _leaves_equal(loaded, state)
    seq, loaded = cp.load_checkpoint(path, ROOT, ecfg,
                                     into=init_engine(ecfg, seed=9))
    assert seq == 42 and _leaves_equal(loaded, state)


def test_the_one_piece_reader_reads_a_streamed_file(tmp_path, ecfg, state,
                                                    monkeypatch):
    _block_bytes(monkeypatch, BLOCK)
    path = cp.write_checkpoint(str(tmp_path), ROOT, ecfg, state, 7)
    with open(path, "rb") as fh:
        blob = fh.read()
    payload = _reference_unseal(b"checkpoint", blob[len(HEAD):], HEAD)
    assert struct.unpack_from("<Q", payload)[0] == 7
    assert _leaves_equal(cp.bytes_to_state(ecfg, payload[8:]), state)


def _traced_peak(fn) -> int:
    tracemalloc.start()
    try:
        tracemalloc.reset_peak()
        base = tracemalloc.get_traced_memory()[0]
        fn()
        return tracemalloc.get_traced_memory()[1] - base
    finally:
        tracemalloc.stop()


def test_a_stream_holds_a_few_blocks_on_the_host_not_the_state(
        tmp_path, ecfg, state, monkeypatch):
    """The bound engine/checkpoint.py states: ``STREAM_HOST_BLOCKS``
    blocks, whatever the state's size (here 68 blocks of 64 KiB). The
    slack is the manifest, the leaves smaller than a block and the
    interpreter's own."""
    _block_bytes(monkeypatch, BLOCK)
    total = sum(x.nbytes for x in jax.tree.leaves(state))
    bound = cp.STREAM_HOST_BLOCKS * BLOCK + (192 << 10)
    assert total > 8 * bound
    path = cp.checkpoint_path(str(tmp_path), 3)
    wrote = _traced_peak(lambda: cp.write_checkpoint(
        str(tmp_path), ROOT, ecfg, state, 3))
    assert wrote < bound, (wrote, bound)
    # the loader: warm once (the compiles), then measured
    cp.load_checkpoint(path, ROOT, ecfg)
    read = _traced_peak(lambda: cp.load_checkpoint(path, ROOT, ecfg))
    assert read < bound, (read, bound)


def _plane_shape(ecfg):
    _, spec = cp.state_spec(ecfg)
    return max(spec, key=lambda x: int(np.prod(x.shape))).shape


def _count_planes_at_every_block(monkeypatch, shape) -> list[int]:
    """Wrap the in-place row update: before each one, how many live
    device arrays have the plane's shape."""
    seen: list[int] = []
    inner = cp._put_rows

    def counted(plane, block, start):
        if plane.shape == shape:
            seen.append(sum(a.shape == shape and not a.is_deleted()
                            for a in jax.live_arrays()))
        return inner(plane, block, start)

    monkeypatch.setattr(cp, "_put_rows", counted)
    return seen


def test_a_load_never_holds_two_of_a_plane(tmp_path, ecfg, state,
                                           monkeypatch):
    """By counting live device buffers of the plane's shape at every
    block of the load: the array that is there is donated to each
    update, so there is one, before, during and after."""
    _block_bytes(monkeypatch, BLOCK)
    shape = _plane_shape(ecfg)
    path = cp.write_checkpoint(str(tmp_path), ROOT, ecfg, state, 1)
    seen = _count_planes_at_every_block(monkeypatch, shape)
    empty = init_engine(ecfg, seed=0)
    others = sum(a.shape == shape and not a.is_deleted()
                 for a in jax.live_arrays()) - 1
    _, loaded = cp.load_checkpoint(path, ROOT, ecfg, into=empty)
    assert len(seen) >= 60 and set(seen) == {others + 1}
    assert all(x.is_deleted() for x in jax.tree.leaves(empty)
               if x.shape == shape)
    assert _leaves_equal(loaded, state)


# -- files that must be refused still are, through the stream ------------


def test_streamed_checkpoint_refusals(tmp_path, ecfg, state, monkeypatch):
    _block_bytes(monkeypatch, BLOCK)
    path = cp.write_checkpoint(str(tmp_path), ROOT, ecfg, state, 7)
    with open(path, "rb") as fh:
        blob = fh.read()

    def load(data: bytes, key=ROOT, cfg=ecfg):
        other = str(tmp_path / "other.sealed")
        with open(other, "wb") as fh:
            fh.write(data)
        return cp.load_checkpoint(other, key, cfg,
                                  into=init_engine(cfg, seed=0))

    assert load(blob)[0] == 7  # the control: the file itself loads
    for cut in (0, 5, len(HEAD), len(HEAD) + 12, len(blob) // 3,
                len(blob) - 32, len(blob) - 1):
        with pytest.raises(cp.CheckpointError):
            load(blob[:cut])
    with pytest.raises(cp.CheckpointError, match="integrity"):
        load(blob + b"\0")
    for at in (len(HEAD) + 3, len(HEAD) + 12, len(blob) // 2,
               len(blob) - 40, len(blob) - 1):
        bad = bytearray(blob)
        bad[at] ^= 0x20
        with pytest.raises(cp.CheckpointError, match="integrity"):
            load(bytes(bad))
    with pytest.raises(cp.CheckpointError, match="integrity"):
        load(blob, key=b"\x42" * 32)
    bigger = EngineConfig.from_config(GrapevineConfig(
        max_messages=2048, max_recipients=64, mailbox_cap=4, batch_size=8,
        stash_size=64))
    with pytest.raises(cp.CheckpointError, match="fingerprint"):
        load(blob, cfg=bigger)
    # the same size, another geometry's fingerprint: refused after the
    # tag, by the manifest
    recut = EngineConfig.from_config(GrapevineConfig(
        max_messages=1024, max_recipients=64, mailbox_cap=4, batch_size=8,
        stash_size=64, bucket_cipher_rounds=0))
    with pytest.raises(cp.CheckpointError, match="fingerprint"):
        load(blob, cfg=recut)


# -- the engine: abandon, recover, and the oracle -------------------------

NOW = 1_700_000_000


def _key(n: int) -> bytes:
    return bytes([n, n ^ 0x5A]) + b"\x07" * 30


def _req(rt, auth, recipient=C.ZERO_PUBKEY, msg_id=C.ZERO_MSG_ID, tag=0):
    return QueryRequest(
        request_type=rt, auth_identity=auth,
        auth_signature=b"\x01" * C.SIGNATURE_SIZE,
        record=RequestRecord(msg_id=msg_id, recipient=recipient,
                             payload=bytes([tag & 0xFF]) * C.PAYLOAD_SIZE))


class _Log:
    """What the benchmark's RoundLog keeps of a round, for
    ``compare.replay``."""

    def __init__(self, engine):
        self.engine, self.entries = engine, []

    def round(self, reqs):
        now = NOW + len(self.entries)
        resps = self.engine.handle_queries_async(reqs, now).resolve()
        self.entries.append({"kind": "round", "reqs": reqs, "now": now,
                             "resps": resps})
        return resps


def _creates(round_no: int):
    """A round of CREATEs to 24 recipients in turn: two messages a
    mailbox over six rounds, under the cap of 4."""
    return [_req(C.REQUEST_TYPE_CREATE, _key(1 + (round_no + i) % 5),
                 recipient=_key(10 + (TOY.batch_size * round_no + i) % 24),
                 tag=16 * round_no + i)
            for i in range(TOY.batch_size)]


def _crash_and_read_back(tmp_path, monkeypatch, lose_the_tail: bool):
    """3 rounds, a checkpoint, 3 rounds more, the crash, the restart,
    then every record read back by id and the mailboxes popped: the
    log's replay on the oracle, and the engine."""
    monkeypatch.setattr(cp, "STREAM_BLOCK_BYTES", BLOCK)
    state_dir = str(tmp_path / "state")
    engine = GrapevineEngine(TOY, seed=3, durability={
        "state_dir": state_dir, "checkpoint_every_rounds": 1000})
    log = _Log(engine)
    made = []  # (msg_id, sender) of every record, in order
    for r in range(6):
        if r == 3:
            assert engine.checkpoint_now() == 3
        reqs = _creates(r)
        for q, a in zip(reqs, log.round(reqs)):
            assert a.status_code == C.STATUS_CODE_SUCCESS
            made.append((a.record.msg_id, q.auth_identity))
    assert engine.durability.status()["last_durable_seq"] == 6
    engine.abandon()
    assert engine.state is None
    if lose_the_tail:
        (_, wal), = jr.BatchJournal(state_dir, engine.durability.root_key,
                                    engine.ecfg)._segments()
        os.truncate(wal, 0)
    shape = _plane_shape(engine.ecfg)
    seen = _count_planes_at_every_block(monkeypatch, shape)
    # the engine holds none now; other tests' fixtures may
    others = sum(a.shape == shape and not a.is_deleted()
                 for a in jax.live_arrays())
    engine.recover()
    # one state on the device throughout
    assert seen and set(seen) == {others + 1}
    dm = engine.durability
    assert dm.recovered_from_checkpoint and dm.ckpt_seq == 3
    assert dm.replayed == (0 if lose_the_tail else 3)
    for i in range(0, len(made), TOY.batch_size):
        log.round([_req(C.REQUEST_TYPE_READ, sender, msg_id=mid)
                   for mid, sender in made[i:i + TOY.batch_size]])
    log.round([_req(C.REQUEST_TYPE_DELETE, _key(10 + 3 * i),
                    recipient=_key(10 + 3 * i))
               for i in range(TOY.batch_size)])
    rep = compare.replay(log.entries, {
        "max_messages": TOY.max_messages,
        "max_recipients": TOY.max_recipients,
        "mailbox_cap": TOY.mailbox_cap})
    health = engine.health()
    engine.close()
    return rep, health


def test_after_a_crash_the_restart_answers_as_the_oracle(tmp_path,
                                                         monkeypatch):
    rep, health = _crash_and_read_back(tmp_path, monkeypatch, False)
    assert rep["ops_compared"] == 13 * TOY.batch_size
    assert rep["ops_wrong"] == 0 and rep["ops_unresolved"] == 0
    assert health["messages"] == rep["oracle_messages"]
    assert health["recipients"] == rep["oracle_recipients"]
    assert health["stash_overflow"] == 0


def test_a_restart_that_lost_acknowledged_rounds_is_caught(tmp_path,
                                                           monkeypatch):
    """The control: the journal's tail deleted before recovery. The
    three rounds behind the checkpoint were acknowledged; a state
    without them answers their reads wrongly and counts fewer records
    than the oracle."""
    rep, health = _crash_and_read_back(tmp_path, monkeypatch, True)
    assert rep["ops_wrong"] >= 3 * TOY.batch_size
    assert health["messages"] != rep["oracle_messages"]


def test_a_failed_recovery_leaves_no_state(tmp_path, monkeypatch):
    """Tamper, a wrong key and a renamed file raise what they raised,
    now from the engine's own ``recover`` on the same object, and the
    engine is left with no state: nothing half-loaded can serve."""
    monkeypatch.setattr(cp, "STREAM_BLOCK_BYTES", BLOCK)
    state_dir = str(tmp_path / "state")
    dcfg = DurabilityConfig(state_dir=state_dir, checkpoint_every_rounds=2)
    engine = GrapevineEngine(TOY, seed=3, durability=dcfg)
    for r in range(3):
        engine.handle_queries(_creates(r), NOW + r)
    engine.abandon()
    (seq, path), = [cp.find_latest_checkpoint(state_dir)]
    with open(path, "rb") as fh:
        blob = fh.read()
    key_path = os.path.join(state_dir, "root.key")
    with open(key_path, "rb") as fh:
        key = fh.read()

    def refused(match):
        with pytest.raises(cp.CheckpointError, match=match):
            engine.recover()
        assert engine.state is None

    bad = bytearray(blob)
    bad[len(blob) // 2] ^= 1
    with open(path, "wb") as fh:
        fh.write(bytes(bad))
    refused("integrity")
    with open(path, "wb") as fh:
        fh.write(blob[: len(blob) - 100])
    refused("integrity")
    with open(path, "wb") as fh:
        fh.write(blob)
    with open(key_path, "wb") as fh:
        fh.write(b"\x42" * 32)
    engine.durability.root_key = b"\x42" * 32
    refused("integrity|root key")
    with open(key_path, "wb") as fh:
        fh.write(key)
    engine.durability.root_key = key
    os.rename(path, cp.checkpoint_path(state_dir, seq + 1))
    refused("renamed")
    os.rename(cp.checkpoint_path(state_dir, seq + 1), path)
    engine.recover()  # the files as they were: the engine is back
    assert engine.durability.seq == 3
    assert engine.health()["messages"] == 3 * TOY.batch_size
    engine.close()


# -- durability as a mapping, and the new telemetry -----------------------


def test_durability_as_a_mapping_builds_what_the_config_builds(
        tmp_path, monkeypatch):
    from grapevine_tpu.server.service import GrapevineServer

    monkeypatch.chdir(tmp_path)
    fields = {"state_dir": "a/state", "journal_fsync_every": 1,
              "checkpoint_every_rounds": 512}
    built = DurabilityConfig.coerce(fields)
    assert built == DurabilityConfig(
        state_dir=str(tmp_path / "a" / "state"), journal_fsync_every=1,
        checkpoint_every_rounds=512)
    assert DurabilityConfig.coerce(built) is built
    assert DurabilityConfig.coerce(None) is None
    with pytest.raises(TypeError):
        DurabilityConfig.coerce({"state_dir": "x", "no_such_field": 1})
    with pytest.raises(ValueError):
        DurabilityConfig.coerce({"state_dir": ""})
    small = GrapevineConfig(max_messages=64, max_recipients=8,
                            mailbox_cap=4, batch_size=4, stash_size=64)
    a = GrapevineServer(small, seed=1, durability=fields)
    b = GrapevineServer(small, seed=1, durability=DurabilityConfig(
        state_dir=str(tmp_path / "b" / "state"), journal_fsync_every=1,
        checkpoint_every_rounds=512))
    try:
        assert a.engine.durability.dcfg == built
        assert (a.engine.durability.dcfg.checkpoint_every_rounds
                == b.engine.durability.dcfg.checkpoint_every_rounds)
        assert os.path.isfile(tmp_path / "a" / "state" / "root.key")
        assert a.engine.durability.status() == b.engine.durability.status()
    finally:
        a.stop()
        b.stop()


def test_the_journals_parts_and_the_checkpoints_are_counted(tmp_path,
                                                            monkeypatch):
    """The round's ledger carries the journal's parts as counts; a
    checkpoint that falls due in a round's dispatch leaves its three
    parts in that round's ledger; the gauges of the last checkpoint and
    of the last recovery are set."""
    from grapevine_tpu.obs.tracer import RoundTracer

    monkeypatch.setattr(cp, "STREAM_BLOCK_BYTES", BLOCK)
    state_dir = str(tmp_path / "state")
    engine = GrapevineEngine(TOY, seed=3, durability={
        "state_dir": state_dir, "checkpoint_every_rounds": 2})
    tracer = RoundTracer(capacity=8)
    engine.attach_tracer(tracer)
    for r in range(2):
        engine.handle_queries(_creates(r), NOW + r)
    events = tracer.chrome_trace()["traceEvents"]
    rounds = [e for e in events if e["name"] == "grapevine/round"]
    frame = 16 + 12 + 17 + 4 * TOY.batch_size * 255 + 32
    for e in rounds:
        assert e["args"]["journal_bytes"] == frame
        assert e["args"]["journal_seal_s"] > 0
        assert e["args"]["journal_fsync_s"] > 0
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    second = rounds[1]["args"]["seq"]
    for part in ("checkpoint", "checkpoint_read", "checkpoint_seal",
                 "checkpoint_write"):
        (span,) = [e for e in by_name[f"grapevine/{part}"]
                   if e["args"]["seq"] == second and e["dur"] > 0]
        assert span["dur"] <= by_name["grapevine/dispatch"][1]["dur"]
    registry = engine.metrics.registry
    size = os.path.getsize(cp.checkpoint_path(state_dir, 2))
    assert registry.get("grapevine_checkpoint_bytes").get() == size
    assert registry.get("grapevine_checkpoint_seconds").get() > 0
    engine.handle_queries(_creates(2), NOW + 2)
    engine.abandon()
    engine.recover()
    assert registry.get("grapevine_recovery_replayed_records").get() == 1
    load_s = registry.get("grapevine_recovery_load_seconds").get()
    assert 0 < load_s <= registry.get("grapevine_recovery_seconds").get()
    engine.close()
