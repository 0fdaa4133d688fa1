"""Sealing, checkpoint files, and journal codec (engine/checkpoint.py,
engine/journal.py) — plus the checkpoint-seal CI gate.

The torn-file corpus here is the tier-1 half of the crash-safety story:
every truncation/bitflip of a sealed file must be rejected whole with a
clear error (or, for a journal *tail*, discarded whole) — never
half-loaded. The process-kill half lives in tests/test_chaos_recovery.py
(slow) and tools/chaos_run.py.
"""

import importlib.util
import os

import numpy as np
import pytest

from grapevine_tpu.config import DurabilityConfig, GrapevineConfig
from grapevine_tpu.engine import checkpoint as cp
from grapevine_tpu.engine import journal as jr
from grapevine_tpu.engine.batcher import pack_batch
from grapevine_tpu.engine.state import EngineConfig, init_engine
from grapevine_tpu.session.chacha import ChaCha20
from grapevine_tpu.wire import constants as C
from grapevine_tpu.wire.records import QueryRequest, RequestRecord

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SMALL = GrapevineConfig(
    max_messages=64, max_recipients=8, mailbox_cap=4,
    batch_size=4, stash_size=64, bucket_cipher_rounds=0,
)

ROOT = bytes(range(32))


# -- sealing primitives -------------------------------------------------


def test_bulk_chacha_matches_session_stream():
    """The numpy-vectorized keystream is the same RFC 7539 stream the
    session layer's (test-vector-pinned) implementation produces."""
    key, nonce = bytes(range(32)), bytes(range(12))
    for n in (1, 63, 64, 65, 1000, 4096):
        data = bytes((i * 7) & 0xFF for i in range(n))
        ks = ChaCha20(key, nonce).keystream(n)
        want = bytes(a ^ b for a, b in zip(data, ks))
        assert cp.chacha20_xor(key, nonce, data) == want


def test_seal_roundtrip_and_rejections():
    blob = cp.seal(ROOT, b"checkpoint", b"payload bytes", aad=b"hdr")
    assert cp.unseal(ROOT, b"checkpoint", blob, aad=b"hdr") == b"payload bytes"
    with pytest.raises(cp.SealError):  # tamper
        cp.unseal(ROOT, b"checkpoint", blob[:-1] + b"\x00", aad=b"hdr")
    with pytest.raises(cp.SealError):  # truncation
        cp.unseal(ROOT, b"checkpoint", blob[:-5], aad=b"hdr")
    with pytest.raises(cp.SealError):  # wrong domain subkey
        cp.unseal(ROOT, b"journal", blob, aad=b"hdr")
    with pytest.raises(cp.SealError):  # aad (header) mangled
        cp.unseal(ROOT, b"checkpoint", blob, aad=b"HDR")
    with pytest.raises(cp.SealError):  # wrong root key
        cp.unseal(b"\x01" * 32, b"checkpoint", blob, aad=b"hdr")
    with pytest.raises(cp.SealError):  # shorter than nonce+tag
        cp.unseal(ROOT, b"checkpoint", b"short")


def test_root_key_create_then_load(tmp_path):
    path = str(tmp_path / "root.key")
    k1 = cp.load_or_create_root_key(path)
    assert len(k1) == 32 and oct(os.stat(path).st_mode & 0o777) == "0o600"
    assert cp.load_or_create_root_key(path) == k1
    (tmp_path / "bad.key").write_bytes(b"short")
    with pytest.raises(cp.SealError):
        cp.load_or_create_root_key(str(tmp_path / "bad.key"))


# -- checkpoint files ---------------------------------------------------


@pytest.fixture(scope="module")
def ecfg():
    return EngineConfig.from_config(SMALL)


@pytest.fixture(scope="module")
def state(ecfg):
    return init_engine(ecfg, seed=5)


def test_state_bytes_roundtrip(ecfg, state):
    data = cp.state_to_bytes(ecfg, state)
    state2 = cp.bytes_to_state(ecfg, data)
    assert cp.state_to_bytes(ecfg, state2) == data


def test_checkpoint_write_load(tmp_path, ecfg, state):
    path = cp.write_checkpoint(str(tmp_path), ROOT, ecfg, state, seq=42)
    assert cp.find_latest_checkpoint(str(tmp_path)) == (42, path)
    seq, state2 = cp.load_checkpoint(path, ROOT, ecfg)
    assert seq == 42
    assert cp.state_to_bytes(ecfg, state2) == cp.state_to_bytes(ecfg, state)


@pytest.mark.parametrize("old", [1, 2, 3])
def test_an_older_version_payload_is_refused(
    tmp_path, ecfg, state, monkeypatch, old
):
    """Version 4 stores a wide value row as ``(tiles, 128)`` (PR 46:
    the planes' shapes in the manifest, the bytes are version 3's),
    version 3 brought wide rows to whole lane tiles (PR 44), version 2
    was the tile-aligned keystream order (PR 40): a tree sealed under
    version 1 or 2 would decrypt to noise, so both checks — the sealed
    file's header and the state payload's manifest — refuse an older
    file and say which version they met."""
    assert cp.VERSION == 4
    monkeypatch.setattr(cp, "VERSION", old)
    old_payload = cp.state_to_bytes(ecfg, state)
    old_file = cp.write_checkpoint(str(tmp_path), ROOT, ecfg, state, seq=3)
    monkeypatch.undo()
    with pytest.raises(cp.CheckpointError, match=f"version {old}, want 4"):
        cp.bytes_to_state(ecfg, old_payload)
    with pytest.raises(cp.CheckpointError, match=f"version {old}, want 4"):
        cp.load_checkpoint(old_file, ROOT, ecfg)


def test_version_4_round_trips_the_padded_mailbox_row(tmp_path):
    """The at-rest format of PR 44: a mailbox row of 6,080 block words
    (cap 62) is stored, sealed and loaded 6,144 wide as 48 lane tiles
    (PR 46), ciphertext and pad keystream alike, under a header that
    says version 4."""
    import dataclasses
    import struct

    import jax

    from grapevine_tpu.engine.round_step import engine_round_step

    ecfg = EngineConfig.from_config(dataclasses.replace(
        SMALL, mailbox_cap=62, bucket_cipher_rounds=8))
    assert ecfg.mb.stored_row_words == 6144 == ecfg.mb.val_row_words + 64
    state, _, _ = jax.jit(engine_round_step, static_argnums=(0,))(
        ecfg, init_engine(ecfg, seed=5), _round_batch(ecfg, 7)[0])
    assert state.mb.tree_val.shape == (ecfg.mb.n_buckets_padded, 48, 128)
    # a written row's pad words are keystream at rest, not zeros
    written = np.asarray(state.mb.nonces).any(axis=1)
    rows = np.asarray(state.mb.tree_val).reshape(-1, 6144)
    assert rows[written][:, 6080:].all(axis=1).any()
    path = cp.write_checkpoint(str(tmp_path), ROOT, ecfg, state, seq=9)
    with open(path, "rb") as f:
        assert f.read(12) == cp.MAGIC + struct.pack("<I", 4)
    seq, state2 = cp.load_checkpoint(path, ROOT, ecfg)
    assert seq == 9
    assert state2.mb.tree_val.shape == state.mb.tree_val.shape
    assert cp.state_to_bytes(ecfg, state2) == cp.state_to_bytes(ecfg, state)


def test_checkpoint_geometry_fingerprint_rejected(tmp_path, ecfg, state):
    path = cp.write_checkpoint(str(tmp_path), ROOT, ecfg, state, seq=1)
    other = EngineConfig.from_config(
        GrapevineConfig(
            max_messages=128, max_recipients=8, mailbox_cap=4,
            batch_size=4, stash_size=64, bucket_cipher_rounds=0,
        )
    )
    with pytest.raises(cp.CheckpointError, match="fingerprint"):
        cp.load_checkpoint(path, ROOT, other)


def test_renamed_checkpoint_rejected(tmp_path, ecfg, state):
    """The filename seq picks the file; the sealed payload seq anchors
    replay — a renamed checkpoint must not shift the replay base."""
    path = cp.write_checkpoint(str(tmp_path), ROOT, ecfg, state, seq=7)
    os.rename(path, cp.checkpoint_path(str(tmp_path), 5))
    with open(tmp_path / "root.key", "wb") as fh:
        fh.write(ROOT)
    mgr = cp.DurabilityManager(
        DurabilityConfig(state_dir=str(tmp_path)), ecfg
    )
    with pytest.raises(cp.CheckpointError, match="renamed"):
        mgr.recover(state, lambda s, rec: s)


def test_torn_checkpoint_corpus_never_half_loads(tmp_path, ecfg, state):
    """Truncations at a spread of offsets plus interior bitflips: every
    variant raises CheckpointError; none returns a state."""
    path = cp.write_checkpoint(str(tmp_path), ROOT, ecfg, state, seq=7)
    blob = open(path, "rb").read()
    cuts = [0, 1, len(cp.MAGIC), 11, 12, 50, len(blob) // 2, len(blob) - 33,
            len(blob) - 1]
    for cut in cuts:
        torn = str(tmp_path / f"torn-{cut}.sealed")
        with open(torn, "wb") as fh:
            fh.write(blob[:cut])
        with pytest.raises(cp.CheckpointError):
            cp.load_checkpoint(torn, ROOT, ecfg)
    for flip_at in (8, 20, len(blob) // 2, len(blob) - 10):
        flipped = str(tmp_path / f"flip-{flip_at}.sealed")
        mutated = bytearray(blob)
        mutated[flip_at] ^= 0x40
        with open(flipped, "wb") as fh:
            fh.write(bytes(mutated))
        with pytest.raises(cp.CheckpointError):
            cp.load_checkpoint(flipped, ROOT, ecfg)


# -- journal codec + torn-tail semantics --------------------------------


def _round_batch(ecfg, tag: int):
    reqs = [
        QueryRequest(
            request_type=C.REQUEST_TYPE_CREATE,
            auth_identity=bytes([tag]) * 32,
            auth_signature=b"\x01" * C.SIGNATURE_SIZE,
            record=RequestRecord(
                msg_id=C.ZERO_MSG_ID,
                recipient=bytes([tag ^ 0x5A]) * 32,
                payload=bytes([tag]) * C.PAYLOAD_SIZE,
            ),
        )
    ]
    return pack_batch(reqs, ecfg.batch_size, 1_700_000_000 + tag), len(reqs)


def _fresh_journal(tmp_path, ecfg, **kw):
    j = jr.BatchJournal(str(tmp_path), ROOT, ecfg, **kw)
    list(j.replay(after_seq=0))
    j.open_for_append()
    return j


def test_journal_roundtrip_rounds_and_sweeps(tmp_path, ecfg):
    j = _fresh_journal(tmp_path, ecfg)
    batches = [_round_batch(ecfg, t) for t in (1, 2)]
    assert j.append_round(*batches[0]) == 1
    assert j.append_sweep(123, 4, 60) == 2
    assert j.append_round(*batches[1]) == 3
    j.close()

    j2 = jr.BatchJournal(str(tmp_path), ROOT, ecfg)
    recs = list(j2.replay(after_seq=0))
    assert [r.seq for r in recs] == [1, 2, 3]
    assert [r.kind for r in recs] == [jr.KIND_ROUND, jr.KIND_SWEEP,
                                      jr.KIND_ROUND]
    assert recs[1].now == 123 and recs[1].now_hi == 4 and recs[1].period == 60
    for rec, (batch, n) in zip((recs[0], recs[2]), batches):
        assert rec.n_real == n
        for col in ("req_type", "auth", "msg_id", "recipient", "payload"):
            np.testing.assert_array_equal(rec.batch[col], batch[col])
        assert int(rec.batch["now"]) == int(batch["now"])
    # checkpoint covering seq 2: replay skips the covered prefix
    j3 = jr.BatchJournal(str(tmp_path), ROOT, ecfg)
    assert [r.seq for r in j3.replay(after_seq=2)] == [3]


def test_journal_refuses_the_retired_window_marker_kind(tmp_path, ecfg):
    """Frame kind 3 is retired, never reused: it was the one-byte marker
    a delayed-eviction engine journaled between windows. A journal that
    holds one (sealed exactly as that engine sealed it) is refused by
    name, and the frames before it still replay."""
    import struct

    j = _fresh_journal(tmp_path, ecfg)
    j.append_round(*_round_batch(ecfg, 1))
    j._append(struct.pack("<B", 3))
    j.append_round(*_round_batch(ecfg, 2))
    j.close()
    replay = jr.BatchJournal(str(tmp_path), ROOT, ecfg).replay(after_seq=0)
    assert next(replay).seq == 1
    with pytest.raises(jr.JournalError, match="frame 2.*delayed-eviction"):
        next(replay)


def test_journal_torn_tail_discarded_everywhere_else_rejected(tmp_path, ecfg):
    j = _fresh_journal(tmp_path, ecfg)
    for t in range(3):
        j.append_round(*_round_batch(ecfg, t + 1))
    j.close()
    (first_seq, path), = jr.BatchJournal(str(tmp_path), ROOT, ecfg)._segments()
    blob = open(path, "rb").read()
    frame_len = len(blob) // 3

    # truncating anywhere inside the FINAL frame = torn tail: the first
    # two records replay, the torn one is discarded, never half-decoded
    for cut in (2 * frame_len + 1, 2 * frame_len + 16, len(blob) - 1):
        with open(path, "wb") as fh:
            fh.write(blob[:cut])
        jt = jr.BatchJournal(str(tmp_path), ROOT, ecfg)
        assert [r.seq for r in jt.replay(after_seq=0)] == [1, 2]
        # ...and appending after recovery truncates the torn bytes
        jt.open_for_append()
        seq = jt.append_round(*_round_batch(ecfg, 9))
        assert seq == 3
        jt.close()
        recs = list(jr.BatchJournal(str(tmp_path), ROOT, ecfg).replay(0))
        assert [r.seq for r in recs] == [1, 2, 3]
        with open(path, "wb") as fh:  # restore the 3-frame original
            fh.write(blob)

    # a bitflipped frame with valid frames after it is corruption
    mutated = bytearray(blob)
    mutated[frame_len + 20] ^= 1
    with open(path, "wb") as fh:
        fh.write(bytes(mutated))
    with pytest.raises(jr.JournalError, match="integrity"):
        list(jr.BatchJournal(str(tmp_path), ROOT, ecfg).replay(0))

    # header corruption mid-final-segment must raise too — NOT read as
    # a torn tail that would silently truncate durable frames behind it
    mutated = bytearray(blob)
    mutated[frame_len] ^= 0xFF  # second frame's magic
    with open(path, "wb") as fh:
        fh.write(bytes(mutated))
    with pytest.raises(jr.JournalError, match="magic"):
        list(jr.BatchJournal(str(tmp_path), ROOT, ecfg).replay(0))
    mutated = bytearray(blob)
    mutated[frame_len + 12] ^= 0xFF  # second frame's blob_len field
    with open(path, "wb") as fh:
        fh.write(bytes(mutated))
    with pytest.raises(jr.JournalError, match="impossible blob length"):
        list(jr.BatchJournal(str(tmp_path), ROOT, ecfg).replay(0))

    # a missing prefix (journal starts past the checkpoint's coverage)
    # is corruption, not a quiet skip — frames are constant-size here,
    # so dropping the first one leaves valid frames 2..3
    with open(path, "wb") as fh:
        fh.write(blob[frame_len:])
    with pytest.raises(jr.JournalError, match="starts at seq 2"):
        list(jr.BatchJournal(str(tmp_path), ROOT, ecfg).replay(after_seq=0))
    with open(path, "wb") as fh:  # restore for any later test
        fh.write(blob)


def test_journal_geometry_mismatch_rejected(tmp_path, ecfg):
    j = _fresh_journal(tmp_path, ecfg)
    j.append_round(*_round_batch(ecfg, 1))
    j.close()
    other = EngineConfig.from_config(
        GrapevineConfig(
            max_messages=64, max_recipients=8, mailbox_cap=4,
            batch_size=8, stash_size=64, bucket_cipher_rounds=0,
        )
    )
    # caught at the frame-length gate (round frames are constant-size
    # per geometry) before the sealed body's own batch_size check
    with pytest.raises(jr.JournalError,
                       match="impossible blob length|batch_size"):
        list(jr.BatchJournal(str(tmp_path), ROOT, other).replay(0))


def test_journal_roll_prunes_covered_segments(tmp_path, ecfg):
    j = _fresh_journal(tmp_path, ecfg)
    j.append_round(*_round_batch(ecfg, 1))
    j.append_round(*_round_batch(ecfg, 2))
    j.roll()  # as after a checkpoint at seq 2
    j.append_round(*_round_batch(ecfg, 3))
    j.close()
    segs = jr.BatchJournal(str(tmp_path), ROOT, ecfg)._segments()
    assert [s[0] for s in segs] == [3]
    recs = list(jr.BatchJournal(str(tmp_path), ROOT, ecfg).replay(after_seq=2))
    assert [r.seq for r in recs] == [3]


def test_journal_fsync_batching(tmp_path, ecfg):
    synced = []
    j = jr.BatchJournal(str(tmp_path), ROOT, ecfg, fsync_every=3,
                        on_fsync=synced.append)
    list(j.replay(0))
    j.open_for_append()
    for t in range(1, 8):
        j.append_round(*_round_batch(ecfg, t))
    assert synced == [3, 6]  # every 3rd record
    assert j.durable_seq == 6 and j.seq == 7
    j.sync()
    assert synced == [3, 6, 7]
    j.close()


# -- the CI seal gate (satellite: wired next to check_telemetry_policy) -


def test_checkpoint_seal_gate_passes():
    """tools/check_checkpoint_seal.py: no plaintext payload, identity,
    or key material in any checkpoint/journal file a real durable run
    writes."""
    path = os.path.join(REPO, "tools", "check_checkpoint_seal.py")
    spec = importlib.util.spec_from_file_location("check_checkpoint_seal", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main() == 0


# -- the fingerprint across PR 49 (one slot-order machinery, one sort) ---


@pytest.mark.parametrize("posmap,digest", [
    ("flat",
     "5a18ee82fee8ecb7279fc51832ebda17fc412b46185afb1e779589025386476c"),
    ("recursive",
     "8d862fe580009f9a6933713cb8f91e9ee2880fefded227ddc02d75fe2159a598"),
])
def test_engine_fingerprint_is_what_a_tpu_wrote_before_pr_49(posmap, digest):
    """The digests the parent of PR 49 gave this geometry resolved as a
    TPU resolved it (``vphases_impl="dense"``, the Pallas cipher). They
    move only with a checkpoint ``VERSION``: ``repr(ecfg)`` decides
    whether a state directory loads, so a field added to, dropped from
    or renamed in ``EngineConfig`` or ``OramConfig`` refuses every
    directory a deployment holds."""
    ecfg = EngineConfig.from_config(GrapevineConfig(
        max_messages=1 << 12, max_recipients=1 << 8, batch_size=16,
        bucket_cipher_impl="pallas", posmap_impl=posmap))
    assert "vphases_impl='dense', sort_impl='xla'" in repr(ecfg)
    assert cp.engine_fingerprint(ecfg) == digest


@pytest.mark.parametrize("knob", [
    {"vphases_impl": "dense"}, {"sort_impl": "xla"},
    {"bucket_cipher_impl": "pallas_fused"},
], ids=["vphases_impl", "sort_impl", "pallas_fused"])
def test_the_deleted_implementation_knobs_are_refused(knob):
    """``vphases_impl`` and ``sort_impl`` are no options of the
    configuration any more, at any value, and are constants of
    ``EngineConfig`` that its constructor does not take;
    ``"pallas_fused"`` is refused like any unknown cipher name."""
    import dataclasses

    with pytest.raises((TypeError, ValueError), match=next(iter(knob))):
        GrapevineConfig(**knob)
    ecfg = EngineConfig.from_config(SMALL)
    assert (ecfg.vphases_impl, ecfg.sort_impl) == ("dense", "xla")
    if "bucket_cipher_impl" not in knob:
        with pytest.raises((TypeError, ValueError), match="init=False"):
            dataclasses.replace(ecfg, **knob)
