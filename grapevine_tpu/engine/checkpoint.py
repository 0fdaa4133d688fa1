"""Sealed whole-state checkpoints + the durability orchestrator.

The reference treats engine state as enclave-volatile; production Path
ORAM deployments do not (Stefanov et al. assume a persistent backing
store), and "oblivious redis" is meaningless if a SIGKILL wipes the bus.
This module makes the engine crash-safe without touching the oblivious
round itself:

- **Sealing**: checkpoints and journal frames are encrypted with
  ChaCha20 under per-domain subkeys of a 32-byte root key and
  authenticated encrypt-then-MAC with HMAC-SHA256. A torn, truncated,
  or tampered file fails the tag check and is *rejected whole* — there
  is no partial load. Pure stdlib + the in-repo RFC 7539 stream (the
  ``cryptography`` wheel is optional in this container), with the bulk
  keystream vectorized in numpy (the session-layer block function is a
  per-32-byte-draw path; a checkpoint is megabytes).
- **Obliviousness**: a checkpoint serializes the *entire*
  ``EngineState`` every time, and a journal frame serializes the
  *entire* fixed-size batch every round — both are constant-shape
  functions of the geometry, written at round cadence regardless of
  what the ops inside are. Like the device transcript, the file-system
  access pattern of durability is data-independent by construction
  (OPERATIONS.md §11).
- **Atomicity**: checkpoints are written tmp + fsync + ``os.replace`` +
  directory fsync, so the newest ``ckpt-*.sealed`` is always complete;
  recovery = newest checkpoint + deterministic replay of the journal
  tail (engine/journal.py) — the engine round is deterministic given
  (state, batch), which the PR-3 oracle-equality suites pin.

Crash points for the fault harness (testing/faults.py) are inlined at
the protocol-critical spots.
"""

from __future__ import annotations

import hashlib
import hmac
import json
import os
import re
import struct
import time

import jax
import numpy as np

from ..config import DurabilityConfig
from ..testing import faults
from .state import EngineConfig, EngineState, state_spec

MAGIC = b"GVCKPT1\0"
#: 2 since PR 40: the bucket cipher's keystream order changed
#: (oblivious/bucket_cipher.py), so trees sealed under version 1 would
#: decrypt to noise. Refused, not migrated: re-initialise the state.
VERSION = 2

_CKPT_RE = re.compile(r"^ckpt-(\d{16})\.sealed$")


class DurabilityError(RuntimeError):
    """Base for checkpoint/journal failures (never a partial load)."""


class CheckpointError(DurabilityError):
    pass


class SealError(DurabilityError):
    """Sealed blob failed structural or integrity checks."""


def write_all(fd: int, data: bytes) -> None:
    """os.write until every byte lands: one write() is capped (~2 GiB
    on Linux) and may return short on ENOSPC-adjacent conditions — an
    unchecked short count would publish a truncated sealed file."""
    view = memoryview(data)
    while view:
        n = os.write(fd, view)
        view = view[n:]


# -- sealing primitives (shared with engine/journal.py) -----------------


def _chacha_block_words(key_words, counter0: int, nonce_words, n_blocks: int):
    """RFC 7539 ChaCha20 keystream for ``n_blocks`` consecutive counters,
    vectorized over the block axis with numpy (the session-layer
    pure-Python path is O(n²) byte-appends — unusable at checkpoint
    sizes). Returns u32[n_blocks, 16]; pinned to session/chacha.py's
    stream in tests/test_checkpoint.py."""
    const = np.frombuffer(b"expand 32-byte k", dtype="<u4")
    ctrs = (np.arange(n_blocks, dtype=np.uint64) + np.uint64(counter0)).astype(
        np.uint32
    )
    init = np.empty((n_blocks, 16), np.uint32)
    init[:, 0:4] = const
    init[:, 4:12] = key_words
    init[:, 12] = ctrs
    init[:, 13:16] = nonce_words
    x = init.copy()

    def rot(v, n):
        return (v << np.uint32(n)) | (v >> np.uint32(32 - n))

    def qr(a, b, c, d):
        x[:, a] += x[:, b]
        x[:, d] = rot(x[:, d] ^ x[:, a], 16)
        x[:, c] += x[:, d]
        x[:, b] = rot(x[:, b] ^ x[:, c], 12)
        x[:, a] += x[:, b]
        x[:, d] = rot(x[:, d] ^ x[:, a], 8)
        x[:, c] += x[:, d]
        x[:, b] = rot(x[:, b] ^ x[:, c], 7)

    with np.errstate(over="ignore"):
        for _ in range(10):
            qr(0, 4, 8, 12)
            qr(1, 5, 9, 13)
            qr(2, 6, 10, 14)
            qr(3, 7, 11, 15)
            qr(0, 5, 10, 15)
            qr(1, 6, 11, 12)
            qr(2, 7, 8, 13)
            qr(3, 4, 9, 14)
        x += init
    return x


def chacha20_xor(key: bytes, nonce: bytes, data: bytes, counter: int = 0) -> bytes:
    """ChaCha20-XOR ``data`` (encrypt ≡ decrypt), bulk-vectorized."""
    if len(key) != 32 or len(nonce) != 12:
        raise ValueError("key must be 32 bytes, nonce 12")
    n_blocks = (len(data) + 63) // 64
    if n_blocks == 0:
        return b""
    ks = _chacha_block_words(
        np.frombuffer(key, "<u4"),
        counter,
        np.frombuffer(nonce, "<u4"),
        n_blocks,
    )
    ks_bytes = ks.astype("<u4").tobytes()[: len(data)]
    return (
        np.frombuffer(data, np.uint8) ^ np.frombuffer(ks_bytes, np.uint8)
    ).tobytes()


def derive_key(root_key: bytes, label: bytes) -> bytes:
    """Per-domain 32-byte subkey: HMAC-SHA256(root, label)."""
    if len(root_key) != 32:
        raise ValueError("root key must be 32 bytes")
    return hmac.new(root_key, label, hashlib.sha256).digest()


def seal(root_key: bytes, domain: bytes, plaintext: bytes,
         aad: bytes = b"") -> bytes:
    """Encrypt-then-MAC: returns ``nonce(12) | ct | tag(32)``.

    ``domain`` separates key schedules (checkpoint vs journal);
    ``aad`` binds plaintext headers (magic, seq) into the tag without
    encrypting them."""
    enc = derive_key(root_key, b"grapevine-seal-enc:" + domain)
    mac = derive_key(root_key, b"grapevine-seal-mac:" + domain)
    nonce = os.urandom(12)
    ct = chacha20_xor(enc, nonce, plaintext)
    tag = hmac.new(mac, aad + nonce + ct, hashlib.sha256).digest()
    return nonce + ct + tag


def unseal(root_key: bytes, domain: bytes, blob: bytes,
           aad: bytes = b"") -> bytes:
    """Verify and decrypt a :func:`seal` blob; raises SealError on any
    truncation or integrity failure — never returns partial plaintext."""
    if len(blob) < 12 + 32:
        raise SealError("sealed blob truncated (shorter than nonce + tag)")
    nonce, ct, tag = blob[:12], blob[12:-32], blob[-32:]
    mac = derive_key(root_key, b"grapevine-seal-mac:" + domain)
    want = hmac.new(mac, aad + nonce + ct, hashlib.sha256).digest()
    if not hmac.compare_digest(tag, want):
        raise SealError(
            "sealed blob failed integrity check (torn, truncated, "
            "tampered, or sealed under a different root key)"
        )
    enc = derive_key(root_key, b"grapevine-seal-enc:" + domain)
    return chacha20_xor(enc, nonce, ct)


def load_or_create_root_key(path: str) -> bytes:
    """32-byte root seal key at ``path``; generated 0600 on first use."""
    try:
        fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_EXCL, 0o600)
    except FileExistsError:
        with open(path, "rb") as fh:
            key = fh.read()
        if len(key) != 32:
            raise SealError(
                f"root key file {path!r} is {len(key)} bytes, want 32"
            )
        return key
    try:
        key = os.urandom(32)
        os.write(fd, key)
        os.fsync(fd)
    finally:
        os.close(fd)
    return key


# -- EngineState <-> bytes ---------------------------------------------


def engine_fingerprint(ecfg: EngineConfig) -> str:
    """Geometry fingerprint a checkpoint/journal is only valid against.

    ``repr`` of the frozen dataclass tree is deterministic and covers
    every field that shapes the state arrays or the replay semantics."""
    return hashlib.sha256(repr(ecfg).encode()).hexdigest()


def state_to_bytes(ecfg: EngineConfig, state: EngineState) -> bytes:
    """Serialize a (host-synced) EngineState: JSON manifest + raw leaf
    buffers in pytree order. Blocks until the device state is ready."""
    leaves = jax.tree_util.tree_leaves(state)
    arrays = [np.asarray(leaf) for leaf in leaves]
    manifest = {
        "version": VERSION,
        "fingerprint": engine_fingerprint(ecfg),
        "leaves": [[a.dtype.str, list(a.shape)] for a in arrays],
    }
    head = json.dumps(manifest, separators=(",", ":")).encode()
    parts = [struct.pack("<I", len(head)), head]
    for a in arrays:
        # copy=False: a no-op on little-endian hosts — tobytes() is the
        # single unavoidable copy per leaf (this runs under the engine
        # lock; every avoided full-state copy shortens the round stall)
        le = np.ascontiguousarray(a).astype(
            a.dtype.newbyteorder("<"), copy=False
        )
        parts.append(le.tobytes())
    return b"".join(parts)


def bytes_to_state(
    ecfg: EngineConfig, data: bytes, shardings=None
) -> EngineState:
    """Inverse of :func:`state_to_bytes`; rejects geometry mismatches and
    truncated buffers whole (CheckpointError).

    ``shardings``: a pytree of one ``jax.sharding.Sharding`` per state
    leaf (a mesh engine's live placement). Each leaf then goes from host
    memory straight to its shards' devices; without it, to the default
    device."""
    if len(data) < 4:
        raise CheckpointError("state payload truncated (no manifest)")
    (head_len,) = struct.unpack_from("<I", data, 0)
    if len(data) < 4 + head_len:
        raise CheckpointError("state payload truncated (manifest cut short)")
    try:
        manifest = json.loads(data[4 : 4 + head_len])
    except ValueError as exc:
        raise CheckpointError(f"state manifest unparseable: {exc}") from None
    if manifest.get("version") != VERSION:
        raise CheckpointError(
            f"state payload version {manifest.get('version')!r}, "
            f"want {VERSION}"
        )
    if manifest.get("fingerprint") != engine_fingerprint(ecfg):
        raise CheckpointError(
            "checkpoint geometry fingerprint does not match this engine "
            "config — restore requires the identical GrapevineConfig "
            "(capacities, heights, batch size, cipher) it was taken under"
        )
    treedef, spec = state_spec(ecfg)
    decl = manifest.get("leaves", [])
    if len(decl) != len(spec):
        raise CheckpointError(
            f"state payload has {len(decl)} leaves, geometry wants "
            f"{len(spec)}"
        )
    off = 4 + head_len
    leaves = []
    places = (jax.tree_util.tree_leaves(shardings)
              if shardings is not None else [None] * len(spec))
    if len(places) != len(spec):
        raise ValueError(
            f"shardings has {len(places)} leaves, state has {len(spec)}"
        )
    for (dt_str, shape), want, place in zip(decl, spec, places):
        dt = np.dtype(dt_str)
        shape = tuple(shape)
        if shape != tuple(want.shape) or dt.newbyteorder("=") != np.dtype(
            want.dtype
        ):
            raise CheckpointError(
                f"state leaf mismatch: payload {dt_str}{shape}, geometry "
                f"wants {np.dtype(want.dtype).str}{tuple(want.shape)}"
            )
        nbytes = dt.itemsize * int(np.prod(shape, dtype=np.int64))
        if off + nbytes > len(data):
            raise CheckpointError("state payload truncated (leaf cut short)")
        arr = np.frombuffer(data, dt, count=nbytes // dt.itemsize, offset=off)
        leaves.append(jax.device_put(
            arr.reshape(shape).astype(dt.newbyteorder("=")), place
        ))
        off += nbytes
    if off != len(data):
        raise CheckpointError(
            f"state payload has {len(data) - off} trailing bytes"
        )
    return jax.tree_util.tree_unflatten(treedef, leaves)


# -- sealed checkpoint files -------------------------------------------


def checkpoint_path(state_dir: str, seq: int) -> str:
    return os.path.join(state_dir, f"ckpt-{seq:016d}.sealed")


def write_checkpoint(
    state_dir: str, root_key: bytes, ecfg: EngineConfig,
    state: EngineState, seq: int,
) -> str:
    """Atomically write the sealed checkpoint for journal seq ``seq``.

    tmp + fsync + rename + directory fsync: a crash at any point leaves
    either the previous checkpoint set or the new file complete — never
    a half-written ``ckpt-*.sealed``."""
    payload = struct.pack("<Q", seq) + state_to_bytes(ecfg, state)
    head = MAGIC + struct.pack("<I", VERSION)
    blob = head + seal(root_key, b"checkpoint", payload, aad=head)
    path = checkpoint_path(state_dir, seq)
    tmp = path + f".tmp.{os.getpid()}"
    fd = os.open(tmp, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o600)
    try:
        if faults.active() and faults.hit("checkpoint.tmp.torn"):
            write_all(fd, blob[: len(blob) // 2])
            os.fsync(fd)
            faults.die()
        write_all(fd, blob)
        os.fsync(fd)
    finally:
        os.close(fd)
    if faults.active():
        faults.crash("checkpoint.pre_rename")
    os.replace(tmp, path)
    dfd = os.open(state_dir, os.O_RDONLY)
    try:
        os.fsync(dfd)
    finally:
        os.close(dfd)
    if faults.active():
        faults.crash("checkpoint.post_rename")
    return path


def load_checkpoint(
    path: str, root_key: bytes, ecfg: EngineConfig, shardings=None
) -> tuple[int, EngineState]:
    """Load a sealed checkpoint; returns ``(seq, state)``. Any
    truncation, tamper, or geometry mismatch raises CheckpointError —
    the state is never half-loaded. ``shardings``: see
    :func:`bytes_to_state`."""
    with open(path, "rb") as fh:
        blob = fh.read()
    head = MAGIC + struct.pack("<I", VERSION)
    if len(blob) < len(head) or blob[: len(MAGIC)] != MAGIC:
        raise CheckpointError(f"{path}: not a grapevine checkpoint")
    if blob[len(MAGIC) : len(head)] != head[len(MAGIC) :]:
        (ver,) = struct.unpack_from("<I", blob, len(MAGIC))
        raise CheckpointError(f"{path}: version {ver}, want {VERSION}")
    try:
        payload = unseal(root_key, b"checkpoint", blob[len(head):], aad=head)
    except SealError as exc:
        raise CheckpointError(f"{path}: {exc}") from None
    if len(payload) < 8:
        raise CheckpointError(f"{path}: payload truncated")
    (seq,) = struct.unpack_from("<Q", payload, 0)
    return seq, bytes_to_state(ecfg, payload[8:], shardings)


def find_latest_checkpoint(state_dir: str) -> tuple[int, str] | None:
    """Newest ``ckpt-<seq>.sealed`` by sequence number, or None."""
    best = None
    try:
        names = os.listdir(state_dir)
    except FileNotFoundError:
        return None
    for name in names:
        m = _CKPT_RE.match(name)
        if m:
            seq = int(m.group(1))
            if best is None or seq > best[0]:
                best = (seq, os.path.join(state_dir, name))
    return best


def prune_checkpoints(state_dir: str, keep_seq: int) -> None:
    """Delete every checkpoint except ``keep_seq``'s (called only after
    the kept one is durably renamed)."""
    for name in os.listdir(state_dir):
        m = _CKPT_RE.match(name)
        if m and int(m.group(1)) != keep_seq:
            try:
                os.unlink(os.path.join(state_dir, name))
            except OSError:  # pragma: no cover - concurrent cleanup
                pass
    # stale tmp files from crashed checkpoint attempts are dead weight
    for name in os.listdir(state_dir):
        if ".sealed.tmp." in name:
            try:
                os.unlink(os.path.join(state_dir, name))
            except OSError:  # pragma: no cover
                pass


# -- the durability orchestrator ---------------------------------------


class DurabilityManager:
    """Owns a state dir: root key, journal, checkpoints, recovery.

    One per engine, driven from ``GrapevineEngine`` under the engine
    lock (appends and checkpoints are serialized with rounds by
    construction). Telemetry is batch-level only: sequence numbers,
    counts, and durations — never content."""

    def __init__(self, dcfg: DurabilityConfig, ecfg: EngineConfig,
                 registry=None, state_shardings=None):
        from .journal import BatchJournal

        self.dcfg = dcfg
        self.ecfg = ecfg
        #: a mesh engine's per-leaf placement (bytes_to_state); None =
        #: restored checkpoints go to the default device
        self.state_shardings = state_shardings
        os.makedirs(dcfg.state_dir, exist_ok=True)
        key_path = dcfg.seal_key_file or os.path.join(
            dcfg.state_dir, "root.key"
        )
        self.root_key = load_or_create_root_key(key_path)
        self._c_records = self._c_fsyncs = self._c_ckpts = None
        self._g_durable = self._g_ckpt = self._g_replayed = None
        self._g_recovery_s = self._g_applied = None
        if registry is not None:
            self._c_records = registry.counter(
                "grapevine_journal_records_total",
                "batches + sweeps appended to the sealed journal")
            self._c_fsyncs = registry.counter(
                "grapevine_journal_fsyncs_total",
                "journal fsync barriers issued")
            self._c_ckpts = registry.counter(
                "grapevine_checkpoints_total",
                "sealed whole-state checkpoints written")
            self._g_durable = registry.gauge(
                "grapevine_last_durable_seq",
                "highest journal sequence fsynced to disk")
            self._g_ckpt = registry.gauge(
                "grapevine_last_checkpoint_seq",
                "journal sequence of the newest sealed checkpoint")
            self._g_replayed = registry.gauge(
                "grapevine_recovery_replayed_records",
                "journal records replayed during the last recovery")
            self._g_recovery_s = registry.gauge(
                "grapevine_recovery_seconds",
                "wall time of the last startup recovery")
            self._g_applied = registry.gauge(
                "grapevine_journal_applied_seq",
                "highest journal sequence applied to engine state (on "
                "the primary this tracks journal_seq; on a follower "
                "replaying shipped journal frames it is the replication "
                "frontier — the fleet aggregator derives "
                "grapevine_fleet_journal_lag_seq from it; ROADMAP "
                "item 4, OPERATIONS.md §20)")
        self.journal = BatchJournal(
            dcfg.state_dir, self.root_key, ecfg,
            fsync_every=dcfg.journal_fsync_every,
            on_fsync=self._note_fsync,
        )
        self.ckpt_seq = 0  # journal seq covered by the newest checkpoint
        #: highest journal seq applied to engine state. On the primary
        #: this tracks journal.seq (each record is applied as part of
        #: the round that journals it); on a follower consuming shipped
        #: frames it trails the primary's durable seq — the replication
        #: lag the fleet aggregator prices (obs/fleet.py).
        self.applied_seq = 0
        self.replayed = 0
        self.recovered_from_checkpoint = False

    # journal callback — runs under the engine lock with the append
    def _note_fsync(self, durable_seq: int) -> None:
        if self._c_fsyncs is not None:
            self._c_fsyncs.inc()
            self._g_durable.set(durable_seq)

    # -- recovery -------------------------------------------------------

    def recover(self, init_state: EngineState, apply_fn):
        """Restore state: newest checkpoint (if any) + journal replay.

        ``apply_fn(state, record)`` applies one journal record and
        returns the next state (the engine's jitted step/sweep).
        Corrupt checkpoints and mid-journal corruption raise — only a
        torn *tail* frame (the crash-mid-append case) is discarded."""
        t0 = time.monotonic()
        state = init_state
        latest = find_latest_checkpoint(self.dcfg.state_dir)
        if latest is not None:
            seq, state = load_checkpoint(
                latest[1], self.root_key, self.ecfg, self.state_shardings
            )
            if seq != latest[0]:
                # the filename seq picks which file to load; the sealed
                # payload seq is what replay trusts — a renamed file
                # must not shift the replay base
                raise CheckpointError(
                    f"{latest[1]}: filename seq {latest[0]} != sealed "
                    f"payload seq {seq} (file renamed?)"
                )
            self.ckpt_seq = seq
            self.recovered_from_checkpoint = True
        self.replayed = 0
        self.note_applied_seq(self.ckpt_seq)
        for rec in self.journal.replay(after_seq=self.ckpt_seq):
            state = apply_fn(state, rec)
            self.replayed += 1
            self.note_applied_seq(self.journal.seq)
            if self._g_replayed is not None:
                self._g_replayed.set(self.replayed)
        self.journal.open_for_append()
        if self._g_ckpt is not None:
            self._g_ckpt.set(self.ckpt_seq)
            self._g_durable.set(self.journal.seq)
            self._g_recovery_s.set(round(time.monotonic() - t0, 6))
        return state

    # -- steady state ---------------------------------------------------

    @property
    def seq(self) -> int:
        return self.journal.seq

    def note_applied_seq(self, seq: int) -> None:
        """Record that engine state now reflects journal records up to
        ``seq``. The primary calls this implicitly from the append path;
        a follower replaying shipped frames calls it per applied record
        — the gauge is what the fleet aggregator scrapes to derive
        replication lag."""
        self.applied_seq = seq
        if self._g_applied is not None:
            self._g_applied.set(seq)

    def append_round(self, batch: dict, n_real: int) -> int:
        seq = self.journal.append_round(batch, n_real)
        if self._c_records is not None:
            self._c_records.inc()
        self.note_applied_seq(seq)
        return seq

    def append_sweep(self, now: int, now_hi: int, period: int) -> int:
        seq = self.journal.append_sweep(now, now_hi, period)
        if self._c_records is not None:
            self._c_records.inc()
        self.note_applied_seq(seq)
        return seq

    def append_raw_frame(self, seq: int, frame: bytes) -> int:
        """Follower path (engine/replication.py): persist one shipped
        journal frame verbatim. Counts in the records telemetry exactly
        like a locally encoded record; the caller notes the applied seq
        only after the device apply succeeds."""
        seq = self.journal.append_raw(seq, frame)
        if self._c_records is not None:
            self._c_records.inc()
        return seq

    def install_checkpoint(self, seq: int, blob: bytes):
        """Standby bootstrap: persist a primary-shipped sealed
        checkpoint and re-base the local journal at it. The blob goes
        through the normal load path (seal + geometry fingerprint +
        payload seq) before anything is re-based, so a cross-knob or
        tampered checkpoint refuses with the standard fingerprint
        error; returns the loaded EngineState."""
        path = checkpoint_path(self.dcfg.state_dir, seq)
        tmp = f"{path}.tmp.{os.getpid()}"
        fd = os.open(tmp, os.O_WRONLY | os.O_CREAT | os.O_EXCL, 0o600)
        try:
            write_all(fd, blob)
            os.fsync(fd)
        finally:
            os.close(fd)
        os.replace(tmp, path)
        dfd = os.open(self.dcfg.state_dir, os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)
        got_seq, state = load_checkpoint(
            path, self.root_key, self.ecfg, self.state_shardings
        )
        if got_seq != seq:
            raise CheckpointError(
                f"{path}: shipped checkpoint payload seq {got_seq} != "
                f"advertised {seq}"
            )
        # re-base: fresh segment at seq+1; every older file is covered
        self.journal.seq = seq
        self.journal.durable_seq = seq
        self.journal.roll()
        prune_checkpoints(self.dcfg.state_dir, seq)
        self.ckpt_seq = seq
        self.recovered_from_checkpoint = True
        if self._c_ckpts is not None:
            self._c_ckpts.inc()
            self._g_ckpt.set(seq)
            self._g_durable.set(seq)
        self.note_applied_seq(seq)
        return state

    def should_checkpoint(self) -> bool:
        return (
            self.journal.seq - self.ckpt_seq
            >= self.dcfg.checkpoint_every_rounds
        )

    def checkpoint(self, state: EngineState) -> int:
        """Seal the current state at the current journal seq, then roll
        the journal and prune files the new checkpoint covers. Returns
        the checkpointed seq (also when skipped because nothing new was
        journaled)."""
        seq = self.journal.seq
        if seq == self.ckpt_seq and self.recovered_from_checkpoint:
            return seq  # nothing journaled since the last checkpoint
        # make the journal tail durable first: if the checkpoint crashes
        # half-way, recovery must still reach seq via the old chain
        self.journal.sync()
        write_checkpoint(
            self.dcfg.state_dir, self.root_key, self.ecfg, state, seq
        )
        self.ckpt_seq = seq
        self.recovered_from_checkpoint = True
        self.journal.roll()
        prune_checkpoints(self.dcfg.state_dir, seq)
        if self._c_ckpts is not None:
            self._c_ckpts.inc()
            self._g_ckpt.set(seq)
        return seq

    def status(self) -> dict:
        """Batch-level durability detail for /healthz."""
        return {
            "last_durable_seq": self.journal.durable_seq,
            "journal_seq": self.journal.seq,
            "applied_seq": self.applied_seq,
            "last_checkpoint_seq": self.ckpt_seq,
            "recovery_replayed_records": self.replayed,
            "journal_epoch": self.journal.epoch,
        }

    def close(self) -> None:
        self.journal.close()
