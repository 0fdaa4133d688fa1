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
  ``cryptography`` wheel is optional in this container). The stream
  runs in the native session library (``native/r255.c``
  ``r255_chacha20_xor``, :func:`stream_xor`): a round's journal frame
  is 2 MB and a checkpoint of the 2^21 bus 10 GB, sealed under the
  engine's lock. The numpy keystream (:func:`chacha20_xor`) is the
  plain reference the native one is pinned to, and what runs where the
  library did not build.
- **Streaming**: a checkpoint is written and read block by block
  (:data:`STREAM_BLOCK_BYTES`): device to host, seal, MAC, write, and
  back. The host holds a few blocks, never a copy of the state, and the
  device never a second state: a load writes into the state it is
  given, plane by plane, in place (:func:`load_checkpoint`).
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

import functools
import hashlib
import hmac
import json
import os
import re
import struct
import time

import jax
import numpy as np

from .. import native
from ..config import DurabilityConfig
from ..obs.phases import span as _plain_span
from ..testing import faults
from .state import EngineConfig, EngineState, state_spec

MAGIC = b"GVCKPT1\0"
#: 4 since PR 46: a value row stored on whole lane tiles is a
#: ``(tiles, 128)`` row of its plane (OramConfig.stored_row_shape), so
#: the manifest's shapes moved; the bytes are version 3's, row-major.
#: 3 was PR 44's: a value row of eight lane tiles or more stored on
#: whole tiles (OramConfig.stored_row_words: the mailbox row's 6,080
#: words as 6,144), so the planes' widths and the place of the
#: slot-index words in a row's keystream both moved. 2 was PR 40's
#: keystream order (oblivious/bucket_cipher.py); trees sealed under 1
#: or 2 would decrypt to noise. Refused, not migrated: re-initialise
#: the state.
VERSION = 4

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


#: threads of one native call on a block of a checkpoint; a journal
#: frame (2 MB) is sealed on the caller's alone
SEAL_THREADS = 4


def stream_xor(key: bytes, nonce: bytes, data, counter: int = 0) -> bytes:
    """:func:`chacha20_xor` at the speed of the memory it reads: the
    native library's ChaCha20 (``native.chacha20_xor``), or the numpy
    reference where the library did not build. Same bytes either way
    (tests/test_seal_stream.py)."""
    if native.lib is None:
        return chacha20_xor(key, nonce, bytes(data), counter)
    return bytes(native.chacha20_xor(key, nonce, counter, data))


def derive_key(root_key: bytes, label: bytes) -> bytes:
    """Per-domain 32-byte subkey: HMAC-SHA256(root, label)."""
    if len(root_key) != 32:
        raise ValueError("root key must be 32 bytes")
    return hmac.new(root_key, label, hashlib.sha256).digest()


def _seal_keys(root_key: bytes, domain: bytes) -> tuple[bytes, bytes]:
    """(encryption key, MAC key) of one sealing domain."""
    return (derive_key(root_key, b"grapevine-seal-enc:" + domain),
            derive_key(root_key, b"grapevine-seal-mac:" + domain))


def seal(root_key: bytes, domain: bytes, plaintext: bytes,
         aad: bytes = b"") -> bytes:
    """Encrypt-then-MAC: returns ``nonce(12) | ct | tag(32)``.

    ``domain`` separates key schedules (checkpoint vs journal);
    ``aad`` binds plaintext headers (magic, seq) into the tag without
    encrypting them."""
    enc, mac = _seal_keys(root_key, domain)
    nonce = os.urandom(12)
    ct = stream_xor(enc, nonce, plaintext)
    tag = hmac.new(mac, aad + nonce, hashlib.sha256)
    tag.update(ct)
    return nonce + ct + tag.digest()


def unseal(root_key: bytes, domain: bytes, blob: bytes,
           aad: bytes = b"") -> bytes:
    """Verify and decrypt a :func:`seal` blob; raises SealError on any
    truncation or integrity failure — never returns partial plaintext."""
    if len(blob) < 12 + 32:
        raise SealError("sealed blob truncated (shorter than nonce + tag)")
    blob = memoryview(blob)
    nonce, ct, tag = bytes(blob[:12]), blob[12:-32], blob[-32:]
    enc, mac = _seal_keys(root_key, domain)
    want = hmac.new(mac, aad + nonce, hashlib.sha256)
    want.update(ct)
    if not hmac.compare_digest(tag, want.digest()):
        raise SealError(_INTEGRITY)
    return stream_xor(enc, nonce, ct)


_INTEGRITY = ("sealed blob failed integrity check (torn, truncated, "
              "tampered, or sealed under a different root key)")


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


def _manifest(ecfg: EngineConfig, leaves) -> bytes:
    """The state payload's JSON head for ``leaves`` (anything with a
    ``dtype`` and a ``shape``: arrays, or ``state_spec``'s structs)."""
    manifest = {
        "version": VERSION,
        "fingerprint": engine_fingerprint(ecfg),
        "leaves": [[np.dtype(x.dtype).newbyteorder("<").str, list(x.shape)]
                   for x in leaves],
    }
    return json.dumps(manifest, separators=(",", ":")).encode()


def state_to_bytes(ecfg: EngineConfig, state: EngineState) -> bytes:
    """Serialize a (host-synced) EngineState: JSON manifest + raw leaf
    buffers in pytree order, in one piece. Blocks until the device state
    is ready. The plaintext of a checkpoint, and the plain reference of
    :func:`write_checkpoint`'s stream; tests and tools compare states by
    it. It holds the whole state on the host, several times over."""
    arrays = [np.asarray(leaf) for leaf in jax.tree_util.tree_leaves(state)]
    head = _manifest(ecfg, arrays)
    parts = [struct.pack("<I", len(head)), head]
    for a in arrays:
        le = np.ascontiguousarray(a).astype(
            a.dtype.newbyteorder("<"), copy=False
        )
        parts.append(le.tobytes())
    return b"".join(parts)


def _check_manifest(ecfg: EngineConfig, data) -> tuple[int, list]:
    """The checks on a state payload's head: returns (offset of the
    first leaf, the geometry's leaf specs) or raises CheckpointError for
    a truncated, unparseable, other-version or other-geometry one."""
    if len(data) < 4:
        raise CheckpointError("state payload truncated (no manifest)")
    (head_len,) = struct.unpack_from("<I", data, 0)
    if len(data) < 4 + head_len:
        raise CheckpointError("state payload truncated (manifest cut short)")
    try:
        manifest = json.loads(bytes(data[4 : 4 + head_len]))
    except ValueError as exc:
        raise CheckpointError(f"state manifest unparseable: {exc}") from None
    if not isinstance(manifest, dict):
        raise CheckpointError("state manifest unparseable: not an object")
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
    _, spec = state_spec(ecfg)
    decl = manifest.get("leaves", [])
    if len(decl) != len(spec):
        raise CheckpointError(
            f"state payload has {len(decl)} leaves, geometry wants "
            f"{len(spec)}"
        )
    for (dt_str, shape), want in zip(decl, spec):
        dt = np.dtype(dt_str)
        if tuple(shape) != tuple(want.shape) or dt.newbyteorder(
            "="
        ) != np.dtype(want.dtype):
            raise CheckpointError(
                f"state leaf mismatch: payload {dt_str}{tuple(shape)}, "
                f"geometry wants {np.dtype(want.dtype).str}"
                f"{tuple(want.shape)}"
            )
    return 4 + head_len, spec


def bytes_to_state(
    ecfg: EngineConfig, data: bytes, shardings=None
) -> EngineState:
    """Inverse of :func:`state_to_bytes`; rejects geometry mismatches and
    truncated buffers whole (CheckpointError).

    ``shardings``: a pytree of one ``jax.sharding.Sharding`` per state
    leaf (a mesh engine's live placement). Each leaf then goes from host
    memory straight to its shards' devices; without it, to the default
    device."""
    off, spec = _check_manifest(ecfg, data)
    treedef, _ = state_spec(ecfg)
    leaves = []
    for want, place in zip(spec, _places(shardings, len(spec))):
        dt = np.dtype(want.dtype).newbyteorder("<")
        nbytes = dt.itemsize * int(np.prod(want.shape, dtype=np.int64))
        if off + nbytes > len(data):
            raise CheckpointError("state payload truncated (leaf cut short)")
        arr = np.frombuffer(data, dt, count=nbytes // dt.itemsize, offset=off)
        leaves.append(jax.device_put(
            arr.reshape(want.shape).astype(dt.newbyteorder("=")), place
        ))
        off += nbytes
    if off != len(data):
        raise CheckpointError(
            f"state payload has {len(data) - off} trailing bytes"
        )
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _places(shardings, n: int) -> list:
    places = (jax.tree_util.tree_leaves(shardings)
              if shardings is not None else [None] * n)
    if len(places) != n:
        raise ValueError(
            f"shardings has {len(places)} leaves, state has {n}"
        )
    return places


# -- sealed checkpoint files, as streams --------------------------------

#: plaintext is staged, sealed, MACed and written (and read back) in
#: blocks of this many bytes; a multiple of ChaCha20's 64. A stream
#: owns one staging block; with the block a device transfer lands in
#: (or leaves from) and the copy the transfer itself may make, a
#: checkpoint's write or load holds at most :data:`STREAM_HOST_BLOCKS`
#: blocks on the host, whatever the state's size
#: (tests/test_seal_stream.py holds the writer and the loader to it)
STREAM_BLOCK_BYTES = 32 << 20
STREAM_HOST_BLOCKS = 3


def _xor_in_place(key: bytes, nonce: bytes, counter: int, view) -> None:
    """``view`` (u8 array) XOR the keystream from block ``counter``."""
    if native.lib is not None:
        native.chacha20_xor(key, nonce, counter, view, view,
                            threads=SEAL_THREADS)
    else:
        view[:] = np.frombuffer(
            chacha20_xor(key, nonce, view.tobytes(), counter), np.uint8)


def _as_bytes(data) -> np.ndarray:
    """A flat u8 view of a contiguous array or a bytes-like."""
    if isinstance(data, np.ndarray):
        return np.ascontiguousarray(data).reshape(-1).view(np.uint8)
    return np.frombuffer(data, np.uint8)


class _SealedWriter:
    """:func:`seal` as a stream into ``fd``: ``nonce | ct | tag`` for
    the plaintext handed to :meth:`write`, the same bytes ``seal`` gives
    for the same key, nonce and plaintext. One staging block: filled,
    encrypted with the running block counter and MACed (span
    ``checkpoint_seal``), written (``checkpoint_write``), filled
    again."""

    def __init__(self, fd: int, root_key: bytes, domain: bytes, aad: bytes,
                 span):
        self._enc, mac = _seal_keys(root_key, domain)
        self._nonce = os.urandom(12)
        self._mac = hmac.new(mac, aad + self._nonce, hashlib.sha256)
        self._fd, self._span = fd, span
        if STREAM_BLOCK_BYTES <= 0 or STREAM_BLOCK_BYTES % 64:
            raise ValueError(
                "STREAM_BLOCK_BYTES must be a positive multiple of 64")
        self._buf, self._fill = np.empty(STREAM_BLOCK_BYTES, np.uint8), 0
        self._blocks = 0  # ChaCha20 blocks sealed so far
        #: plaintext bytes taken so far
        self.taken = 0
        write_all(fd, self._nonce)

    def write(self, data) -> None:
        src = _as_bytes(data)
        off = 0
        while off < src.size:
            with self._span("checkpoint_seal"):
                n = min(src.size - off, self._buf.size - self._fill)
                self._buf[self._fill:self._fill + n] = src[off:off + n]
                self._fill += n
                off += n
            if self._fill == self._buf.size:
                self.flush()
        self.taken += src.size

    def flush(self) -> None:
        """Seal and write what is staged. Only the stream's last block
        may be short of a whole one."""
        block = self._buf[:self._fill]
        if not block.size:
            return
        with self._span("checkpoint_seal"):
            _xor_in_place(self._enc, self._nonce, self._blocks, block)
            self._mac.update(block)
        with self._span("checkpoint_write"):
            write_all(self._fd, block)
        self._blocks += -(-block.size // 64)
        self._fill = 0

    def close(self) -> None:
        """The last block, then the tag."""
        self.flush()
        with self._span("checkpoint_write"):
            write_all(self._fd, self._mac.digest())


def _block_rows(n_rows: int, nbytes: int, block_bytes: int) -> int:
    """Rows of a plane that make one block: whole sublane tiles of 8
    where a block holds that many, so that a slice starts on one."""
    rows = max(1, block_bytes // max(1, nbytes // max(1, n_rows)))
    return rows - rows % 8 if rows > 8 else rows


@functools.partial(jax.jit, static_argnums=(2,))
def _rows(plane, start, n: int):
    return jax.lax.dynamic_slice_in_dim(plane, start, n, axis=0)


@functools.partial(jax.jit, donate_argnums=(0,))
def _put_rows(plane, block, start):
    return jax.lax.dynamic_update_slice_in_dim(plane, block, start, axis=0)


def _single_device_parts(leaf) -> list:
    """Arrays on one device each that, laid end to end along axis 0,
    are ``leaf``: the leaf itself, or a mesh engine's row shards. A
    leaf sharded any other way comes back as one host array."""
    if (not isinstance(leaf, jax.Array) or leaf.ndim == 0
            or len(leaf.sharding.device_set) == 1):
        return [leaf]
    if leaf.is_fully_replicated:
        return [leaf.addressable_shards[0].data]
    by_start: dict[int, jax.Array] = {}
    for shard in leaf.addressable_shards:
        rows, *rest = shard.index
        if any(sl != slice(None) and (sl.start or 0, sl.stop) != (0, dim)
               for sl, dim in zip(rest, leaf.shape[1:])):
            return [np.asarray(leaf)]
        by_start.setdefault(rows.start or 0, shard.data)
    return [by_start[k] for k in sorted(by_start)]


def _host_blocks(leaf, block_bytes: int):
    """``leaf`` on the host, little-endian, in pytree byte order, in
    pieces of about ``block_bytes``: whole where it is smaller, else
    row block by row block, each sliced on its device and copied from
    there, so that neither side ever holds a second copy of a plane."""
    for part in _single_device_parts(leaf):
        n_rows = part.shape[0] if part.ndim else 1
        rows = _block_rows(n_rows, part.nbytes, block_bytes)
        if part.nbytes <= block_bytes or isinstance(part, np.ndarray):
            pieces = [part]
        else:
            pieces = (_rows(part, r0, min(rows, n_rows - r0))
                      for r0 in range(0, n_rows, rows))
        for piece in pieces:
            a = np.asarray(piece)
            yield np.ascontiguousarray(a).astype(
                a.dtype.newbyteorder("<"), copy=False)


def checkpoint_path(state_dir: str, seq: int) -> str:
    return os.path.join(state_dir, f"ckpt-{seq:016d}.sealed")


def _fsync_dir(path: str) -> None:
    dfd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(dfd)
    finally:
        os.close(dfd)


def write_checkpoint(
    state_dir: str, root_key: bytes, ecfg: EngineConfig,
    state: EngineState, seq: int, *, span=_plain_span,
) -> str:
    """Atomically write the sealed checkpoint for journal seq ``seq``.

    tmp + fsync + rename + directory fsync: a crash at any point leaves
    either the previous checkpoint set or the new file complete — never
    a half-written ``ckpt-*.sealed``.

    The file is ``MAGIC | version | seal(seq | state_to_bytes(state))``
    byte for byte, written as a stream: leaf by leaf and, for a plane,
    row block by row block, each copied from the device (span
    ``checkpoint_read``), staged and encrypted with the running block
    counter and MACed (``checkpoint_seal``), written
    (``checkpoint_write``: the writes, then fsync, rename and directory
    fsync), on the caller's thread, one after the other. ``span(name)``
    opens a span of the caller's."""
    leaves = jax.tree_util.tree_leaves(state)
    manifest = _manifest(ecfg, leaves)
    total = 8 + 4 + len(manifest) + sum(x.nbytes for x in leaves)
    if total > 64 << 32:
        # one nonce, one 32-bit block counter (RFC 7539): 256 GiB
        raise CheckpointError(
            f"a state of {total} bytes is past one ChaCha20 stream")
    head = MAGIC + struct.pack("<I", VERSION)
    path = checkpoint_path(state_dir, seq)
    tmp = path + f".tmp.{os.getpid()}"
    torn = faults.active() and faults.hit("checkpoint.tmp.torn")
    fd = os.open(tmp, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o600)
    try:
        write_all(fd, head)
        out = _SealedWriter(fd, root_key, b"checkpoint", head, span)
        out.write(struct.pack("<QI", seq, len(manifest)))
        out.write(manifest)
        for leaf in leaves:
            blocks = _host_blocks(leaf, STREAM_BLOCK_BYTES)
            while True:
                with span("checkpoint_read"):
                    block = next(blocks, None)
                if block is None:
                    break
                out.write(block)
                if torn and out.taken >= total // 2:
                    out.flush()
                    os.fsync(fd)
                    faults.die()
        out.close()
        with span("checkpoint_write"):
            os.fsync(fd)
    finally:
        os.close(fd)
    if faults.active():
        faults.crash("checkpoint.pre_rename")
    with span("checkpoint_write"):
        os.replace(tmp, path)
        _fsync_dir(state_dir)
    if faults.active():
        faults.crash("checkpoint.post_rename")
    return path


class _SealedReader:
    """:func:`unseal` as a stream from ``fh``, positioned at the nonce,
    with ``ct_len`` bytes of ciphertext and the tag behind it: read,
    MACed and decrypted block by block into one staging block, handed
    on in order by :meth:`readinto`. The tag is known good or bad only
    at :meth:`finish`: until then what was read is bytes to move, not
    to believe."""

    def __init__(self, fh, root_key: bytes, domain: bytes, aad: bytes,
                 ct_len: int):
        self._enc, mac = _seal_keys(root_key, domain)
        self._nonce = fh.read(12)
        self._mac = hmac.new(mac, aad + self._nonce, hashlib.sha256)
        self._fh, self._left = fh, ct_len
        self._buf = np.empty(STREAM_BLOCK_BYTES, np.uint8)
        self._n = self._off = 0  # plaintext staged, and handed on
        self._blocks = 0

    def _next_block(self) -> None:
        n = min(self._buf.size, self._left)
        if not n:
            raise CheckpointError("state payload truncated (leaf cut short)")
        block = self._buf[:n]
        if self._fh.readinto(memoryview(block)) != n:
            raise CheckpointError("sealed file cut short")
        self._mac.update(block)
        _xor_in_place(self._enc, self._nonce, self._blocks, block)
        self._blocks += -(-n // 64)
        self._left -= n
        self._n, self._off = n, 0

    def readinto(self, dst: np.ndarray) -> None:
        """Fill ``dst`` (flat u8) with the next plaintext bytes."""
        off = 0
        while off < dst.size:
            if self._off == self._n:
                self._next_block()
            n = min(dst.size - off, self._n - self._off)
            dst[off:off + n] = self._buf[self._off:self._off + n]
            off += n
            self._off += n

    def finish(self) -> bool:
        """Whether every byte of ciphertext was taken (the caller sized
        the stream: ``ct_len`` is what it reads) and the tag verifies."""
        return (self._left == 0 and self._off == self._n
                and hmac.compare_digest(self._fh.read(32),
                                        self._mac.digest()))


def _refuse_unfit(fh, path: str, root_key: bytes, ecfg: EngineConfig,
                  head: bytes, ct_len: int, want_len: int) -> None:
    """A checkpoint whose size is not this geometry's: authenticate it
    (a stream of MAC updates, no plaintext), then say what it is. Always
    raises CheckpointError."""
    enc, mac = _seal_keys(root_key, b"checkpoint")
    nonce = fh.read(12)
    tag = hmac.new(mac, head + nonce, hashlib.sha256)
    first, left = b"", ct_len
    while left:
        chunk = fh.read(min(left, 1 << 20))
        if not chunk:
            break
        first = first or chunk
        tag.update(chunk)
        left -= len(chunk)
    if left or not hmac.compare_digest(fh.read(32), tag.digest()):
        raise CheckpointError(f"{path}: {_INTEGRITY}")
    if ct_len < 8:
        raise CheckpointError(f"{path}: payload truncated")
    _check_manifest(ecfg, stream_xor(enc, nonce, first)[8:])
    raise CheckpointError(
        "state payload truncated (leaf cut short)" if ct_len < want_len
        else f"state payload has {ct_len - want_len} trailing bytes")


def _load_leaf(reader: _SealedReader, want, place, old, block_bytes: int):
    """One leaf from the stream to its device. A plane on one device is
    written row block by row block into the array that is there (``old``,
    donated to each update, or zeros): never two of it. Anything else is
    staged whole on the host and takes ``old``'s place."""
    dt = np.dtype(want.dtype).newbyteorder("<")
    shape = tuple(want.shape)
    nbytes = dt.itemsize * int(np.prod(shape, dtype=np.int64))
    one_device = place is None or len(place.device_set) == 1
    if nbytes <= block_bytes or not shape or not one_device:
        arr = np.empty(shape, dt)
        reader.readinto(arr.reshape(-1).view(np.uint8))
        if old is not None and nbytes > block_bytes:
            old.delete()  # a mesh engine's plane: gone before its heir
        return jax.device_put(
            arr.astype(dt.newbyteorder("="), copy=False), place)
    plane = old if old is not None else jax.device_put(
        jax.numpy.zeros(shape, want.dtype), place)
    rows = _block_rows(shape[0], nbytes, block_bytes)
    staged = np.empty((rows,) + shape[1:], dt)
    for r0 in range(0, shape[0], rows):
        block = staged[: min(rows, shape[0] - r0)]
        reader.readinto(block.reshape(-1).view(np.uint8))
        # waited for, so that the one staging block can be filled again
        plane = jax.block_until_ready(
            _put_rows(plane, block.astype(dt.newbyteorder("="), copy=False),
                      r0))
    return plane


def load_checkpoint(
    path: str, root_key: bytes, ecfg: EngineConfig, shardings=None,
    into: EngineState | None = None,
) -> tuple[int, EngineState]:
    """Load a sealed checkpoint; returns ``(seq, state)``. Any
    truncation, tamper, or geometry mismatch raises CheckpointError and
    no state comes back.

    The file is read once, as a stream (:class:`_SealedReader`), and
    its leaves go to the device as they arrive, laid out by *this
    engine's* geometry (``state_spec``), which also fixes the file's
    length to the byte: a file of any other length is authenticated and
    refused before a byte of it is decrypted for use. ``into``: a state
    of this geometry that the load consumes, writing each plane into the
    array that is there, so that the device never holds a second state;
    without it the planes are made here. Until the tag has verified,
    nothing that was read is looked at (the manifest, the seq) or
    handed out; a load that fails has consumed ``into`` all the same,
    which is why a failed recovery leaves no engine (``GrapevineEngine.
    recover``). ``shardings``: see :func:`bytes_to_state`; a leaf that
    lies on several devices is staged whole on the host."""
    head = MAGIC + struct.pack("<I", VERSION)
    treedef, spec = state_spec(ecfg)
    manifest = _manifest(ecfg, spec)
    want_len = 8 + 4 + len(manifest) + sum(
        np.dtype(x.dtype).itemsize * int(np.prod(x.shape, dtype=np.int64))
        for x in spec)
    olds = (jax.tree_util.tree_leaves(into) if into is not None
            else [None] * len(spec))
    with open(path, "rb") as fh:
        got = fh.read(len(head))
        if len(got) < len(head) or got[: len(MAGIC)] != MAGIC:
            raise CheckpointError(f"{path}: not a grapevine checkpoint")
        if got != head:
            (ver,) = struct.unpack_from("<I", got, len(MAGIC))
            raise CheckpointError(f"{path}: version {ver}, want {VERSION}")
        ct_len = os.fstat(fh.fileno()).st_size - len(head) - 12 - 32
        if ct_len < 0:
            raise CheckpointError(
                f"{path}: sealed blob truncated (shorter than nonce + tag)")
        if ct_len != want_len:
            _refuse_unfit(fh, path, root_key, ecfg, head, ct_len, want_len)
        reader = _SealedReader(fh, root_key, b"checkpoint", head, ct_len)
        first = np.empty(12 + len(manifest), np.uint8)
        reader.readinto(first)
        leaves = [
            _load_leaf(reader, want, place, old, STREAM_BLOCK_BYTES)
            for want, place, old in zip(
                spec, _places(shardings, len(spec)), olds)
        ]
        if not reader.finish():
            raise CheckpointError(f"{path}: {_INTEGRITY}")
    # authenticated: now the plaintext may say what it is
    seq, head_len = struct.unpack_from("<QI", first, 0)
    if head_len != len(manifest) or first[12:].tobytes() != manifest:
        _check_manifest(ecfg, first[8:].tobytes())
        raise CheckpointError(
            f"{path}: state manifest is not this engine's geometry's")
    return seq, jax.tree_util.tree_unflatten(treedef, leaves)


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

    def __init__(self, dcfg, ecfg: EngineConfig,
                 registry=None, state_shardings=None, span=_plain_span):
        self.dcfg = dcfg = DurabilityConfig.coerce(dcfg)
        self.ecfg = ecfg
        #: ``span(name, ledger)``: the engine's span primitive with its
        #: phase histogram behind it (``EngineMetrics.span``)
        self._span = span
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
        self._g_recovery_s = self._g_applied = self._g_load_s = None
        self._g_ckpt_s = self._g_ckpt_bytes = None
        self._c_replayed = self._c_replay_s = None
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
            self._c_replayed = registry.counter(
                "grapevine_recover_replayed_total",
                "journal records replayed by this process's recoveries, "
                "by kind", labels={"kind": ("round", "sweep")})
            self._c_replay_s = registry.counter(
                "grapevine_recover_replay_seconds",
                "wall seconds this process's recoveries spent replaying "
                "the journal's tail: the first frame read to the device "
                "ready with the last (over grapevine_recover_replayed_"
                "total: the replay rate, the RTO's second term)")
            self._g_recovery_s = registry.gauge(
                "grapevine_recovery_seconds",
                "wall time of the last startup recovery")
            self._g_load_s = registry.gauge(
                "grapevine_recovery_load_seconds",
                "the part of the last recovery spent loading the "
                "checkpoint, file to device, waited for (0: none found)")
            self._g_ckpt_s = registry.gauge(
                "grapevine_checkpoint_seconds",
                "wall time of the last sealed checkpoint, journal sync "
                "to journal roll: how long it held the engine's lock")
            self._g_ckpt_bytes = registry.gauge(
                "grapevine_checkpoint_bytes",
                "size of the last sealed checkpoint's file")
            self._g_applied = registry.gauge(
                "grapevine_journal_applied_seq",
                "highest journal sequence applied to engine state (on "
                "the primary this tracks journal_seq; on a follower "
                "replaying shipped journal frames it is the replication "
                "frontier — the fleet aggregator derives "
                "grapevine_fleet_journal_lag_seq from it; ROADMAP "
                "item 4, OPERATIONS.md §20)")
        self.journal = self._new_journal()
        self.ckpt_seq = 0  # journal seq covered by the newest checkpoint
        #: highest journal seq applied to engine state. On the primary
        #: this tracks journal.seq (each record is applied as part of
        #: the round that journals it); on a follower consuming shipped
        #: frames it trails the primary's durable seq — the replication
        #: lag the fleet aggregator prices (obs/fleet.py).
        self.applied_seq = 0
        self.replayed = 0
        self.recovered_from_checkpoint = False

    def _new_journal(self):
        from .journal import BatchJournal

        return BatchJournal(
            self.dcfg.state_dir, self.root_key, self.ecfg,
            fsync_every=self.dcfg.journal_fsync_every,
            on_fsync=self._note_fsync,
        )

    def abandon(self) -> None:
        """What a SIGKILL leaves of this manager: the journal's handle
        dropped with no sync and no checkpoint, and every count of
        this process forgotten. :meth:`recover` then starts from the
        state directory alone, as a new process would."""
        doorbell = self.journal.on_append
        self.journal.abandon()
        self.journal = self._new_journal()
        self.journal.on_append = doorbell
        self.ckpt_seq = self.applied_seq = self.replayed = 0
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
        torn *tail* frame (the crash-mid-append case) is discarded.

        ``init_state`` is consumed: a checkpoint's planes are written
        into its arrays (:func:`load_checkpoint` ``into``), so the
        device holds one state throughout. After a raise it is not to
        be used."""
        from .journal import KIND_ROUND

        t0 = time.monotonic()
        state = init_state
        load_s = 0.0
        latest = find_latest_checkpoint(self.dcfg.state_dir)
        if latest is not None:
            seq, state = load_checkpoint(
                latest[1], self.root_key, self.ecfg, self.state_shardings,
                into=init_state,
            )
            jax.block_until_ready(state)
            load_s = time.monotonic() - t0
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
        t_replay = time.monotonic()
        for rec in self.journal.replay(after_seq=self.ckpt_seq):
            state = apply_fn(state, rec)
            self.replayed += 1
            self.note_applied_seq(self.journal.seq)
            if self._g_replayed is not None:
                self._g_replayed.set(self.replayed)
                self._c_replayed.inc(
                    kind="round" if rec.kind == KIND_ROUND else "sweep")
        self.journal.open_for_append()
        # the replay is dispatched, not yet applied: the recovery ends
        # when the device has it
        jax.block_until_ready(state)
        if self._c_replay_s is not None:
            self._c_replay_s.inc(time.monotonic() - t_replay)
        if self._g_ckpt is not None:
            self._g_ckpt.set(self.ckpt_seq)
            self._g_durable.set(self.journal.seq)
            self._g_recovery_s.set(round(time.monotonic() - t0, 6))
            self._g_load_s.set(round(load_s, 6))
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
        _fsync_dir(self.dcfg.state_dir)
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

    def checkpoint(self, state: EngineState, ledger: dict | None = None) -> int:
        """Seal the current state at the current journal seq, then roll
        the journal and prune files the new checkpoint covers. Returns
        the checkpointed seq (also when skipped because nothing new was
        journaled). ``ledger``: the span ledger of the round whose
        dispatch the checkpoint fell due in; its parts
        (``checkpoint_read`` / ``_seal`` / ``_write``) land there beside
        the caller's own ``checkpoint`` span."""
        seq = self.journal.seq
        if seq == self.ckpt_seq and self.recovered_from_checkpoint:
            return seq  # nothing journaled since the last checkpoint
        t0 = time.monotonic()
        # make the journal tail durable first: if the checkpoint crashes
        # half-way, recovery must still reach seq via the old chain
        self.journal.sync()
        path = write_checkpoint(
            self.dcfg.state_dir, self.root_key, self.ecfg, state, seq,
            span=lambda name: self._span(name, ledger),
        )
        self.ckpt_seq = seq
        self.recovered_from_checkpoint = True
        self.journal.roll()
        prune_checkpoints(self.dcfg.state_dir, seq)
        if self._c_ckpts is not None:
            self._c_ckpts.inc()
            self._g_ckpt.set(seq)
            self._g_ckpt_s.set(round(time.monotonic() - t0, 6))
            self._g_ckpt_bytes.set(os.path.getsize(path))
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
