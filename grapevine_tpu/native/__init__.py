"""Build-on-first-import ctypes loader for the native session library.

r255.c is compiled with the system C compiler into a cached shared
object next to the source (the build-time codegen analog of the
reference's api/build.rs protoc step). If the library cannot be built
or loaded the package degrades to the pure-Python paths — callers must
treat ``lib`` as Optional — and ``load_error`` says why, so a server
can log the degradation instead of serving sr25519 at pure-Python
speed without a word (:func:`log_state`).

Thread-safety contract, per wrapper class:

- the single-item group wrappers (verify1, reencode, mult_base) hold
  the module lock because their C functions use static scratch (the
  decoded-key cache): signing and the bisect's leaves, never a round's
  critical path;
- chunk_check and the STROBE/merlin/keccak wrappers are deliberately
  LOCK-FREE and in exchange their C functions must never write static
  scratch — they touch only the caller's buffers and their own heap
  arena, because a round's chunk checks run on several threads at once
  (inside one call, for which ctypes releases the GIL) and gRPC worker
  threads run transcripts concurrently.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from array import array
from pathlib import Path

_DIR = Path(__file__).parent
_SRC = _DIR / "r255.c"
_SO = _DIR / "_r255.so"

_lock = threading.Lock()
lib = None
#: why ``lib`` is None (no compiler, failed build, unloadable or stale
#: library); None while the native library is live
load_error: str | None = None


class _NativeUnavailable(Exception):
    """The library could not be built; the message is ``load_error``."""


def _build(force: bool = False) -> Path:
    try:
        fresh = _SO.exists() and _SO.stat().st_mtime >= _SRC.stat().st_mtime
        if fresh and not force:
            return _SO
    except OSError:
        # a cached .so without the C source: use it
        if _SO.exists() and not force:
            return _SO
        raise _NativeUnavailable(f"neither {_SRC.name} nor {_SO.name} found")
    # compile to a private temp file, then atomically rename: concurrent
    # importers (pytest workers, server + bench) must never dlopen a
    # half-written .so or have a mapped one rewritten under them
    tmp = _DIR / f"_r255.{os.getpid()}.tmp.so"
    cc = os.environ.get("CC", "cc")
    cmd = [cc, "-O2", "-shared", "-fPIC", "-pthread", "-o", str(tmp),
           str(_SRC)]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, _SO)
    except (OSError, subprocess.SubprocessError) as e:
        tmp.unlink(missing_ok=True)
        err = getattr(e, "stderr", b"") or b""
        raise _NativeUnavailable(
            f"building {_SRC.name} with {cc!r} failed: {e} "
            f"{err.decode(errors='replace')[-300:]}".strip()
        ) from e
    return _SO


def _open(so: Path):
    """Bind the library at ``so``. A library that lacks an export is
    unmapped again before AttributeError goes on: the dynamic loader
    finds a mapped library by its NAME, so the rebuilt file would
    otherwise never be read."""
    handle = ctypes.CDLL(str(so))
    try:
        return _bind(handle)
    except AttributeError:
        import _ctypes

        _ctypes.dlclose(handle._handle)
        raise


def _load():
    global load_error
    load_error = None
    try:
        try:
            bound = _open(_build())
        except AttributeError:
            # a cached .so newer than the source it was NOT built from
            # (missing a newer export): rebuild once. Degrading here
            # would serve sr25519 on the pure-Python path, seconds a
            # round where the native check takes milliseconds.
            bound = _open(_build(force=True))
    except _NativeUnavailable as e:
        load_error = str(e)
    except OSError as e:
        load_error = f"loading {_SO.name} failed: {e}"
    except AttributeError as e:
        load_error = f"{_SO.name} is stale after a rebuild (missing export: {e})"
    else:
        if bound is not None:
            return bound
        load_error = f"{_SO.name}: r255_init failed"
    return None


def log_state(logger) -> None:
    """One line on which sr25519/merlin backend is live: INFO when the
    native library loaded, WARNING (with the reason) when the session
    crypto is running on the pure-Python paths."""
    if lib is not None:
        logger.info("native session library %s loaded", _SO.name)
    else:
        logger.warning(
            "native session library unavailable (%s): sr25519 verify and "
            "merlin transcripts run on the pure-Python paths",
            load_error,
        )


def _bind(handle):
    handle.r255_init.restype = ctypes.c_int
    handle.r255_verify1.restype = ctypes.c_int
    handle.r255_verify1.argtypes = [ctypes.c_char_p] * 4
    handle.r255_round_check.restype = ctypes.c_int
    handle.r255_round_check.argtypes = (
        [ctypes.c_size_t] * 2 + [ctypes.c_char_p] * 7
        + [ctypes.POINTER(ctypes.c_double)])
    handle.r255_chunk_scalars.restype = ctypes.c_int
    handle.r255_chunk_scalars.argtypes = (
        [ctypes.c_size_t] + [ctypes.c_char_p] * 7
        + [ctypes.POINTER(ctypes.c_char)] * 2
    )
    handle.r255_encode.restype = ctypes.c_int
    handle.r255_encode.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
    handle.r255_mult_base.restype = ctypes.c_int
    handle.r255_mult_base.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
    handle.r255_keccak_f1600.restype = None
    handle.r255_keccak_f1600.argtypes = [ctypes.POINTER(ctypes.c_char)]
    handle.r255_strobe_op.restype = ctypes.c_int
    handle.r255_strobe_op.argtypes = [
        ctypes.POINTER(ctypes.c_char), ctypes.c_int, ctypes.c_char_p,
        ctypes.c_size_t, ctypes.POINTER(ctypes.c_char), ctypes.c_int,
    ]
    handle.r255_merlin_append.restype = None
    handle.r255_merlin_append.argtypes = [
        ctypes.POINTER(ctypes.c_char), ctypes.c_char_p, ctypes.c_size_t,
        ctypes.c_char_p, ctypes.c_size_t,
    ]
    handle.r255_merlin_challenge.restype = None
    handle.r255_merlin_challenge.argtypes = [
        ctypes.POINTER(ctypes.c_char), ctypes.c_char_p, ctypes.c_size_t,
        ctypes.POINTER(ctypes.c_char), ctypes.c_size_t,
    ]
    handle.r255_schnorrkel_challenge.restype = None
    handle.r255_schnorrkel_challenge.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t,
        ctypes.c_char_p, ctypes.c_char_p, ctypes.POINTER(ctypes.c_char),
    ]
    handle.r255_chacha20_xor.restype = ctypes.c_int
    handle.r255_chacha20_xor.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_uint64, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_size_t, ctypes.c_size_t,
    ]
    if handle.r255_init() != 0:
        return None
    return handle


lib = _load()


def chacha20_xor(key: bytes, nonce: bytes, counter: int, src, dst=None,
                 threads: int = 1):
    """``src`` XOR the RFC 7539 ChaCha20 keystream from block
    ``counter`` on, written to ``dst``: any writable buffer of
    ``src``'s length (``src`` itself seals in place), or a new
    ``bytearray``, which is returned either way. On up to ``threads``
    threads inside the call, with no GIL held and no module lock (the C
    function writes its stack and ``dst``). Requires ``lib is not
    None``; ``engine/checkpoint.py`` holds the numpy reference this is
    pinned to. ValueError for a wrong key or nonce length, buffers of
    different lengths, or a counter that would pass 2^32 blocks."""
    import numpy as np

    if len(key) != 32 or len(nonce) != 12:
        raise ValueError("key must be 32 bytes, nonce 12")
    src_a = np.frombuffer(src, np.uint8)
    if dst is None:
        dst = bytearray(src_a.size)
    dst_a = np.frombuffer(dst, np.uint8)
    if dst_a.size != src_a.size or not dst_a.flags.writeable:
        raise ValueError("dst must be a writable buffer of src's length")
    if src_a.size and lib.r255_chacha20_xor(
            bytes(key), bytes(nonce), counter, src_a.ctypes.data,
            dst_a.ctypes.data, src_a.size, threads) != 0:
        raise ValueError("ChaCha20 block counter would pass 2^32")
    return dst


def verify1(pub: bytes, r_enc: bytes, s: bytes, k: bytes) -> int:
    """1 valid, 0 invalid, -1 malformed. Requires ``lib is not None``."""
    with _lock:
        return lib.r255_verify1(pub, r_enc, s, k)


def _chunk_args(pubs, sigs, rand, prefix, msgs, ks):
    """The chunk calls' arguments from lists of byte strings, lengths
    checked here so the C side can trust every pointer's extent: 32-byte
    keys, 64-byte signatures, 16 random bytes an item, and either the
    203-byte STROBE ``prefix`` with the signed ``msgs`` or 32-byte
    challenges ``ks``. None when a length is off (no such signature)."""
    n = len(pubs)
    if (len(sigs) != n or len(rand) != 16 * n
            or any(len(p) != 32 for p in pubs)
            or any(len(s) != 64 for s in sigs)):
        return None
    if prefix is not None:
        if len(prefix) != 203 or len(msgs) != n:
            return None
        mlens = array("I", map(len, msgs)).tobytes()
        return (n, b"".join(pubs), b"".join(sigs), rand, prefix,
                b"".join(msgs), mlens, None)
    if ks is None or len(ks) != n or any(len(k) != 32 for k in ks):
        return None
    return (n, b"".join(pubs), b"".join(sigs), rand, None, None, None,
            b"".join(ks))


def chunk_check(pubs, sigs, rand: bytes, *, prefix: bytes | None = None,
                msgs=None, ks=None, chunks: int = 1) -> int:
    """The batch equation over signatures in one crossing: 1 all
    verify, 0 an equation fails, negative for input that cannot be
    signatures. ``pubs`` / ``sigs`` / ``msgs`` / ``ks`` are lists of
    byte strings, ``rand`` 16 unpredictable bytes an item. With
    ``chunks`` > 1 the items are checked as that many contiguous chunks,
    each its own equation, on as many threads inside the call
    (r255_round_check; server/scheduler.py picks the number).

    No module lock, and the GIL is released for the length of the call:
    a chunk check writes only its own stack and heap arena, so any
    number of calls and chunks run side by side.

    Two spans of the calling thread (obs/phases.py): ``verify_prep``,
    the arguments joined under the GIL, and ``verify_native``, the
    foreign call itself, which is also told the call's own seconds so
    that the wait to get the GIL back is known. Inside a scheduler's
    ``verify`` they land in that round's ledger; elsewhere they cost two
    clock reads each and record nothing."""
    # imported here: a client process that only signs never loads the
    # observability package
    from ..obs.phases import span

    with span("verify_prep"):
        args = _chunk_args(pubs, sigs, rand, prefix, msgs, ks)
    if args is None:
        return -1
    elapsed = ctypes.c_double()
    with span("verify_native") as crossing:
        rc = lib.r255_round_check(args[0], chunks, *args[1:],
                                  ctypes.byref(elapsed))
        crossing.native_s = elapsed.value
    return rc


def chunk_scalars(pubs, sigs, rand: bytes, *, prefix: bytes | None = None,
                  msgs=None, ks=None) -> tuple[bytes, bytes] | None:
    """Test hook: the scalars chunk_check derives, as (64 bytes an item:
    z_i ‖ z_i*k_i mod L, sum z_i*s_i mod L); None for input it refuses."""
    args = _chunk_args(pubs, sigs, rand, prefix, msgs, ks)
    if args is None:
        return None
    scal = ctypes.create_string_buffer(64 * args[0])
    sb = ctypes.create_string_buffer(32)
    if lib.r255_chunk_scalars(*args, scal, sb) != 0:
        return None
    return bytes(scal.raw), bytes(sb.raw)


def reencode(enc: bytes) -> bytes | None:
    out = ctypes.create_string_buffer(32)
    with _lock:
        rc = lib.r255_encode(out, enc)
    return bytes(out.raw) if rc == 0 else None


def keccak_f1600(state: bytearray) -> None:
    """In-place Keccak-f[1600] on a 200-byte state (merlin hot path).

    No module lock: the C function writes only the caller's buffer (no
    static scratch), so concurrent calls on distinct states are safe."""
    buf = (ctypes.c_char * 200).from_buffer(state)
    lib.r255_keccak_f1600(buf)


def mult_base(scalar_le: bytes) -> bytes | None:
    """Encoded ``scalar * basepoint`` (scalar: 32B LE, already reduced).

    The client-side signing hot path (session/ristretto.py:sign does two
    of these per request when cold, one when the pubkey is cached)."""
    out = ctypes.create_string_buffer(32)
    with _lock:
        rc = lib.r255_mult_base(out, scalar_le)
    return bytes(out.raw) if rc == 0 else None


# -- STROBE-128 / merlin transcript ops (session/merlin.py hot path) ---
# No module lock on any of these: the C functions touch only the
# caller's 203-byte blob (state ‖ pos ‖ pos_begin ‖ cur_flags), so
# concurrent calls on distinct transcripts are safe.

def strobe_op(blob: bytearray, op: int, data: bytes, more: bool) -> int:
    """One STROBE op: 0=meta_ad 1=ad 3=key. Returns 0, or <0 on a
    continued-op flag mismatch (caller raises)."""
    buf = (ctypes.c_char * 203).from_buffer(blob)
    return lib.r255_strobe_op(buf, op, data, len(data), None, 1 if more else 0)


def strobe_prf(blob: bytearray, n: int, more: bool) -> bytes | None:
    """PRF squeeze of ``n`` bytes; None on flag mismatch."""
    buf = (ctypes.c_char * 203).from_buffer(blob)
    out = ctypes.create_string_buffer(n)
    rc = lib.r255_strobe_op(buf, 2, None, n, out, 1 if more else 0)
    return bytes(out.raw) if rc == 0 else None


def merlin_append(blob: bytearray, label: bytes, message: bytes) -> None:
    """merlin append_message in one crossing (meta_ad + len + ad)."""
    buf = (ctypes.c_char * 203).from_buffer(blob)
    lib.r255_merlin_append(buf, label, len(label), message, len(message))


def merlin_challenge(blob: bytearray, label: bytes, n: int) -> bytes:
    """merlin challenge_bytes in one crossing (meta_ad + len + PRF)."""
    buf = (ctypes.c_char * 203).from_buffer(blob)
    out = ctypes.create_string_buffer(n)
    lib.r255_merlin_challenge(buf, label, len(label), out, n)
    return bytes(out.raw)


def schnorrkel_challenge(
    prefix_blob: bytes, message: bytes, pub: bytes, r_enc: bytes
) -> bytes:
    """64 challenge bytes from the cached SigningContext prefix in ONE
    crossing (clone + 4 appends + PRF; schnorrkel sign.rs labels).
    ``prefix_blob`` is the 203-byte transcript blob after
    ``Transcript(b"SigCtx")`` + ``append_message(b"", context)``."""
    out = ctypes.create_string_buffer(64)
    lib.r255_schnorrkel_challenge(
        bytes(prefix_blob), message, len(message), pub, r_enc, out
    )
    return bytes(out.raw)
