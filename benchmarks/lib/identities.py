"""Seeded sr25519 identities and challenge signatures made before the
window, in a pool of JAX-free worker processes.

The program is used only for what a client uses: its signature scheme
(``grapevine_tpu.session``), which neither imports JAX nor touches the
device, so the workers are safe beside a parent that holds the chip.
"""

from __future__ import annotations

import hashlib
import multiprocessing
import os
import random

from . import wire as W


def identity_seed(seed: int, i: int) -> bytes:
    return hashlib.sha256(f"grapevine-bench-{seed}-{i}".encode()).digest()


def _scheme():
    from grapevine_tpu.session import get_signature_scheme

    return get_signature_scheme("schnorrkel")


def _keygen_slice(args) -> list:
    seed, lo, hi = args
    scheme = _scheme()
    return [scheme.keygen(identity_seed(seed, i)) for i in range(lo, hi)]


def _sign_slice(args) -> bytes:
    """Signatures over seeded challenges for one slice of the script;
    one ``bytes`` back (challenge 32 | signature 64 per op)."""
    seed, lo, sks, askers = args
    scheme = _scheme()
    rng = random.Random(f"{seed}-challenges-{lo}")
    out = bytearray()
    for a in askers:
        challenge = rng.randbytes(W.CHALLENGE_SIZE)
        out += challenge
        out += scheme.sign(sks[a], W.SIGNING_CONTEXT, challenge)
    return bytes(out)


def worker_count() -> int:
    return max(1, min(12, (os.cpu_count() or 2) - 1))


class SigningPool:
    """A spawn-context pool; ``close()`` ends and joins the workers."""

    def __init__(self):
        self.n = worker_count()
        self._pool = multiprocessing.get_context("spawn").Pool(self.n)

    def identities(self, seed: int, n: int) -> list[tuple[bytes, bytes]]:
        """``n`` identities as (secret key, public key)."""
        step = -(-n // (self.n * 2))
        jobs = [(seed, lo, min(n, lo + step)) for lo in range(0, n, step)]
        return [kp for part in self._pool.map(_keygen_slice, jobs)
                for kp in part]

    def sign_script(self, seed: int, idents, askers):
        """Starts the workers on one signature per entry of ``askers``
        (identity indices) and returns at once, so that the caller's own
        set-up goes on beside them. The function returned waits for the
        last and gives one scheduler AuthItem ``(pub, context, challenge,
        signature)`` per entry, in order."""
        n = len(askers)
        step = -(-n // (self.n * 4)) if n else 1
        sks = [sk for sk, _ in idents]
        pending = self._pool.map_async(
            _sign_slice, [(seed, lo, sks, askers[lo:lo + step])
                          for lo in range(0, n, step)])
        w = W.CHALLENGE_SIZE + W.SIGNATURE_SIZE

        def collect() -> list[tuple]:
            blob = b"".join(pending.get())
            return [(idents[a][1], W.SIGNING_CONTEXT,
                     blob[j * w:j * w + W.CHALLENGE_SIZE],
                     blob[j * w + W.CHALLENGE_SIZE:(j + 1) * w])
                    for j, a in enumerate(askers)]

        return collect

    def close(self) -> None:
        self._pool.close()
        self._pool.join()
