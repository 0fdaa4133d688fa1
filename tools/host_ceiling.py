"""Host serving-path ceiling: loopback gRPC with the engine stubbed out.

Measures the throughput of everything the host does per op — client-side
sign + AEAD seal, gRPC loopback, server envelope decode, session lookup,
AEAD open, challenge lockstep, request unpack/validate, batched sr25519
verification, scheduling, response seal — with the device round replaced
by an instant canned response. This is the frontend's ceiling: a device
engine faster than this number is wasted (round-4 review weak #3).

Run:  python tools/host_ceiling.py [--clients 32] [--ops 40] [--batch 64]
                                   [--legacy]
``--legacy`` disables the native STROBE ops and the OpenSSL ChaCha
backend to reproduce the pre-lever host path for before/after deltas.

Client and server share one interpreter (and the GIL), so the number is
a lower bound on a real deployment where clients are remote; the per-
component attribution lives in PERF.md's host table.
"""

from __future__ import annotations

import argparse
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")


class _CannedPending:
    """Stands in for engine.PendingRound: resolves instantly."""

    def __init__(self, resps):
        self._resps = resps

    def resolve(self):
        return self._resps


def _stub_engine(engine):
    """Replace the device round with a canned constant-time response.
    Returns a mutable [rounds, ops] counter the stub updates."""
    from grapevine_tpu.wire import constants as C
    from grapevine_tpu.wire.records import QueryResponse, Record

    counter = [0, 0]

    def handle_queries_async(reqs, now):
        counter[0] += 1
        counter[1] += len(reqs)
        resp = QueryResponse(
            status_code=C.STATUS_CODE_SUCCESS,
            record=Record(
                msg_id=b"\x01" * 16,
                sender=b"\x02" * 32,
                recipient=b"\x03" * 32,
                timestamp=int(now),
                payload=b"\x00" * C.PAYLOAD_SIZE,
            ),
        )
        return _CannedPending([resp] * len(reqs))

    engine.handle_queries_async = handle_queries_async
    return counter


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=32)
    ap.add_argument("--ops", type=int, default=40, help="ops per client")
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--legacy", action="store_true",
                    help="pre-lever host path (pure-Python STROBE + ChaCha)")
    args = ap.parse_args()

    if args.legacy:
        # Disable exactly the round-5 host levers (native STROBE ops,
        # one-crossing challenge, OpenSSL ChaCha) while KEEPING the
        # native MSM and the native Keccak permutation (both shipped in
        # r4) — so the delta isolates this round's levers, not all of C.
        from grapevine_tpu.session import chacha, merlin, schnorrkel

        chacha._Cipher = None
        merlin._native_strobe = lambda: None
        schnorrkel._challenge_scalar = schnorrkel._challenge_scalar_pure

    import jax

    jax.config.update("jax_platforms", "cpu")

    from grapevine_tpu.config import GrapevineConfig
    from grapevine_tpu.server.client import GrapevineClient
    from grapevine_tpu.server.service import GrapevineServer
    from grapevine_tpu.wire import constants as C

    cfg = GrapevineConfig(
        max_messages=1 << 10, max_recipients=1 << 8, batch_size=args.batch,
        bucket_cipher_rounds=0,
    )
    server = GrapevineServer(config=cfg)
    counter = _stub_engine(server.engine)
    port = server.start("insecure-grapevine://127.0.0.1:0")
    try:
        clients = [
            GrapevineClient(f"insecure-grapevine://127.0.0.1:{port}",
                            identity_seed=(i + 1).to_bytes(4, "little") * 8)
            for i in range(args.clients)
        ]
        for c in clients:
            c.auth()

        lat: list[float] = []
        errs: list[Exception] = []
        lock = threading.Lock()
        start = threading.Barrier(args.clients + 1)

        def run(c):
            mine = []
            try:
                start.wait()
                for i in range(args.ops):
                    t0 = time.perf_counter()
                    r = c.create(recipient=b"\x03" * 32,
                                 payload=bytes([i & 0xFF]) * C.PAYLOAD_SIZE)
                    assert r.status_code == C.STATUS_CODE_SUCCESS
                    mine.append(time.perf_counter() - t0)
            except Exception as e:  # pragma: no cover
                errs.append(e)
            with lock:
                lat.extend(mine)

        threads = [threading.Thread(target=run, args=(c,)) for c in clients]
        for t in threads:
            t.start()
        start.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        if errs:
            raise errs[0]
        n = args.clients * args.ops
        rounds = counter[0]
        lat.sort()
        print({
            "mode": "legacy" if args.legacy else "current",
            "ops": n,
            "ops_per_sec": round(n / wall, 1),
            "p50_ms": round(lat[len(lat) // 2] * 1e3, 2),
            "p99_ms": round(lat[int(len(lat) * 0.99) - 1] * 1e3, 2),
            "rounds": rounds,
            "avg_round_fill": round(n / rounds, 1) if rounds else None,
            "batch": args.batch,
            "clients": args.clients,
        })
    finally:
        server.stop()


if __name__ == "__main__":
    main()
