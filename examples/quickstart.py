"""grapevine-tpu quickstart: server + two clients, end to end.

Runs entirely in-process, on whatever platform JAX gives it (a TPU if
one is attached; ``JAX_PLATFORMS=cpu`` for a machine without one — the
same code either way). Demonstrates the full reference
workflow (reference README.md:126-175): attested-style Auth handshake,
challenge-signed queries, CRUD on fixed-size records, zero-id "next
message" semantics, and the expiry sweep.

    python examples/quickstart.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from grapevine_tpu.config import GrapevineConfig
from grapevine_tpu.server.client import GrapevineClient
from grapevine_tpu.server.service import GrapevineServer
from grapevine_tpu.session.channel import ServerIdentity
from grapevine_tpu.wire import constants as C


def main():
    # -- server ---------------------------------------------------------
    cfg = GrapevineConfig(
        max_messages=1 << 10,     # bus capacity (power of two)
        max_recipients=256,
        batch_size=8,             # ops per oblivious round
        expiry_period=3600,       # seconds until messages expire
    )
    # a STABLE static key (IX handshake): clients pin it to reject MITM.
    # DEMO-ONLY SEED — anyone can derive this key. Production: derive
    # from a SECRET 32-byte seed (or ServerIdentity.generate()) and
    # distribute identity.public to clients out of band.
    identity = ServerIdentity.from_seed(b"demo-server-identity-seed-32byte")
    server = GrapevineServer(config=cfg, identity=identity)
    port = server.start("insecure-grapevine://127.0.0.1:0")
    print(f"server listening on insecure-grapevine://127.0.0.1:{port}")
    print(f"server static key (pin me): {identity.public.hex()[:16]}…")

    # -- clients: Alice and Bob -----------------------------------------
    # identity = a ristretto255 keypair derived from a 32-byte seed;
    # server_static pins the IX-authenticated server key (an active
    # MITM that substitutes its own identity is rejected at auth())
    alice = GrapevineClient(
        f"insecure-grapevine://127.0.0.1:{port}", identity_seed=b"A" * 32,
        server_static=identity.public,
    )
    bob = GrapevineClient(
        f"insecure-grapevine://127.0.0.1:{port}", identity_seed=b"B" * 32,
        server_static=identity.public,
    )
    alice.auth()  # IX handshake; pins the static, seeds the lockstep RNG
    bob.auth()
    print("clients authenticated (server pinned; challenge RNG in lockstep)")

    # -- create: Alice -> Bob -------------------------------------------
    payload = b"hello, oblivious world".ljust(C.PAYLOAD_SIZE, b"\x00")
    r = alice.create(recipient=bob.public_key, payload=payload)
    assert r.status_code == C.STATUS_CODE_SUCCESS
    msg_id = r.record.msg_id
    print(f"alice sent a message; server-assigned id {msg_id.hex()[:16]}…")

    # -- read: Bob pops his next message (zero id) ----------------------
    r = bob.read()  # id omitted = "give me my next message"
    assert r.status_code == C.STATUS_CODE_SUCCESS
    print(f"bob read: {r.record.payload.rstrip(chr(0).encode())!r}")

    # -- update: full-record replace by id ------------------------------
    r = alice.update(
        msg_id=msg_id,
        recipient=bob.public_key,
        payload=b"updated".ljust(C.PAYLOAD_SIZE, b"\x00"),
    )
    assert r.status_code == C.STATUS_CODE_SUCCESS

    # -- delete: Bob pops (deletes) it ----------------------------------
    r = bob.delete()  # zero id = pop next; indistinguishable from a read
    assert r.status_code == C.STATUS_CODE_SUCCESS
    r = bob.read()
    assert r.status_code == C.STATUS_CODE_NOT_FOUND  # inbox empty
    print("bob's inbox drained; absence and denial look identical")

    # -- expiry ---------------------------------------------------------
    alice.create(recipient=bob.public_key, payload=payload)
    evicted = server.engine.expire(int(time.time()) + 7200)
    print(f"expiry sweep evicted {evicted} record(s)")

    # -- aggregate health (never keyed by client identity) --------------
    h = server.health()
    print(
        f"health: rounds={h['rounds']} real_ops={h['real_ops']} "
        f"occupancy={h['batch_occupancy']:.2f} p99={h.get('round_ms_p99')}ms"
    )
    server.stop()
    print("done")


def main_tier():
    """The same workflow over the split serving tier (`--tier`):
    one engine process-equivalent + two frontends, Alice and Bob on
    DIFFERENT frontends, one shared oblivious bus (server/tier.py)."""
    from grapevine_tpu.server.tier import EngineServer, FrontendServer

    cfg = GrapevineConfig(max_messages=1 << 10, max_recipients=256, batch_size=8)
    engine = EngineServer(cfg)
    eport = engine.start("127.0.0.1:0")
    fe1 = FrontendServer(f"127.0.0.1:{eport}", config=cfg)
    fe2 = FrontendServer(f"127.0.0.1:{eport}", config=cfg)
    p1 = fe1.start("insecure-grapevine://127.0.0.1:0")
    p2 = fe2.start("insecure-grapevine://127.0.0.1:0")
    print(f"engine tier on :{eport}; frontends on :{p1} and :{p2}")

    alice = GrapevineClient(
        f"insecure-grapevine://127.0.0.1:{p1}", identity_seed=b"A" * 32,
        server_static=fe1.identity.public,
    )
    bob = GrapevineClient(
        f"insecure-grapevine://127.0.0.1:{p2}", identity_seed=b"B" * 32,
        server_static=fe2.identity.public,
    )
    alice.auth()
    bob.auth()
    payload = b"hello across the tier".ljust(C.PAYLOAD_SIZE, b"\x00")
    r = alice.create(recipient=bob.public_key, payload=payload)
    assert r.status_code == C.STATUS_CODE_SUCCESS
    r = bob.read()
    assert r.status_code == C.STATUS_CODE_SUCCESS
    print(f"bob (frontend 2) read alice's (frontend 1) message: "
          f"{r.record.payload.rstrip(chr(0).encode())!r}")
    r = bob.delete()
    assert r.status_code == C.STATUS_CODE_SUCCESS
    fe1.stop()
    fe2.stop()
    engine.stop()
    print("tier demo done")


if __name__ == "__main__":
    if "--tier" in sys.argv:
        main_tier()
    else:
        main()
