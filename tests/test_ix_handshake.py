"""IX handshake: static-key authentication inside the handshake.

round-3 review #4 / reference shape ``mc-attest-ake`` (grapevine.proto:17-36,
README.md:177-183): both sides' statics are authenticated by the DH mix
(ee ‖ es ‖ se) — an active MITM that substitutes either key derives
different channel keys, so the first frame fails AEAD; a pinned server
static is rejected before any frame flows.
"""

import pytest

from grapevine_tpu.session import channel
# whichever backend channel.py loaded (the wheel, or the stdlib port in
# wheel-less containers) — the handshake properties must hold on both
from grapevine_tpu.session.channel import X25519PrivateKey


def _full_handshake(client_static=None, attestation=None, pin=None,
                    identity=None):
    state, msg1 = channel.client_handshake(client_static)
    reply, server_chan = channel.server_handshake(
        msg1, attestation, identity=identity
    )
    client_chan = channel.client_finish(
        state, reply, attestation, expected_server_static=pin
    )
    return client_chan, server_chan


def test_ix_roundtrip_and_peer_statics():
    ident = channel.ServerIdentity.from_seed(b"\x05" * 32)
    cs = X25519PrivateKey.generate()
    state, msg1 = channel.client_handshake(cs)
    assert len(msg1) == 64
    reply, server_chan = channel.server_handshake(msg1, identity=ident)
    client_chan = channel.client_finish(
        state, reply, expected_server_static=ident.public
    )
    assert client_chan.peer_static == ident.public
    assert server_chan.peer_static == cs.public_key().public_bytes_raw()
    ct = client_chan.encrypt(b"ping")
    assert server_chan.decrypt(ct) == b"ping"
    assert client_chan.decrypt(server_chan.encrypt(b"pong")) == b"pong"


def test_anonymous_client_works_and_is_flagged():
    client_chan, server_chan = _full_handshake()
    assert server_chan.peer_static is None
    assert server_chan.decrypt(client_chan.encrypt(b"x")) == b"x"


def test_pinned_server_static_rejects_impostor():
    """Active MITM: the relay terminates the handshake with its OWN
    identity (it cannot forge the real one inside the AEAD). A client
    that pinned the real server static must refuse."""
    real = channel.ServerIdentity.from_seed(b"\x06" * 32)
    mitm = channel.ServerIdentity.generate()
    state, msg1 = channel.client_handshake()
    reply_from_mitm, _ = channel.server_handshake(msg1, identity=mitm)
    with pytest.raises(ValueError, match="pinned"):
        channel.client_finish(
            state, reply_from_mitm, expected_server_static=real.public
        )


def test_tampered_static_in_reply_fails_aead():
    """Flipping any byte of the encrypted (s_r ‖ evidence) blob — the
    attack surface for key substitution — fails the transcript-bound
    AEAD before any key is accepted."""
    state, msg1 = channel.client_handshake()
    reply, _ = channel.server_handshake(msg1)
    for pos in (32, 40, len(reply) - 1):  # inside e_r-adjacent ct
        bad = bytearray(reply)
        bad[pos] ^= 1
        with pytest.raises(ValueError, match="authentication"):
            channel.client_finish(state, bytes(bad))


def test_substituted_ephemeral_fails():
    """A MITM that swaps e_r (leaving the ciphertext) changes ee, so
    the handshake AEAD key is wrong — decryption fails."""
    state, msg1 = channel.client_handshake()
    reply, _ = channel.server_handshake(msg1)
    fake_e = X25519PrivateKey.generate().public_key().public_bytes_raw()
    with pytest.raises(ValueError, match="authentication"):
        channel.client_finish(state, fake_e + reply[32:])


def test_forged_client_static_cannot_talk():
    """A client claiming someone else's static without the private key
    completes the wire exchange but derives wrong keys (missing se):
    its first frame fails on the server — IX initiator authentication."""
    victim = X25519PrivateKey.generate()
    victim_pub = victim.public_key().public_bytes_raw()
    eph = X25519PrivateKey.generate()
    msg1 = eph.public_key().public_bytes_raw() + victim_pub  # forged claim
    reply, server_chan = channel.server_handshake(msg1)
    # forger CAN complete the wire exchange (that needs only ee) ...
    state = channel.ClientHandshake(eph, None, msg1)
    forged_chan = channel.client_finish(state, reply)
    # ... but cannot derive the channel keys: se is missing from its
    # mix, so the server rejects its very first frame
    with pytest.raises(Exception):
        server_chan.decrypt(forged_chan.encrypt(b"hello"))


def test_attestation_binding_receives_transcript():
    """Evidence is bound to the handshake transcript: the verify hook
    sees a stable binding that covers both messages + the static."""
    seen = {}

    class Recorder(channel.NullAttestation):
        def evidence(self, binding: bytes = b"") -> bytes:
            seen["evidence_binding"] = binding
            return b"EVIDENCE"

        def verify(self, evidence: bytes, binding: bytes = b"") -> bool:
            seen["verify_evidence"] = evidence
            seen["verify_binding"] = binding
            return True

    att = Recorder()
    client_chan, server_chan = _full_handshake(attestation=att)
    assert seen["verify_evidence"] == b"EVIDENCE"
    assert len(seen["verify_binding"]) == 32
    # a REAL provider signs the binding it is handed at evidence() time;
    # the verifier must therefore be handed the *identical* value
    assert seen["verify_binding"] == seen["evidence_binding"]
    assert server_chan.decrypt(client_chan.encrypt(b"ok")) == b"ok"


def test_rejecting_attestation_aborts():
    class Reject(channel.NullAttestation):
        def verify(self, evidence: bytes, binding: bytes = b"") -> bool:
            return False

    state, msg1 = channel.client_handshake()
    reply, _ = channel.server_handshake(msg1)
    with pytest.raises(ValueError, match="attestation"):
        channel.client_finish(state, reply, attestation=Reject())


def test_server_identity_from_seed_is_stable():
    a = channel.ServerIdentity.from_seed(b"\x09" * 32)
    b = channel.ServerIdentity.from_seed(b"\x09" * 32)
    c = channel.ServerIdentity.from_seed(b"\x0a" * 32)
    assert a.public == b.public != c.public
    with pytest.raises(ValueError):
        channel.ServerIdentity.from_seed(b"short")


def test_legacy_32_byte_msg1_rejected():
    with pytest.raises(ValueError, match="64|e_c"):
        channel.server_handshake(b"\x01" * 32)


def test_server_e2e_pinning(tmp_path):
    """Full gRPC stack: client pins server.identity.public; a client
    pinning a WRONG static refuses the session."""
    from grapevine_tpu.config import GrapevineConfig
    from grapevine_tpu.server.client import GrapevineClient
    from grapevine_tpu.server.service import GrapevineServer
    from grapevine_tpu.wire import constants as C

    ident = channel.ServerIdentity.from_seed(b"\x0c" * 32)
    cfg = GrapevineConfig(
        max_messages=64, max_recipients=8, mailbox_cap=4, batch_size=4,
        bucket_cipher_rounds=0,
    )
    server = GrapevineServer(config=cfg, identity=ident)
    port = server.start("insecure-grapevine://127.0.0.1:0")
    try:
        good = GrapevineClient(
            f"insecure-grapevine://127.0.0.1:{port}",
            identity_seed=b"\x21" * 32,
            server_static=ident.public,
        )
        good.auth()
        r = good.create(recipient=good.public_key,
                        payload=b"\x01" * C.PAYLOAD_SIZE)
        assert r.status_code == C.STATUS_CODE_SUCCESS

        wrong_pin = channel.ServerIdentity.generate().public
        bad = GrapevineClient(
            f"insecure-grapevine://127.0.0.1:{port}",
            identity_seed=b"\x22" * 32,
            server_static=wrong_pin,
        )
        with pytest.raises(ValueError, match="pinned"):
            bad.auth()
    finally:
        server.stop()
