"""sr25519 (schnorrkel) signature scheme tests.

Byte-compatibility target: reference clients sign challenges with
``sign_schnorrkel`` under context ``b"grapevine-challenge"`` (reference
README.md:193-199, types/src/lib.rs:13, Cargo.toml:62). The transcript
layer is vector-pinned in test_merlin.py; these tests pin the schnorrkel
construction on top (labels, marker bit, canonical-scalar rules) and the
scheme's integration into the verify/batch-verify seams.
"""

import os

import pytest

from grapevine_tpu.session import get_signature_scheme, ristretto, schnorrkel


def _mk(i: int):
    sk, pub = schnorrkel.keygen(bytes([i]) * 32)
    return sk, pub


def test_sign_verify_roundtrip():
    sk, pub = _mk(1)
    ctx, msg = b"grapevine-challenge", os.urandom(32)
    sig = schnorrkel.sign(sk, ctx, msg)
    assert len(sig) == 64
    assert schnorrkel.verify(pub, ctx, msg, sig)
    assert not schnorrkel.verify(pub, ctx, os.urandom(32), sig)
    assert not schnorrkel.verify(pub, b"other-context", msg, sig)
    other_pub = _mk(2)[1]
    assert not schnorrkel.verify(other_pub, ctx, msg, sig)


def test_signature_is_deterministic():
    sk, _ = _mk(3)
    msg = b"m" * 32
    assert schnorrkel.sign(sk, b"c", msg) == schnorrkel.sign(sk, b"c", msg)


def test_marker_bit_required_and_set():
    """schnorrkel Signature::{to,from}_bytes: bit 7 of byte 63 marks a
    schnorrkel signature; unmarked (ed25519-style) bytes are rejected."""
    sk, pub = _mk(4)
    msg = os.urandom(32)
    sig = schnorrkel.sign(sk, b"ctx", msg)
    assert sig[63] & 0x80
    unmarked = bytearray(sig)
    unmarked[63] &= 0x7F
    assert not schnorrkel.verify(pub, b"ctx", msg, bytes(unmarked))


def test_non_canonical_scalar_rejected():
    sk, pub = _mk(5)
    msg = os.urandom(32)
    sig = bytearray(schnorrkel.sign(sk, b"ctx", msg))
    # force s >= L while keeping the marker bit: set bits 252..254
    sig[63] |= 0x70
    assert not schnorrkel.verify(pub, b"ctx", msg, bytes(sig))


def test_malformed_inputs_never_raise():
    _, pub = _mk(6)
    for bad in (b"", b"x" * 63, b"x" * 64, b"x" * 65):
        assert schnorrkel.verify(pub, b"c", b"m", bad) is False
    sig = schnorrkel.sign(_mk(6)[0], b"c", b"m")
    assert schnorrkel.verify(b"short", b"c", b"m", sig) is False
    # non-canonical R encoding
    bad_r = bytearray(sig)
    bad_r[:32] = b"\xff" * 32
    assert schnorrkel.verify(pub, b"c", b"m", bytes(bad_r)) is False


def test_cross_scheme_rejection():
    """RFC-9496 signatures and sr25519 signatures must not cross-verify
    (different Fiat–Shamir derivations; rfc9496 sigs are unmarked)."""
    seed = bytes([7]) * 32
    sk_s, pub_s = schnorrkel.keygen(seed)
    sk_r, pub_r = ristretto.keygen(seed)
    assert pub_s == pub_r  # same key derivation, same group
    msg = os.urandom(32)
    assert not schnorrkel.verify(pub_s, b"c", msg, ristretto.sign(sk_r, b"c", msg))
    assert not ristretto.verify(pub_r, b"c", msg, schnorrkel.sign(sk_s, b"c", msg))


def test_batch_verify_all_valid_and_offender():
    ctx = b"grapevine-challenge"
    items = []
    for i in range(1, 33):
        sk, pub = _mk(i)
        msg = os.urandom(32)
        items.append((pub, ctx, msg, schnorrkel.sign(sk, ctx, msg)))
    assert schnorrkel.batch_verify(items)
    items[13] = (items[13][0], ctx, os.urandom(32), items[13][3])
    assert not schnorrkel.batch_verify(items)
    assert schnorrkel.batch_verify([])


def test_batch_matches_individual_under_pure_python():
    """Native and pure-Python paths agree (the native lib is the fast
    path; pure Python is the oracle)."""
    ctx = b"grapevine-challenge"
    items = []
    for i in range(40, 44):
        sk, pub = _mk(i)
        msg = os.urandom(32)
        items.append((pub, ctx, msg, schnorrkel.sign(sk, ctx, msg)))
    native = ristretto._native.lib
    try:
        assert schnorrkel.batch_verify(items)
        assert all(schnorrkel.verify(*it) for it in items)
        ristretto._native.lib = None
        assert schnorrkel.batch_verify(items)
        assert all(schnorrkel.verify(*it) for it in items)
    finally:
        ristretto._native.lib = native


def test_challenge_transcript_labels_golden():
    """Pin the exact challenge derivation as a golden value: any change
    to the transcript labels or framing (the compat surface vs
    schnorrkel sign.rs) shows up as a diff here."""
    k = schnorrkel._challenge_scalar(
        b"grapevine-challenge", b"\x01" * 32, b"\x02" * 32, b"\x03" * 32
    )
    assert k == 0xB4430E99729B59EBA580AB30C1D0968E4EF06EC3E803E837F1A4BDBEF47ECA


def test_golden_challenge_through_the_one_crossing_path():
    """The same golden, derived inside the native chunk call: with all
    random bytes 0 the randomiser z is 1, so the call's z*k is k."""
    from grapevine_tpu import native

    if native.lib is None:
        pytest.skip("native library unavailable")
    marked_zero_s = b"\x03" * 32 + b"\x00" * 31 + b"\x80"
    scal, sb = native.chunk_scalars(
        [b"\x02" * 32], [marked_zero_s], b"\x00" * 16,
        prefix=schnorrkel._context_prefix_blob(b"grapevine-challenge"),
        msgs=[b"\x01" * 32],
    )
    assert int.from_bytes(scal[:32], "little") == 1
    assert int.from_bytes(scal[32:], "little") == \
        0xB4430E99729B59EBA580AB30C1D0968E4EF06EC3E803E837F1A4BDBEF47ECA
    assert sb == b"\x00" * 32


@pytest.mark.parametrize("bad_context", [None, b"ctx-a", b"ctx-b"])
def test_batch_verify_across_contexts(bad_context):
    """A batch may mix signing contexts (the native call takes one
    STROBE prefix, so the scheme checks a context at a time)."""
    items = []
    for i in range(1, 21):
        sk, pub = _mk(i)
        ctx = (b"ctx-a", b"ctx-b")[i % 2]
        msg = os.urandom(32)
        items.append((pub, ctx, msg, schnorrkel.sign(sk, ctx, msg)))
    if bad_context is not None:
        at = next(i for i, it in enumerate(items) if it[1] == bad_context)
        pub, ctx, msg, sig = items[at]
        items[at] = (pub, ctx, os.urandom(32), sig)
    assert schnorrkel.batch_verify(items) is (bad_context is None)


def test_scheme_registry():
    assert get_signature_scheme("schnorrkel") is schnorrkel
    assert get_signature_scheme("rfc9496") is ristretto
    with pytest.raises(ValueError):
        get_signature_scheme("ed25519")


# Substrate's well-known sr25519 dev keypairs (`subkey inspect //Alice`
# etc.) — externally published (seed, public) byte pairs this codebase
# did not generate. sp-core expands the mini secret with schnorrkel's
# ExpandMode::Ed25519, so reproducing public from seed transits SHA-512
# expansion, ed25519 clamping, divide-by-cofactor, ristretto255
# scalar*basepoint and compressed encoding against a foreign stack.
_SUBSTRATE_DEV_VECTORS = [
    (  # //Alice (SS58 5GrwvaEF5zXb26Fz9rcQpDWS57CtERHpNehXCPcNoHGKutQY)
        "e5be9a5092b81bca64be81d212e7f2f9eba183bb7a90954f7b76361f6edb5c0a",
        "d43593c715fdd31c61141abd04a99fd6822c8558854ccde39a5684e7a56da27d",
    ),
    (  # //Bob (SS58 5FHneW46xGXgs5mUiveU4sbTyGBzmstUspZC92UhjJM694ty)
        "398f0c28f98885e046333d4a41c19cee4c37368a9832c6502f6cfd182e2aef89",
        "8eaf04151687736326c9fea17e25fc5287613693c912909cb226aa4794f26a48",
    ),
]


@pytest.mark.parametrize("seed_hex,pub_hex", _SUBSTRATE_DEV_VECTORS)
def test_expand_mini_secret_substrate_vectors(seed_hex, pub_hex):
    sk, nonce = schnorrkel.expand_mini_secret(bytes.fromhex(seed_hex))
    assert len(nonce) == 32
    assert schnorrkel.public_key(sk).hex() == pub_hex


@pytest.mark.parametrize("seed_hex,pub_hex", _SUBSTRATE_DEV_VECTORS)
def test_expand_mini_secret_substrate_vectors_pure_python(seed_hex, pub_hex):
    """Same vectors with the native r255.c path disabled: pins the pure
    Python group arithmetic independently."""
    native = ristretto._native.lib
    ristretto.public_key.cache_clear()
    try:
        ristretto._native.lib = None
        sk, _ = schnorrkel.expand_mini_secret(bytes.fromhex(seed_hex))
        assert schnorrkel.public_key(sk).hex() == pub_hex
    finally:
        ristretto._native.lib = native
        ristretto.public_key.cache_clear()


def test_expanded_dev_key_signs_and_verifies():
    """The expanded //Alice secret is a working signing key here."""
    sk, _ = schnorrkel.expand_mini_secret(
        bytes.fromhex(_SUBSTRATE_DEV_VECTORS[0][0]))
    ctx, msg = b"grapevine-challenge", b"\x07" * 32
    sig = schnorrkel.sign(sk, ctx, msg)
    assert schnorrkel.verify(schnorrkel.public_key(sk), ctx, msg, sig)


def test_expand_mini_secret_rejects_bad_length():
    with pytest.raises(ValueError):
        schnorrkel.expand_mini_secret(b"\x00" * 31)
