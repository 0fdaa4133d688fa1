"""Native ristretto255 library ≡ the pure-Python implementation.

The C library (grapevine_tpu/native/r255.c) is verification-speed
infrastructure; the pure-Python RFC 9496 implementation (vector-tested in
test_session.py) is its correctness oracle. Skipped entirely when no C
compiler is available (the package degrades to pure Python)."""

import os
import random

import pytest

from grapevine_tpu import native
from grapevine_tpu.session import ristretto as R

pytestmark = pytest.mark.skipif(
    native.lib is None, reason="no C compiler; pure-Python fallback in use"
)

rng = random.Random(1234)


def test_point_encode_decode_roundtrip_matches_python():
    for _ in range(64):
        k = rng.randrange(1, R.L)
        enc = (k * R.BASEPOINT).encode()
        assert native.reencode(enc) == enc


def test_decode_validity_agrees_with_python():
    cases = [
        b"\x00" * 32,  # identity: valid
        b"\x01" + b"\x00" * 31,
        b"\xff" * 32,
        (R.P - 1).to_bytes(32, "little"),
        (R.P).to_bytes(32, "little"),
    ] + [os.urandom(32) for _ in range(64)]
    for enc in cases:
        py_ok = True
        try:
            R.RistrettoPoint.decode(enc)
        except ValueError:
            py_ok = False
        assert (native.reencode(enc) is not None) == py_ok, enc.hex()


def test_verify_and_batch_agree_with_python_paths():
    items = []
    for i in range(12):
        sk, pub = R.keygen(bytes([i + 1]) * 32)
        msg = bytes([i]) * 32
        sig = R.sign(sk, b"ctx", msg)
        items.append((pub, b"ctx", msg, sig))
    # public API (native-dispatching) accepts all
    for it in items:
        assert R.verify(*it)
    assert R.batch_verify(items)
    # pure-python check of the same signatures (oracle agreement)
    for pub, ctx, msg, sig in items:
        s = int.from_bytes(sig[32:], "little")
        k = R._h_scalar(R._CHAL_DOMAIN, ctx, sig[:32], pub, msg)
        big_r = R.RistrettoPoint.decode(sig[:32])
        a_pt = R.RistrettoPoint.decode(pub)
        assert R._fixed_base_mult(s) == (big_r + k * a_pt)
    # tampering caught by both
    pub, ctx, msg, sig = items[3]
    bad = sig[:32] + bytes([sig[32] ^ 1]) + sig[33:]
    assert not R.verify(pub, ctx, msg, bad)
    bad_batch = list(items)
    bad_batch[3] = (pub, ctx, msg, bad)
    assert not R.batch_verify(bad_batch)


def test_malformed_inputs_return_invalid_not_crash():
    assert not R.verify(b"\x00" * 32, b"c", b"m", b"\xff" * 64)
    assert not R.verify(b"\xff" * 32, b"c", b"m", b"\x00" * 64)
    assert not R.batch_verify([(b"\xff" * 32, b"c", b"m" * 8, b"\x00" * 64)])
    # scalar ≥ L rejected
    sk, pub = R.keygen(b"q" * 32)
    sig = R.sign(sk, b"c", b"m" * 8)
    big_s = sig[:32] + (R.L).to_bytes(32, "little")
    assert not R.verify(pub, b"c", b"m" * 8, big_s)


def test_mult_base_matches_python():
    """Native fixed-base mult ≡ pure-Python scalar·B (the signing path)."""
    for _ in range(32):
        k = rng.randrange(1, R.L)
        assert native.mult_base(k.to_bytes(32, "little")) == (k * R.BASEPOINT).encode()
    # edge scalars: 1, L-1, and a value that reduces mod L
    for k in (1, R.L - 1):
        assert native.mult_base(k.to_bytes(32, "little")) == (k * R.BASEPOINT).encode()


def test_sign_uses_native_and_stays_verifiable():
    """sign() with the native fast path produces signatures the (native
    and python) verifiers accept, and is deterministic."""
    sk, pub = R.keygen(b"\x09" * 32)
    sig1 = R.sign(sk, b"grapevine-challenge", b"m" * 32)
    sig2 = R.sign(sk, b"grapevine-challenge", b"m" * 32)
    assert sig1 == sig2
    assert R.verify(pub, b"grapevine-challenge", b"m" * 32, sig1)


def test_batch_verify_pippenger_paths():
    """Batches large enough to cross the Straus→Pippenger dispatch
    (>64 points → c=6; >=1024 points → c=8). A wrong bucket MSM makes
    the random-linear-combination equation fail with overwhelming
    probability, so valid-batch acceptance + corrupted-batch rejection
    pin the new path against the algebra."""
    import grapevine_tpu.native as native

    if native.lib is None:
        pytest.skip("native library unavailable")
    ctx = b"test-pippenger"
    for n_sigs in (100, 520):  # 200 points (c=6) and 1040 points (c=8)
        items = []
        for i in range(n_sigs):
            sk, pub = R.keygen(i.to_bytes(4, "little") * 8)
            msg = i.to_bytes(8, "little")
            items.append((pub, ctx, msg, R.sign(sk, ctx, msg)))
        assert R.batch_verify(items), f"valid batch of {n_sigs} rejected"
        bad = list(items)
        sig = bytearray(bad[n_sigs // 2][3])
        sig[1] ^= 0x40
        bad[n_sigs // 2] = (bad[n_sigs // 2][0], ctx, bad[n_sigs // 2][2], bytes(sig))
        assert not R.batch_verify(bad), f"corrupted batch of {n_sigs} accepted"


def test_pub_decode_cache_transparent():
    """The C decoded-public-key cache must be semantically invisible:
    same pub verifying twice (hit path), a bad signature under a cached
    pub still rejected, and an invalid encoding rejected repeatedly
    (never cached)."""
    import grapevine_tpu.native as native

    if native.lib is None:
        pytest.skip("native library unavailable")
    sk, pub = R.keygen(b"\x21" * 32)
    ctx, msg = b"cache-test", b"m" * 16
    sig = R.sign(sk, ctx, msg)
    assert R.verify(pub, ctx, msg, sig)      # cold: caches pub
    assert R.verify(pub, ctx, msg, sig)      # hit: same result
    bad = bytearray(sig)
    bad[3] ^= 1
    assert not R.verify(pub, ctx, msg, bytes(bad))  # hit + bad sig
    # invalid encoding: rejected every time, never enters the cache
    non_canonical = b"\xff" * 32
    for _ in range(3):
        assert not R.verify(non_canonical, ctx, msg, sig)
    assert R.verify(pub, ctx, msg, sig)      # cache still coherent


# -- the chunk check: one crossing, no shared scratch ------------------

from grapevine_tpu.session import schnorrkel as S  # noqa: E402

CTX = b"grapevine-challenge"


@pytest.fixture(scope="module")
def signed():
    """2,048 sr25519 items (pub, context, message, signature), one
    identity each."""
    items = []
    for i in range(2048):
        sk, pub = S.keygen(i.to_bytes(4, "little") * 8)
        msg = rng.randbytes(32)
        items.append((pub, CTX, msg, S.sign(sk, CTX, msg)))
    return items


def _chunk(items, seeded=None, chunks=1):
    """native.chunk_check over sr25519 items, as schnorrkel.batch_verify
    calls it."""
    pubs, _, msgs, sigs = zip(*items)
    rand = (seeded.randbytes if seeded else os.urandom)(16 * len(items))
    return native.chunk_check(
        pubs, sigs, rand, prefix=S._context_prefix_blob(CTX), msgs=msgs,
        chunks=chunks)


def _pure_batch(items):
    parsed = []
    for pub, ctx, msg, sig in items:
        r_enc, s = S._parse(sig)
        parsed.append(
            (r_enc, pub, s, S._challenge_scalar_pure(ctx, msg, pub, r_enc)))
    return R.batch_verify_core(parsed)


@pytest.mark.parametrize("n", [1, 2, 63, 64, 65, 300, 2048])
def test_chunk_check_agrees_on_valid_batches(signed, n):
    """The one-crossing check, ``verify`` item by item and the
    pure-Python equation give one answer; the sizes cross Straus ->
    Pippenger (32 / 33 signatures) and every window the rule picks."""
    items = signed[:n]
    assert _chunk(items) == 1
    assert S.batch_verify(items)
    assert S.batch_verify(items, rng=random.Random(n))  # a seeded rng still works
    assert all(S.verify(*it) for it in items)
    assert _pure_batch(items)


def _corrupt(item, kind, other_pub):
    pub, ctx, msg, sig = item
    sig = bytearray(sig)
    if kind == "signature":
        sig[40] ^= 0x04
    elif kind == "public_key":
        pub = other_pub
    elif kind == "R":
        sig[1] ^= 0x40
    elif kind == "s_geq_L":
        sig[32:] = R.L.to_bytes(32, "little")
        sig[63] |= 0x80
    return (pub, ctx, msg, bytes(sig))


@pytest.mark.parametrize("position", ["first", "middle", "last"])
@pytest.mark.parametrize("kind", ["signature", "public_key", "R", "s_geq_L"])
def test_one_bad_item_fails_its_chunk_and_only_it(signed, kind, position):
    chunks = [signed[0:64], signed[64:128], signed[128:192]]
    at = {"first": 0, "middle": 31, "last": 63}[position]
    bad = list(chunks[1])
    bad[at] = _corrupt(bad[at], kind, other_pub=signed[500][0])
    assert not S.verify(*bad[at])
    assert [_chunk(chunks[0]), _chunk(bad) == 1, _chunk(chunks[2])] == [
        1, False, 1]
    assert not S.batch_verify(bad)
    assert not _pure_batch(bad) if kind != "s_geq_L" else S._parse(
        bad[at][3]) is None
    # the round-sized call sees it too, wherever its chunk lies
    whole = signed[:64] + bad + signed[128:600]
    assert not S.batch_verify(whole)


@pytest.mark.parametrize("n,chunks", [(2048, 8), (2048, 6), (600, 2),
                                      (100, 64), (5, 9), (4100, 3)])
def test_round_check_is_the_conjunction_of_its_chunks(signed, n, chunks):
    """One call, ``chunks`` equations on as many threads: valid rounds
    pass, and one bad item anywhere fails the round — in the first
    chunk, the last, and at a chunk's edge, with messages of different
    lengths so every chunk must find its own bytes."""
    items = (signed + signed + signed)[:n]
    sk, pub = S.keygen(b"\x77" * 32)
    for at, size in ((0, 0), (n // 2, 700), (n - 1, 31)):
        msg = bytes([at % 251]) * size
        items[at] = (pub, CTX, msg, S.sign(sk, CTX, msg))
    assert _chunk(items, chunks=chunks) == 1
    assert S.batch_verify(items, chunks=chunks)
    assert S.batch_verify(items, rng=random.Random(n), chunks=chunks)
    step = -(-n // chunks)
    for at in (0, step - 1, min(step, n - 1), n // 2, n - 1):
        bad = list(items)
        bad[at] = _corrupt(bad[at], "signature", None)
        assert _chunk(bad, chunks=chunks) == 0, at
        assert not S.batch_verify(bad, chunks=chunks)
    rk = [(pub, CTX, bytes([i % 256]) * 8, R.sign(sk, CTX, bytes([i % 256]) * 8))
          for i in range(min(n, 600))]
    assert R.batch_verify(rk, chunks=chunks)
    rk[-1] = (pub, CTX, b"other", rk[-1][3])
    assert not R.batch_verify(rk, chunks=chunks)


def test_chunk_check_refuses_malformed_lengths():
    sk, pub = S.keygen(b"\x31" * 32)
    msg = b"m" * 32
    sig = S.sign(sk, CTX, msg)
    pre = S._context_prefix_blob(CTX)
    good = dict(prefix=pre, msgs=[msg])
    assert native.chunk_check([pub], [sig], os.urandom(16), **good) == 1
    assert native.chunk_check([], [], b"", prefix=pre, msgs=[]) == 1
    for pubs, sigs, rand, kw in [
        ([pub[:31]], [sig], os.urandom(16), good),
        ([pub + b"\x00"], [sig], os.urandom(16), good),
        ([pub], [sig[:63]], os.urandom(16), good),
        ([pub], [sig + b"\x00"], os.urandom(16), good),
        ([pub], [sig, sig], os.urandom(16), good),
        ([pub], [sig], os.urandom(15), good),
        ([pub], [sig], os.urandom(16), dict(prefix=pre[:100], msgs=[msg])),
        ([pub], [sig], os.urandom(16), dict(prefix=pre, msgs=[])),
        ([pub], [sig], os.urandom(16), {}),
        ([pub], [sig], os.urandom(16), dict(ks=[b"\x01" * 31])),
        ([pub], [sig], os.urandom(16), dict(ks=[])),
    ]:
        assert native.chunk_check(pubs, sigs, rand, **kw) == -1
        assert native.chunk_scalars(pubs, sigs, rand, **kw) is None
    # through the scheme: False, never an exception
    assert S.batch_verify([(pub[:31], CTX, msg, sig)]) is False
    assert S.batch_verify([(pub, CTX, msg, sig[:63])]) is False
    assert S.batch_verify([(pub, CTX, b"", sig)]) is False
    assert S.batch_verify([(pub, CTX, msg * 40, S.sign(sk, CTX, msg * 40))])


def test_chunk_scalars_match_python_integers():
    """The arithmetic mod L done in C (a 512-bit challenge reduced,
    z*k, the sum of z*s) against Python's integers, on random bytes and
    on the edges of every reduction."""
    pre = S._context_prefix_blob(CTX)
    edge = [b"\x00" * 16, b"\xff" * 16, b"\x01" + b"\x00" * 15]
    s_edge = [0, 1, R.L - 1, (1 << 252) - 1, 1 << 252]
    pubs, sigs, msgs, rand = [], [], [], b""
    for i in range(200):
        s = s_edge[i] if i < len(s_edge) else rng.randrange(R.L)
        sig = bytearray(rng.randbytes(32) + s.to_bytes(32, "little"))
        sig[63] |= 0x80
        pubs.append(rng.randbytes(32))
        sigs.append(bytes(sig))
        msgs.append(rng.randbytes(rng.randrange(0, 400)))
        rand += edge[i] if i < len(edge) else rng.randbytes(16)
    scal, sb = native.chunk_scalars(pubs, sigs, rand, prefix=pre, msgs=msgs)
    want_sb = 0
    for i in range(200):
        z = int.from_bytes(rand[16 * i:16 * i + 16], "little") | 1
        k = S._challenge_scalar_pure(CTX, msgs[i], pubs[i], sigs[i][:32])
        s = int.from_bytes(sigs[i][32:], "little") & ((1 << 255) - 1)
        want_sb = (want_sb + z * s) % R.L
        assert int.from_bytes(scal[64 * i:64 * i + 32], "little") == z
        assert int.from_bytes(scal[64 * i + 32:64 * i + 64], "little") \
            == z * k % R.L, i
    assert int.from_bytes(sb, "little") == want_sb
    # given challenges (the RFC 9496 scheme's mode): any 256-bit k, and
    # no marker bit, so bit 255 of s is part of s
    ks = [(R.L - 1).to_bytes(32, "little"), b"\xff" * 32, b"\x00" * 32]
    sigs3 = [rng.randbytes(32) + (R.L - 1).to_bytes(32, "little")] * 3
    scal, sb = native.chunk_scalars(pubs[:3], sigs3, b"\xff" * 48, ks=ks)
    z = (1 << 128) - 1
    for i, k in enumerate(ks):
        assert int.from_bytes(scal[64 * i + 32:64 * i + 64], "little") \
            == z * int.from_bytes(k, "little") % R.L
    assert int.from_bytes(sb, "little") == 3 * z * (R.L - 1) % R.L
    assert native.chunk_scalars(pubs[:1], [sigs[2]], b"\x00" * 16,
                                ks=ks[:1]) is None  # marker bit: s >= L
    unmarked = sigs[2][:63] + bytes([sigs[2][63] & 0x7F])
    assert native.chunk_scalars(pubs[:1], [unmarked], b"\x00" * 16,
                                prefix=pre, msgs=[b""]) is None


def test_chunk_check_rfc9496_scheme_rides_the_same_call(signed):
    """ristretto.batch_verify hashes its challenges in Python and gives
    them to the same native call; a key repeated inside a chunk is
    decoded once and still checked every time."""
    sk, pub = R.keygen(b"\x42" * 32)
    items = [(pub, b"ctx", bytes([i]) * 8, R.sign(sk, b"ctx", bytes([i]) * 8))
             for i in range(70)]
    assert R.batch_verify(items)
    assert R.batch_verify(items, rng=random.Random(7))
    bad = list(items)
    bad[69] = (pub, b"ctx", b"other", bad[69][3])
    assert not R.batch_verify(bad)
    same_key = [signed[3]] * 40
    assert _chunk(same_key) == 1
    assert _chunk(same_key[:39] + [_corrupt(signed[3], "signature", None)]) == 0


def test_chunk_checks_run_concurrently_with_one_answer(signed):
    """8 threads x 50 chunk checks of different batches at once, valid
    and invalid mixed, Straus- and Pippenger-sized: every answer is the
    single-threaded one, so the call writes no shared scratch."""
    import sys
    import threading

    batches = []
    for b in range(16):
        size = (20, 32, 33, 48)[b % 4]
        items = signed[b * 48:b * 48 + size]
        if b % 3 == 0:
            at = b % size
            items = list(items)
            items[at] = _corrupt(
                items[at], ("signature", "public_key", "s_geq_L")[b % 9 // 3],
                other_pub=signed[1000 + b][0])
        batches.append(items)
    want = [_chunk(items, random.Random(b)) for b, items in enumerate(batches)]
    assert sorted(set(want)) == [-1, 0, 1]
    got = [[None] * 50 for _ in range(8)]

    def worker(t):
        for j in range(50):
            b = (7 * t + j) % 16
            got[t][j] = (b, _chunk(batches[b], random.Random(b)))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(t,)) for t in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(old)
    for row in got:
        assert all(rc == want[b] for b, rc in row), row


# -- the loader: a stale library is rebuilt, not degraded to -----------


@pytest.mark.parametrize("compiler", ["present", "absent"])
def test_library_lacking_an_export_is_rebuilt_once(tmp_path, monkeypatch,
                                                   compiler):
    """A cached _r255.so that is newer than the source but was built
    from older source (here: r255.c without the chunk check) must not
    put sr25519 on the pure-Python path in silence: the loader rebuilds
    it once; only with no compiler does the package degrade, and
    ``load_error`` says so. The old source is r255.c up to where the
    chunk check begins."""
    import subprocess

    src = (native._DIR / "r255.c").read_text()
    old_src = tmp_path / "old.c"
    old_src.write_text(src[:src.index("/* The chunk check:")])
    (tmp_path / "r255.c").write_text(src)
    so = tmp_path / "_r255.so"
    subprocess.run(["cc", "-O0", "-shared", "-fPIC", "-pthread", "-o",
                    str(so), str(old_src)], check=True)
    stale_inode = so.stat().st_ino
    os.utime(tmp_path / "r255.c", (1, 1))  # the cache looks fresh
    for name, value in (("_DIR", tmp_path), ("_SRC", tmp_path / "r255.c"),
                        ("_SO", so), ("load_error", native.load_error)):
        monkeypatch.setattr(native, name, value)
    builds = []
    run = subprocess.run
    monkeypatch.setattr(
        native.subprocess, "run",
        lambda cmd, **kw: builds.append(cmd) or run(cmd, **kw))
    if compiler == "absent":
        monkeypatch.setenv("CC", str(tmp_path / "no-such-cc"))
    handle = native._load()
    assert len(builds) == 1
    if compiler == "present":
        assert native.load_error is None
        assert so.stat().st_ino != stale_inode
        assert handle.r255_round_check(0, 1, None, None, None, b"p", None,
                                       None, None, None) == 1
    else:
        assert handle is None
        assert "no-such-cc" in native.load_error
        assert "failed" in native.load_error
