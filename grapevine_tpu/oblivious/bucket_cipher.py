"""BucketCipher: on-device keystream encryption of HBM bucket rows.

In the reference, ORAM contents live inside SGX's hardware-encrypted EPC
(reference README.md:16,49) — the operator snapshotting RAM sees only
ciphertext. A TPU has no enclave, so this module supplies the equivalent
property for the bucket trees at rest in HBM: every bucket row is XORed
with a ChaCha keystream keyed by a device-resident secret, the bucket's
heap index, and a per-write epoch nonce, so

- a memory snapshot reveals nothing about record contents or slot
  metadata (which blocks live where);
- rewriting a bucket with identical plaintext yields fresh ciphertext
  (the epoch advances every round), so snapshot diffing shows only
  *that* the transcript's buckets were written — which the transcript
  already reveals.

Cipher: RFC 7539 ChaCha block function on the 16-word state
``[consts | key(8) | block_ctr | bucket | epoch_lo | epoch_hi]`` — i.e.
standard ChaCha with counter = in-row block index and nonce = (bucket,
epoch), vectorized over rows and blocks (the MXU is untouched, this
rides the VPU). ``rounds`` is configurable:
20 = RFC ChaCha20; the engine default is 8 (ChaCha8, unbroken, standard
in perf-sensitive deployments) because keystream cost scales linearly
with rounds. SURVEY.md §7 hard-part 3 names AES-CTR with a documented
fallback: this is that documented fallback — AES without AES-NI/VPU
byte-shuffles would be a bitsliced Pallas project for strictly worse
throughput at no security gain over ChaCha.

Stream order (the ONE definition; :func:`stream_tiles` and
:func:`group_words`, which the jnp path below and the Pallas kernel of
pallas_cipher.py both go through): stream position ``p``
of a row is **state word ``(p // 128) % 16`` of the block whose counter
is ``(p // 2048) * 128 + p % 128``**. So every 128-lane tile ``q`` of
the stream is one whole state word (``q % 16``) of one group of 128
blocks (``q // 16``): a ``[rows, 128]`` block computation yields sixteen
whole lane tiles, and nothing is ever interleaved, concatenated off a
tile boundary or relaid. A bucket row spends its stream on its stored
value row first (``OramConfig.stored_row_words``: the ``Z*V`` block
words and, since PR 44, the zero words that bring a wide row to whole
tiles) and its ``Z`` slot-index words after it
(:func:`row_plane_keystreams`), so the wide value plane starts on
tile 0 and, at a stored width, ends on a tile's last lane. A fixed
permutation of the same ChaCha output — PRF security does not depend
on the order — and the at-rest format since checkpoint version 2
(version 1 took state word ``m // n_blocks`` of block
``m % n_blocks``, index words first; version 3 is this order at the
stored widths).

What the compiler makes of the jnp path (described v5e, PERF.md §5,
PR 40): it is NOT fused into one pass. XLA splits the rounds over
multi-output fusions that hand the state words to each other through
HBM and assembles the keystream there before the XOR reads it; the
tile-aligned order spares the relayout and the masked copy, not the
keystream's round trip. On a TPU the engine therefore resolves
``bucket_cipher_impl`` to the Pallas kernel (pallas_cipher.py), which
makes the keystream in VMEM and XORs it where it is made; this module
stays the reference, the CPU path and what the tests compare against.

Epoch-0 convention: ``nonce == 0`` marks a never-written bucket and maps
to the identity keystream (the all-zero initial tree is its own
ciphertext). The operator learns which buckets were never written —
information the public access transcript already contains. The keystream
is still *computed* for every row and masked, so work is
content-independent.

The stash, position map, and freelist stay plaintext: they are private
working state (the EPC analog — see the threat model in
oram/path_oram.py), not part of the HBM bucket-tree surface this cipher
protects. Key material (u32[8]) lives in OramState, never in the tree.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

U32 = jnp.uint32

#: "expand 32-byte k"
_SIGMA = (0x61707865, 0x3320646E, 0x79622D32, 0x6B206574)


def _rotl(x: jax.Array, n: int) -> jax.Array:
    return (x << U32(n)) | (x >> U32(32 - n))


def _qr(s, a, b, c, d):
    s[a] = s[a] + s[b]
    s[d] = _rotl(s[d] ^ s[a], 16)
    s[c] = s[c] + s[d]
    s[b] = _rotl(s[b] ^ s[c], 12)
    s[a] = s[a] + s[b]
    s[d] = _rotl(s[d] ^ s[a], 8)
    s[c] = s[c] + s[d]
    s[b] = _rotl(s[b] ^ s[c], 7)


#: stream positions that one state word of a block group covers: the
#: TPU's lane count, so a state word of 128 blocks is one lane tile
LANES = 128
#: stream positions per group of LANES blocks (16 state words each)
GROUP_WORDS = 16 * LANES


def _double_round(s):
    """One column round and one diagonal round on the state list."""
    _qr(s, 0, 4, 8, 12)
    _qr(s, 1, 5, 9, 13)
    _qr(s, 2, 6, 10, 14)
    _qr(s, 3, 7, 11, 15)
    _qr(s, 0, 5, 10, 15)
    _qr(s, 1, 6, 11, 12)
    _qr(s, 2, 7, 8, 13)
    _qr(s, 3, 4, 9, 14)
    return s


def chacha_words(key, counter, n1, n2, n3, rounds: int = 8,
                 rolled: bool = False) -> list:
    """ChaCha block function → the 16 output words, each ``counter``'s
    shape. ``key`` is anything ``key[i]`` indexes into eight u32 scalars
    (an array here, words read from a kernel's ref in Pallas): the ONE
    copy of the round schedule and the feedforward every
    implementation runs.

    ``rolled`` traces the double rounds as a ``fori_loop`` over that
    one copy, unrolled again where the program is lowered
    (``unroll=True``): the same words and the same straight-line code
    for the compiler, from a jaxpr a quarter the size. The Pallas
    kernel asks for it, because its body is traced in Python once a
    signature at every start-up, beside whatever else the host is
    doing; left rolled for the compiler too, the kernel's mailbox pass
    went from 1.66 to 2.07-2.29 ms on a v5e (PERF.md section 6, PR 46).
    XLA's jnp path keeps the straight-line form."""
    shape = counter.shape
    init = [jnp.full(shape, c, U32) for c in _SIGMA]
    init += [jnp.broadcast_to(key[i], shape) for i in range(8)]
    init += [counter] + [jnp.broadcast_to(n, shape) for n in (n1, n2, n3)]
    s = list(init)
    if rolled:
        s = list(jax.lax.fori_loop(
            0, rounds // 2, lambda _, st: tuple(_double_round(list(st))),
            tuple(s), unroll=True))
    else:
        for _ in range(rounds // 2):
            _double_round(s)
    # feedforward (state + init, mod 2^32 by RFC 7539) as a plain loop:
    # a listcomp would put the adds in a `<listcomp>` frame on py<=3.11,
    # making the rangelint allowlist site key python-version-dependent
    out = []
    for a, b in zip(s, init):
        out.append(a + b)
    return out


def chacha_blocks(
    key: jax.Array,  # u32[8]
    counter: jax.Array,  # u32[...] block counter per lane
    n1: jax.Array,  # u32[...] nonce word 1 (bucket heap index)
    n2: jax.Array,  # u32[...] nonce word 2 (write epoch, low word)
    n3: jax.Array | None = None,  # u32[...] nonce word 3 (epoch, high word)
    rounds: int = 8,
) -> jax.Array:
    """ChaCha block function, vectorized: → u32[..., 16] keystream."""
    n3 = U32(0) if n3 is None else n3
    return jnp.stack(chacha_words(key, counter, n1, n2, n3, rounds), axis=-1)


def stream_tiles(n_words: int):
    """The stream order, tile by tile: ``(group, word, start, width)``
    for every lane tile of an ``n_words`` stream — positions
    ``[start, start + width)`` are lanes ``[0, width)`` of state word
    ``word`` of block group ``group`` (:func:`group_words`). Only the
    last tile can be narrower than ``LANES``."""
    for q in range(-(-n_words // LANES)):
        start = q * LANES
        yield q // 16, q % 16, start, min(LANES, n_words - start)


def group_words(key, lane, n1, n2, n3, group: int, rounds: int,
                rolled: bool = False) -> list:
    """The 16 state words of block group ``group``: lane ``l`` of each
    is that word of the block whose counter is ``group * LANES + l``.
    ``lane`` is the u32 lane index, ``[rows, lanes]``; the nonce words
    broadcast against it."""
    return chacha_words(
        key, lane + U32(group * LANES), n1, n2, n3, rounds, rolled)


def keystream_tile(key, n1, n2, n3, rows: int, n_words: int, rounds: int):
    """ChaCha keystream u32[rows, n_words] in stream order, unmasked,
    for rows whose nonce words are ``n1/n2/n3`` ([rows, 1] or scalars)
    and ``key`` eight u32 scalars: the stream as ONE array, for the jnp
    path (pallas_cipher.py's XOR kernel stores tile by tile instead).
    A stream under one lane tile computes only its own
    blocks of state word 0."""
    lanes = min(LANES, n_words)
    # 2-D iota: the one form Mosaic takes, and jnp alike
    lane = jax.lax.broadcasted_iota(U32, (rows, lanes), 1)
    tiles = []
    for group, word, _, width in stream_tiles(n_words):
        if word == 0:
            words = group_words(key, lane, n1, n2, n3, group, rounds)
        tiles.append(words[word][:, :width])
    return jnp.concatenate(tiles, axis=1)


def row_keystream(
    key: jax.Array,  # u32[8]
    bucket: jax.Array,  # u32[R]
    epoch: jax.Array,  # u32[R, 2] (lo, hi); 0 = identity (never written)
    n_words: int,
    rounds: int = 8,
) -> jax.Array:
    """Keystream rows u32[R, n_words] in stream order (module
    docstring); zero rows where epoch == 0.

    The epoch is 64 bits across two nonce words, so the per-round write
    counter cannot wrap within any feasible bus lifetime — a u32 epoch
    would wrap after 2^32 rounds (~1.4 years at 100 rounds/s), landing
    one access in plaintext (epoch 0) and replaying every historical
    (bucket, epoch) pair into a two-time pad for a snapshot-diffing
    operator."""
    ks = keystream_tile(
        key, bucket[:, None], epoch[:, 0:1], epoch[:, 1:2],
        bucket.shape[0], n_words, rounds,
    )
    written = (epoch[:, 0] != 0) | (epoch[:, 1] != 0)
    return jnp.where(written[:, None], ks, U32(0))


def row_plane_keystreams(
    key: jax.Array,  # u32[8]
    bucket: jax.Array,  # u32[R]
    epoch: jax.Array,  # u32[R, 2]
    index_words: int,  # Z
    n_words: int,  # Z + Z*V, the whole bucket row
    rounds: int = 8,
):
    """``(ks_idx u32[R, Z], ks_val u32[R, Z*V])`` of bucket rows: the
    value words take the head of the row's stream, so the wide plane is
    tile-aligned, and the slot-index words follow them. Where a row's
    two planes meet their keystream is said here and nowhere else."""
    ks = row_keystream(key, bucket, epoch, n_words, rounds)
    return ks[:, n_words - index_words:], ks[:, : n_words - index_words]


def epoch_next(epoch: jax.Array) -> jax.Array:
    """Advance a u32[2] (lo, hi) epoch counter with carry."""
    lo = epoch[0] + U32(1)
    hi = epoch[1] + jnp.where(lo == 0, U32(1), U32(0))
    return jnp.stack([lo, hi])


# NOTE: whole-tree passes (the expiry sweep) decrypt/re-encrypt entire
# rows chunk-by-chunk via engine/expiry.py:_chunked_tree_sweep, through
# the same entry point as the rounds (oram/path_oram.py cipher_rows:
# the Pallas kernel on a TPU, reading and writing each chunk where it
# lies in the plane; this module's keystream on the CPU); there is no
# partial-word decrypt API on purpose — CTR-mode random access would
# permit one, but nothing uses it and the sweep's cost model is the
# full-row recrypt documented there.
