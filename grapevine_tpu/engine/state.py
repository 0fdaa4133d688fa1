"""Engine state: the two ORAMs plus private scalar bookkeeping.

Value layouts (all uint32 words, little-endian byte order on the host
side; timestamps and the insertion sequence counter are u64 carried as
two u32 lanes (lo, hi) — matching the wire's u64 timestamp with no 2106
rollover and no 2^32-creates lifetime bound):

records ORAM block (one Record, reference README.md:132-136):
    id[4] | sender[8] | recipient[8] | ts[2] | payload[234]   = 256 words
    (exactly the reference's 1024-byte record)

mailbox ORAM block (one hash bucket of K mailboxes):
    per mailbox: key[8] |
        entries[cap × (blk[1] | idw[1] | seq[2] | ts[2])]
    → K * (8 + 6*cap) words.

A mailbox entry stores only the record's block index plus the second
msg-id word; the full 128-bit id lives in (and is verified against) the
records ORAM. Truncated entry matching is only ever used to *locate* an
entry after the records ORAM has verified the full id (phases B→C), or
for zero-id selection where the mailbox invariant supplies correctness;
block indices are unique among live records, so at most one entry can
match.

Private (non-transcript) state, the EPC analog — see the threat model in
oram/path_oram.py: the free-block stack, live-recipient count, the global
insertion sequence counter, the mailbox hash key, and the RNG key.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..config import GrapevineConfig
from ..wire import constants as C
from ..oram.path_oram import OramConfig, OramState, init_oram

U32 = jnp.uint32

# records block layout offsets (words); u64 fields = (lo, hi) u32 lanes
REC_ID = slice(0, 4)
REC_SENDER = slice(4, 12)
REC_RECIPIENT = slice(12, 20)
REC_TS = 20  # u64 low lane; high lane at REC_TSH
REC_TSH = 21
PAYLOAD_WORDS = C.PAYLOAD_SIZE // 4  # 234 @1KB records, 490 @2KB
REC_PAYLOAD = slice(22, 22 + PAYLOAD_WORDS)
REC_WORDS = 22 + PAYLOAD_WORDS  # 256 @1KB (exactly the 1024B record)
KEY_WORDS = 8
ID_WORDS = 4
ENTRY_WORDS = 6  # blk | msg-id word 1 | seq lo | seq hi | ts lo | ts hi
ENT_BLK = 0
ENT_IDW = 1
ENT_SEQ = 2  # u64 low lane
ENT_SEQH = 3
ENT_TS = 4  # u64 low lane
ENT_TSH = 5


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static engine geometry derived from a GrapevineConfig."""

    max_messages: int
    max_recipients: int
    mailbox_cap: int
    expiry_period: int
    batch_size: int
    rec: OramConfig
    mb: OramConfig
    mb_table_buckets: int
    mb_slots: int  # K mailboxes per hash bucket
    mb_choices: int = 1  # hash choices per recipient (2 = power-of-two)
    # Constants that cannot be set, stand-ins for two deleted options
    # (PR 49: there is one slot-order machinery and one sort).
    # benchmarks/lib/harness.py:218-219 and chip_smoke.py print them in
    # every run's `init` line, and as fields they stay in ``repr``, so
    # `engine_fingerprint` is what it was for every state directory a TPU
    # wrote (tests/test_checkpoint.py pins the digest). They go with the
    # next checkpoint VERSION (ROADMAP Design 1, debt (i)).
    vphases_impl: str = dataclasses.field(default="dense", init=False)
    sort_impl: str = dataclasses.field(default="xla", init=False)
    #: resolved position-map implementation (oram/posmap.py): "flat" or
    #: "recursive" — the per-tree geometry lives in rec.posmap/mb.posmap
    #: (PosMapSpec), which the checkpoint fingerprint covers via repr
    posmap_impl: str = "flat"
    #: resolved tree-top cache depth (the requested k before per-tree
    #: clamping; each tree's effective depth lives in
    #: rec/mb.top_cache_levels and the inner posmap specs — all covered
    #: by the checkpoint fingerprint via repr, so a cached checkpoint
    #: can never silently restore into a differently-cached engine)
    tree_top_cache_levels: int = 0
    # Read-only stand-in for a deleted option: benchmarks/lib/harness.py:221
    # prints it in every run's `init` line and
    # benchmarks/tests/test_round_bytes.py:30 asserts it; no simplicity PR
    # may edit those files. It goes when the next `benchmark` PR drops
    # both readers.
    @property
    def evict_every(self) -> int:
        return 1

    @property
    def id_bits(self) -> int:
        """PRP domain bits for msg-id word 0-1 (the block index space)."""
        return max(1, self.max_messages.bit_length() - 1)

    @classmethod
    def from_config(cls, cfg: GrapevineConfig) -> "EngineConfig":
        m = cfg.mailbox_table_buckets
        k = max(1, cfg.mailbox_slots)
        mb_value_words = k * (KEY_WORDS + ENTRY_WORDS * cfg.mailbox_cap)
        cimpl = cfg.bucket_cipher_impl
        if cimpl is None:
            # the one-pass Pallas kernel where Mosaic compiles it; the
            # CPU would run it in interpret mode, a step a row tile
            # (PERF.md §6, PR 40: the chip's A/B). Resolved here —
            # engine construction time — because config objects must
            # stay importable without initializing a JAX backend.
            from ..config import on_tpu

            cimpl = "pallas" if on_tpu() else "jnp"
        # position-map impl: auto resolves to "flat" on every backend —
        # the recursive map trades ~2× HBM path traffic per round for a
        # ~sqrt(blocks)× smaller resident footprint, a win only once
        # capacity outgrows private memory (flip per OPERATIONS.md §13;
        # not measured on the chip)
        pimpl = cfg.posmap_impl if cfg.posmap_impl is not None else "flat"
        # tree-top cache: auto = 4 on every backend under the phase
        # engine (0 under commit="op" — the differential oracle stays
        # cache-free). Unlike the radix/recursive knobs, caching never
        # trades one algorithm for another: it strictly removes HBM
        # gather/scatter rows and cipher work from every access, and the
        # CPU A/B confirms the win off-TPU (bench.py tree_cache_ab,
        # PERF.md Round 10); per-k sizing and flip guidance in
        # OPERATIONS.md §14. Clamped per tree so at least the leaf
        # level stays in HBM.
        tc = cfg.tree_top_cache_levels
        if tc is None:
            tc = 4 if cfg.commit == "phase" else 0
        rec_tc = min(tc, cfg.records_height)
        mb_tc = min(tc, cfg.mailbox_height)
        rec_pm = mb_pm = None
        if pimpl == "recursive":
            from ..oram.posmap import derive_posmap_spec

            rec_pm = derive_posmap_spec(
                cfg.max_messages,
                stash_size=cfg.stash_size,
                cipher_rounds=cfg.bucket_cipher_rounds,
                top_cache_levels=tc,
            )
            mb_pm = derive_posmap_spec(
                m,
                stash_size=cfg.stash_size,
                cipher_rounds=cfg.bucket_cipher_rounds,
                top_cache_levels=tc,
            )
        return cls(
            max_messages=cfg.max_messages,
            max_recipients=cfg.max_recipients,
            mailbox_cap=cfg.mailbox_cap,
            expiry_period=cfg.expiry_period,
            batch_size=cfg.batch_size,
            rec=OramConfig(
                height=cfg.records_height,
                value_words=REC_WORDS,
                bucket_slots=cfg.bucket_slots,
                stash_size=cfg.stash_size,
                cipher_rounds=cfg.bucket_cipher_rounds,
                cipher_impl=cimpl,
                n_blocks=cfg.max_messages,
                posmap=rec_pm,
                top_cache_levels=rec_tc,
            ),
            mb=OramConfig(
                height=cfg.mailbox_height,
                value_words=mb_value_words,
                bucket_slots=cfg.bucket_slots,
                stash_size=cfg.stash_size,
                cipher_rounds=cfg.bucket_cipher_rounds,
                cipher_impl=cimpl,
                n_blocks=m,
                posmap=mb_pm,
                top_cache_levels=mb_tc,
            ),
            mb_table_buckets=m,
            mb_slots=k,
            mb_choices=cfg.resolved_mailbox_choices,
            posmap_impl=pimpl,
            tree_top_cache_levels=tc,
        )


class EngineState(NamedTuple):
    rec: OramState
    mb: OramState
    freelist: jax.Array  # u32[max_messages]; [0:free_top] = free block indices
    free_top: jax.Array  # u32 scalar
    recipients: jax.Array  # u32 scalar: live recipients
    seq: jax.Array  # u32[2] (lo, hi): u64 global insertion counter
    hash_key: jax.Array  # u32[2]: keyed mailbox-bucket PRF
    id_key: jax.Array  # u32[4]: block-index PRP key (oblivious/prp.py)
    rng: jax.Array  # jax PRNG key


def init_engine(ecfg: EngineConfig, seed: int = 0) -> EngineState:
    key = jax.random.PRNGKey(seed)
    k_rec, k_mb, k_hash, k_id, k_rng = jax.random.split(key, 5)
    return EngineState(
        rec=init_oram(ecfg.rec, k_rec),
        mb=init_oram(ecfg.mb, k_mb),
        freelist=jnp.arange(ecfg.max_messages, dtype=U32),
        free_top=jnp.uint32(ecfg.max_messages),
        recipients=jnp.uint32(0),
        seq=jnp.array([1, 0], U32),
        hash_key=jax.random.bits(k_hash, (2,), U32),
        id_key=jax.random.bits(k_id, (4,), U32),
        rng=k_rng,
    )


def state_spec(ecfg: EngineConfig):
    """Flattened leaf template of an EngineState for this geometry.

    Returns ``(treedef, leaves)`` where ``leaves`` are ShapeDtypeStructs
    in deterministic pytree order — the serialization contract
    engine/checkpoint.py seals against. Computed with ``eval_shape`` so
    no device arrays are materialized."""
    tmpl = jax.eval_shape(lambda: init_engine(ecfg, 0))
    leaves, treedef = jax.tree_util.tree_flatten(tmpl)
    return treedef, leaves


def mb_parse(ecfg: EngineConfig, value: jax.Array):
    """Split a mailbox block value into (keys [K,8], entries [K,cap,4])."""
    k, cap = ecfg.mb_slots, ecfg.mailbox_cap
    v = value.reshape(k, KEY_WORDS + ENTRY_WORDS * cap)
    keys = v[:, :KEY_WORDS]
    entries = v[:, KEY_WORDS:].reshape(k, cap, ENTRY_WORDS)
    return keys, entries


def mb_pack(ecfg: EngineConfig, keys: jax.Array, entries: jax.Array) -> jax.Array:
    k, cap = ecfg.mb_slots, ecfg.mailbox_cap
    flat = jnp.concatenate(
        [keys, entries.reshape(k, cap * ENTRY_WORDS)], axis=1
    )
    return flat.reshape(k * (KEY_WORDS + ENTRY_WORDS * cap))


def mb_bucket_hash(
    hash_key: jax.Array, recipient: jax.Array, n_buckets: int, salt: int = 0
):
    """Keyed PRF: recipient (8 words) → bucket index in [0, n_buckets).

    A small ARX/multiply mixer (murmur-style finalizer per word). Secret
    ``hash_key`` keeps bucket choices unpredictable to clients, thwarting
    targeted hash-flooding of one bucket (the analog of the reference's
    enclave-private hashing). ``salt`` domain-separates the two
    independent hash functions of the two-choice table (h_c = salt c).
    """
    h = hash_key[0] ^ jnp.uint32(salt * 0x9E3779B9)
    c1, c2 = jnp.uint32(0xCC9E2D51), jnp.uint32(0x1B873593)
    for w in range(KEY_WORDS):
        x = recipient[..., w] * c1
        x = (x << 15) | (x >> 17)
        x = x * c2
        h = h ^ x
        h = (h << 13) | (h >> 19)
        h = h * jnp.uint32(5) + jnp.uint32(0xE6546B64)
    h = h ^ hash_key[1]
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h & jnp.uint32(n_buckets - 1)
