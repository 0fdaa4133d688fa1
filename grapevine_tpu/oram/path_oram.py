"""Batched Path ORAM as a branchless JAX array program.

Re-designs the reference's storage layer (upstream ``mc-oblivious-ram``'s
PathORAM-4096-Z4 over ``aligned-cmov``; named at reference README.md:16,49
and SURVEY.md §2b) for TPU:

- the bucket tree lives in HBM as two arrays chosen for XLA-TPU layout
  behavior (each alternative was measured to force multi-GB relayout
  copies or pathological strided slices — see the layout note on
  ``OramState``): a flat 1-D slot-index array ``tree_idx[n*Z]`` and a
  value array ``tree_val`` of one row a bucket, ``[n, Z*V]`` or, where
  the row is eight lane tiles or more, ``[n, tiles, 128]``, whose
  4080-byte rows match upstream's PathORAM-4096 bucket granularity;
- per-block leaf assignments are **not** stored in the tree: the flat
  position map in private memory is authoritative, and working-set
  leaves are one private gather away. (Upstream stores leaves in bucket
  metadata because its enclave cannot afford a big in-EPC posmap; here
  the posmap is already resident private state.)
- the stash is a fixed-size array scanned with masked selects (the
  vectorized constant-time linear scan);
- eviction is the textbook greedy deepest-first assignment, computed as
  masked prefix-sums + one conflict-free scatter per access.

Threat model (the TPU translation of "inside the enclave" vs "untrusted
host", SURVEY.md §1): the *public access transcript* is the sequence of
bucket-tree paths (equivalently leaf indices) touched on the big HBM tree
arrays. Obliviousness means this sequence is independent of which logical
blocks are accessed and what operations are performed. The position map,
stash, free lists, and scalar engine state are private working state (the
EPC analog); upstream likewise keeps its top-level position map inside the
enclave.

Algorithm per access (Path ORAM, Stefanov et al., PAPERS.md):
  1. ``leaf = posmap[idx]``; remap ``posmap[idx] = new_leaf`` (caller
     supplies fresh uniform randomness — keeping the module deterministic
     given its inputs, which is what makes transcript replay testable).
  2. Fetch the ``height+1`` buckets on the root→leaf path into a working
     set alongside the stash.
  3. One masked scan finds the block; the caller's branchless ``fn``
     computes the new value / keep / insert decision.
  4. Greedy eviction reassigns every working-set entry to the deepest
     bucket on the fetched path compatible with its leaf (common-prefix
     depth), at most ``bucket_slots`` per bucket; leftovers return to the
     stash. Stash overflow is counted in a sticky uint32 — it must never
     fire at the configured geometry (tests assert this; Z=4 theory says
     negligible).
  5. Write the path back (same addresses — the write transcript equals the
     read transcript).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax

from ..config import on_tpu as _on_tpu
import jax.numpy as jnp

from ..oblivious.bucket_cipher import (  # noqa: F401  (row_keystream: leaf plane)
    LANES,
    epoch_next,
    row_keystream,
    row_plane_keystreams,
)
from ..oblivious.primitives import SENTINEL, first_true_onehot, onehot_select, rank_of
from ..obs.phases import device_phase

U32 = jnp.uint32

#: u32-lane certified geometry (rangelint, OPERATIONS.md §18): the
#: largest tree this codebase's index arithmetic provably never wraps
#: at. OramConfig.__post_init__ refuses anything bigger.
MAX_U32_HEIGHT = 29
MAX_U32_BLOCKS = 1 << 30


#: the most one round may add to a tree's sticky ``overflow`` counter,
#: as :func:`RANGELINT_BOUNDS` declares it. A round can drop no more
#: rows than its working set holds: 86,012 by rangelint's trace at
#: B = 2048 and 2^21 messages (tests/test_rangelint.py holds the
#: served geometries to it); 2^24 covers B = 2^16 on a 30-level tree.
OVERFLOW_ROUND_BUDGET = 1 << 24

#: a value row at least this many lane tiles wide is STORED on whole
#: tiles (``OramConfig.stored_row_words``): the pad is then under an
#: eighth of the row (the 6,080-word mailbox row pays 64 words, 1.05 %),
#: where a toy row of a few words would be stored many times over
PADDED_ROW_MIN_TILES = 8


def RANGELINT_BOUNDS(cfg: "OramConfig", prefix: str = "state") -> dict:
    """Rangelint input-interval anchors (analysis/rangelint.py) for one
    ``OramState`` pytree under ``prefix`` — the declared invariants of
    the private planes where geometry-bounded values enter a traced
    round:

    - position values (flat table, stash/cache leaf metadata, the
      recursive map's internal table and packed entry values) are
      leaves: ``< cfg.leaves``;
    - everything encrypted at rest (HBM tree planes under the cipher)
      or sentinel-bearing (stash/cache idx) stays at the full u32 lane
      — ciphertext is opaque to interval reasoning, and the round's own
      clamps/masks re-establish bounds after decryption (the posmap
      ``& (leaves-1)`` masks, the eviction bid clamp).

    Declared bounds are *assumptions* the rest of the program is
    certified against; each is an invariant an existing test pins."""
    lv = cfg.leaves - 1
    b = {
        f"{prefix}.stash_leaf": (0, lv),
        f"{prefix}.cache_leaf": (0, lv),
        # sticky diagnostic counter with a declared per-run increment
        # budget: the budgeted headroom is what certifies
        # `overflow + dropped` wrap-free
        f"{prefix}.overflow": (0, 2**32 - OVERFLOW_ROUND_BUDGET),
    }
    if not cfg.encrypted:
        # plaintext trees carry their leaf metadata un-ciphered
        b[f"{prefix}.tree_leaf"] = (0, lv)
    if cfg.posmap is None:
        b[f"{prefix}.posmap"] = (0, lv)
    else:
        from .posmap import inner_oram_config

        icfg = inner_oram_config(cfg.posmap)
        inner = f"{prefix}.posmap.inner"
        # the internal ORAM's block values are packed OUTER leaf
        # entries; its own flat map holds INTERNAL leaves
        b[f"{inner}.posmap"] = (0, icfg.leaves - 1)
        b[f"{inner}.stash_val"] = (0, lv)
        b[f"{inner}.cache_val"] = (0, lv)
        b[f"{inner}.overflow"] = (0, 2**32 - OVERFLOW_ROUND_BUDGET)
        if not icfg.encrypted:
            b[f"{inner}.tree_val"] = (0, lv)
        b[f"{prefix}.posmap.dummy_entry"] = (0, lv)
    return b


def cipher_rows(
    cfg: "OramConfig",
    key: jax.Array,
    buckets: jax.Array,  # u32[R] heap bucket ids
    epochs: jax.Array,  # u32[R, 2] per-row (lo, hi) nonce (0 = identity)
    pidx: jax.Array,  # u32[R, Z]
    pval: jax.Array,  # u32[R, *cfg.stored_row_shape], or [R, Z*V] plaintext
    *,
    chunk: jax.Array | None = None,  # scalar: the R rows' place in a plane
    plane: jax.Array | None = None,  # the plane a chunk's rows go back to
):
    """XOR bucket rows with their keystream (encrypt ≡ decrypt).

    One ChaCha stream per (bucket, epoch) covers the stored value row
    (Z*V words and its pad) followed by the Z slot-index words (the
    stream order of oblivious/bucket_cipher.py) — a memory snapshot of
    the tree arrays reveals neither slot occupancy nor contents.

    The two directions are told apart by what is handed over. Rows cut
    from a plane (``[R, *cfg.stored_row_shape]``) are the fetch: they
    come back ``[R, stored_row_words]``, the pad words nothing anyone
    reads (the fetch cuts the rows to Z*V next). Plaintext ``[R, Z*V]``
    is the write-back: it comes back as the plane stores it, the pad
    words' keystream after the block words (the pad is zeros in
    plaintext, so that is its ciphertext). So the write-back hands over
    its rows without a padded copy of them (on a v5e an unfused pad of
    the mailbox pass is a 1.2 ms pass of its own), and where a plane
    stores its rows as ``(tiles, 128)`` neither direction pays a pass
    to relay them: the kernel reads and writes the words where they
    lie. A narrow row is stored as it is computed on and the directions
    are one signature.

    A whole-tree pass (the expiry sweep, engine/expiry.py) names its
    rows by ``chunk``: rows ``[chunk * R, (chunk + 1) * R)`` of a value
    plane. Its fetch hands over the plane itself as ``pval``; its
    write-back hands over the plaintext and the ``plane``, and gets the
    plane back with the rows written over that chunk. The kernel reads
    and writes those rows where they lie in the plane (the jnp path
    cuts them out and pastes them back, which XLA fuses with its XOR),
    so a pass holds one copy of the plane and one chunk of plaintext.

    ``cfg.cipher_impl == "pallas"`` routes through the fused Pallas
    kernel (keystream generated in VMEM and XORed in one pass — no HBM
    keystream materialization; oblivious/pallas_cipher.py). Both
    implementations produce bit-identical ciphertext."""
    r = pidx.shape[0]
    # plaintext on its way to the plane?
    to_store = pval.ndim == 2 if chunk is None else plane is not None
    tiled = len(cfg.stored_row_shape) == 2
    kernel = cfg.encrypted and cfg.cipher_impl == "pallas"
    if chunk is not None and not kernel:
        first = chunk * U32(r)
        if not to_store:
            pval = jax.lax.dynamic_slice_in_dim(pval, first, r)
        pidx, rows = cipher_rows(cfg, key, buckets, epochs, pidx, pval)
        if to_store:
            rows = jax.lax.dynamic_update_slice_in_dim(plane, rows, first, 0)
        return pidx, rows
    out_shape = (r, *cfg.stored_row_shape) if to_store else (r, -1)
    if not cfg.encrypted:
        return pidx, stored_rows(cfg, pval.reshape(r, -1)).reshape(out_shape)
    z = cfg.bucket_slots
    if kernel:
        from ..oblivious.pallas_cipher import cipher_rows_pallas

        interpret = not _on_tpu()
        if interpret and pidx.shape[0] >= 2048:
            # trace-time (once per compile), not per round: interpret
            # mode on a production-size engine means thousands of
            # per-tile host dispatches — a silent perf cliff on any
            # non-TPU backend (ADVICE r3). Correctness is unaffected.
            import warnings

            warnings.warn(
                f"pallas bucket cipher running in interpret mode on "
                f"backend {jax.default_backend()!r} with "
                f"{pidx.shape[0]} rows/round — expect a severe "
                f"slowdown; use bucket_cipher_impl='jnp' off-TPU",
                RuntimeWarning,
                stacklevel=2,
            )
        if to_store:
            pval = logical_rows(cfg, pval)
        return cipher_rows_pallas(
            key, buckets, epochs, pidx, pval, cfg.cipher_rounds,
            interpret=interpret, zv=cfg.stored_row_words,
            tiled_out=to_store and tiled, chunk=chunk, into=plane,
        )
    ks_idx, ks_val = row_plane_keystreams(
        key, buckets, epochs, z, cfg.row_words, cfg.cipher_rounds
    )
    pval = stored_rows(cfg, logical_rows(cfg, pval.reshape(r, -1)))
    return pidx ^ ks_idx, (pval ^ ks_val).reshape(out_shape)


@dataclasses.dataclass(frozen=True)
class OramConfig:
    """Static geometry (hashable: safe as a jit static argument).

    The logical block-index space and the leaf space are decoupled:
    ``blocks`` defaults to ``leaves`` (the classic ~12.5%-utilization
    Path ORAM shape) but may exceed it — ``blocks = 2·leaves`` halves
    tree HBM per block at 25% slot utilization (still conservative:
    total slots = 8·leaves = 4·blocks), and shortens every path by one
    level. Stash behavior at elevated density is covered by the
    randomized density tests (tests/test_oram.py)."""

    height: int  # leaves = 2**height
    value_words: int  # uint32 words per block value
    bucket_slots: int = 4  # Z
    stash_size: int = 96
    #: ChaCha rounds for at-rest bucket encryption; 0 disables the
    #: cipher (oblivious/bucket_cipher.py — the EPC-encryption analog)
    cipher_rounds: int = 0
    #: "jnp" or "pallas" (the one-pass VMEM keystream+XOR kernel; see
    #: cipher_rows and oblivious/pallas_cipher.py);
    #: EngineConfig.from_config resolves the engine's trees to "pallas"
    #: on a TPU; posmap.py pins the recursive map's inner tree to "jnp"
    cipher_impl: str = "jnp"
    #: logical block index space [0, n_blocks); None = leaves
    n_blocks: int | None = None
    #: position-map geometry (oram/posmap.py): None = the flat private
    #: u32[blocks+1] table; a PosMapSpec = the recursive position ORAM
    #: (state.posmap becomes a RecursivePosMapState pytree, and the
    #: bucket tree carries a per-slot leaf-metadata plane so eviction
    #: never consults the map). Part of the hashable static geometry —
    #: jit static args and the checkpoint fingerprint cover it.
    posmap: "object | None" = None
    #: tree-top cache (ROADMAP item 1, arXiv:1501.01721 §tree-top
    #: caching): the top k levels — 2^k−1 buckets, on EVERY root→leaf
    #: path, so caching them is access-pattern-neutral by construction —
    #: live decrypted in the dense ``cache_*`` planes (private working
    #: state, the stash's standing) instead of the encrypted HBM tree
    #: rows. Path fetch/write-back then touch only the bottom
    #: ``path_len − k`` levels of the big tree arrays, and the
    #: per-access cipher work shrinks by the same fraction. 0 = off,
    #: bit-for-bit the uncached program.
    top_cache_levels: int = 0

    def __post_init__(self):
        k = self.top_cache_levels
        if not (0 <= k <= self.height):
            raise ValueError(
                f"top_cache_levels must be in [0, height={self.height}] "
                f"(at least the leaf level stays in the HBM tree), got {k}"
            )
        # rangelint certified-geometry guard (analysis/rangelint.py;
        # tools/check_ranges.py cites this refusal in its report): every
        # device lane is u32 and every gather/scatter index converts to
        # int32 on the way into XLA, so the geometry must keep (a) heap
        # bucket ids plus the bucket-axis OOB-drop sentinel
        # (n_buckets_padded) within int32, (b) the leaf-plane cipher's
        # domain-separation offset (bucket + n_buckets_padded) within
        # u32, and (c) block ids plus the row-map sentinel (blocks + 2)
        # within int32 and below SENTINEL. height <= 29 and blocks <=
        # 2^30 certify all three with margin (the full argument is the
        # certified-geometry table, OPERATIONS.md §18). Scaling past
        # this bound is recipient-space sharding (ROADMAP item 2) or a
        # deeper recursion with widened lanes (item 4) — never a silent
        # wraparound.
        if self.height > MAX_U32_HEIGHT:
            raise ValueError(
                f"height {self.height} exceeds the u32-lane certified "
                f"bound (height <= {MAX_U32_HEIGHT}: heap bucket ids and "
                "int32 index conversions wrap past it — rangelint "
                "certified geometry, OPERATIONS.md §18); shard the "
                "recipient space or widen the lanes instead"
            )
        if self.blocks > MAX_U32_BLOCKS:
            raise ValueError(
                f"blocks {self.blocks} exceeds the u32-lane certified "
                f"bound (blocks <= {MAX_U32_BLOCKS} = 2^30: block ids, "
                "the dummy index, and the row-map drop sentinel must fit "
                "int32 below SENTINEL — rangelint certified geometry, "
                "OPERATIONS.md §18); shard the recipient space or widen "
                "the lanes instead"
            )

    @property
    def encrypted(self) -> bool:
        return self.cipher_rounds > 0

    @property
    def cache_buckets(self) -> int:
        """Buckets resident in the tree-top cache: 2^k − 1 (heap indices
        [0, 2^k−1) — the top k levels are a contiguous heap prefix, so
        the cache planes are indexed by heap id directly)."""
        return (1 << self.top_cache_levels) - 1

    @property
    def val_row_words(self) -> int:
        """Z*V: the value words of a bucket's Z blocks, the logical row."""
        return self.bucket_slots * self.value_words

    @property
    def stored_row_words(self) -> int:
        """Width of a ``tree_val`` / ``cache_val`` row as it is stored:
        Z*V rounded up to whole 128-word lane tiles where the row is at
        least ``PADDED_ROW_MIN_TILES`` of them wide, Z*V itself under
        that. A function of the row's width alone. The words past Z*V
        are zeros in plaintext and are enciphered with the row; why a
        tile-clean row matters is the layout note on ``OramState``."""
        zv = self.val_row_words
        if zv < PADDED_ROW_MIN_TILES * LANES:
            return zv
        return -(-zv // LANES) * LANES

    @property
    def stored_row_shape(self) -> tuple[int, ...]:
        """One ``tree_val`` row as the plane holds it: a row stored on
        whole lane tiles is ``(tiles, 128)``, so that a bucket is whole
        ``(8, 128)`` memory tiles on an untiled leading axis, contiguous
        in HBM, and one DMA places it (``_path_scatter``); a narrower
        row is ``(Z*V,)``. Row-major the bytes are the same either way.
        A function of the row's width alone, like the width itself."""
        sw = self.stored_row_words
        if sw < PADDED_ROW_MIN_TILES * LANES:
            return (sw,)
        return (sw // LANES, LANES)

    @property
    def row_words(self) -> int:
        """Keystream width per bucket: the stored value row and the Z
        slot-index words after it, enciphered as one row under one
        (bucket, epoch) nonce."""
        return self.bucket_slots + self.stored_row_words

    @property
    def leaves(self) -> int:
        return 1 << self.height

    @property
    def blocks(self) -> int:
        return self.n_blocks if self.n_blocks is not None else self.leaves

    @property
    def n_buckets(self) -> int:
        return (1 << (self.height + 1)) - 1

    @property
    def n_buckets_padded(self) -> int:
        """Tree arrays are allocated one bucket past the heap (a power of
        two) so the bucket axis divides evenly across any power-of-two
        device mesh; heap indices never address the pad bucket."""
        return 1 << (self.height + 1)

    @property
    def path_len(self) -> int:
        return self.height + 1

    @property
    def work_size(self) -> int:
        return self.stash_size + self.path_len * self.bucket_slots

    #: reserved block index used by dummy accesses; never stored in the tree
    @property
    def dummy_index(self) -> int:
        return self.blocks

    def covered_levels(self, accesses: int) -> int:
        """How many top levels one ``oram_round`` of ``accesses`` paths
        covers at least once over: a level of 2^L buckets with
        2^L <= accesses. Every bucket of such a level is an eviction
        target every round (oram/round.py). A function of the batch and
        the height alone — NOT of ``top_cache_levels``, so the cache
        never changes where a block is placed."""
        return min(accesses.bit_length(), self.path_len)

    def dense_levels(self, accesses: int) -> int:
        """``Ld``: how many top levels one ``oram_round`` of ``accesses``
        paths holds level-dense (oram/round.py): read and written back
        whole, once, as a fixed heap range, where deeper levels stay
        per-path. The covered levels, and never fewer than the tree-top
        cache (its planes are the first dense levels)."""
        return max(self.covered_levels(accesses), self.top_cache_levels)

    def fetched_bucket_rows(self, accesses: int) -> int:
        """HBM bucket rows one ``oram_round`` of ``accesses`` paths
        gathers and decrypts, then encrypts and scatters back: the dense
        heap range below the cache, ``[2^k − 1, 2^Ld − 1)``, plus the
        per-path rows of the deeper levels. A function of shapes only."""
        ld = self.dense_levels(accesses)
        return ((1 << ld) - (1 << self.top_cache_levels)
                + self.perpath_bucket_rows(accesses))

    def perpath_bucket_rows(self, accesses: int) -> int:
        """Of :meth:`fetched_bucket_rows`, the rows at the levels the
        round does not hold dense: one per path and deeper level. 0 says
        the round moves the tree whole, level by level."""
        return accesses * (self.path_len - self.dense_levels(accesses))


class OramState(NamedTuple):
    """ORAM state; a pytree (NamedTuple) so it jits/shards cleanly.

    Layout note (all measured on v5e, see git history): a 3-D value
    array ``[n, Z, V]`` with V=255 makes XLA relayout-copy the whole
    tree on gather (8 GB HLO temp, OOM at 2^20 capacity); narrow 2-D
    metadata ``[n, Z]`` gets a transposed ``{0,1}`` layout whose path
    slices dominate the round; a fully packed ``[n, Z*(2+V)]`` row
    (1028 words) is not lane-aligned, padding every row to 1152 words
    and again relayout-copying the tree. The split below keeps the
    slot metadata 1-D, which XLA never transposes, and the value rows
    ``cfg.stored_row_words`` wide: ``Z*V`` words on whole 128-word lane
    tiles. The records tree's 1,024 words are that as they are; the
    mailbox tree's 6,080 (Z=4 x 1,520) are 47.5 tiles and are stored as
    6,144, the 64 pad words zeros in plaintext (PR 44: a v5e's DEFAULT
    layout for ``u32[n,6080]`` is the transposed ``{0,1}``, and each
    round copied the 1.59 GB plane whole after its entry and before
    its exit, 4.90 + 4.76 ms of a 110.27 ms round at 2^21 messages /
    2^17 recipients; pinning ``{1,0}`` through jit's ``Format`` did the
    same on a cold compile, but an executable that jax 0.9.0 loads from
    its persistent cache returns default layouts again).

    Since PR 46 a row stored on whole tiles is a ``(tiles, 128)`` row
    of a 3-D plane, ``cfg.stored_row_shape``: ``u32[n, 48, 128]`` for
    that mailbox tree, ``u32[n, 8, 128]`` for the records tree, the
    same bytes row-major. In HBM the last two dimensions are tiled
    ``T(8,128)`` and the leading one is not, so a bucket is six (or
    one) whole memory tiles, 24 KB (4 KB) contiguous, where a row of
    the 2-D ``u32[n, 6144]`` was 48 pieces of 512 B at a stride of
    4 KB, eight buckets interleaved in every tile. What that buys (my
    chip runs, PR 46, TPU v5 lite, same size):

    - the write-back places each row by one DMA (``_path_scatter``,
      oblivious/pallas_place.py): 1.58 ms a mailbox pass at ~600 GB/s
      of rows read and written, against XLA's 11.26 ms on the 2-D
      plane, which sorted the targets, permuted the rows (2.2 ms) and
      streamed the whole 1.61 GB plane at ~360 GB/s wherever a
      scatter's rows are an eighth of its operand's or more (20,464 of
      65,536). Mosaic refuses a one-row window of the 2-D plane
      ("Slice shape along dimension 0 must be aligned to tiling (8),
      but is 1"), which is why the shape moved and not the kernel
      alone. XLA's own scatter on the 3-D plane goes by rows at
      3.37 ms, what a backend without the kernel gets;
    - XLA's gather of the same rows, 2.23 -> 1.64 ms;
    - ``device_busy_ms`` 100.25 -> 76.75, ``scope_ms.writeback``
      45.50 -> 23.12 (PERF.md section 6, PR 46).

    What it asks of the code: the rows cut from the plane are
    ``[R, tiles, 128]`` and the eviction works on ``[R, Z*V]``; made by
    XLA the step between them is a pass of its own (1.55 ms at the
    mailbox width, four a round), so the cipher kernel reads and writes
    the rows as the plane stores them and is flat on its other side
    (``cipher_rows``). The cut back to ``Z*V`` after the decrypt is
    still a bitcast and the pad before the encrypt the cipher kernel's
    own. tests/test_mosaic_lowering.py holds the compiled round to all
    of it: the plane never copied, transposed or relaid, two placement
    kernels aliased onto it, no scatter, sort or permute of its rows.
    """

    tree_idx: jax.Array  # u32[n_buckets * Z] flat; SENTINEL = empty slot
    tree_val: jax.Array  # u32[n_buckets, *stored_row_shape]; a row a bucket
    #: tree-top cache planes (cfg.top_cache_levels = k > 0; zero-length
    #: otherwise): the decrypted-resident image of heap buckets
    #: [0, 2^k−1) — the authoritative copy; those buckets' HBM tree rows
    #: go stale (empty-at-init ciphertext, re-keyed but never read).
    #: Private working state with the stash's standing (the EPC analog:
    #: VMEM/registers on TPU, a donated array elsewhere) — every path
    #: touches all k cached levels, so cache accesses are
    #: access-pattern-neutral and the plane needs no cipher or nonces.
    #: Sealed checkpoints cover it like any other leaf (engine/
    #: checkpoint.py serializes the whole pytree).
    cache_idx: jax.Array  # u32[cache_buckets * Z] (or u32[0])
    cache_val: jax.Array  # u32[cache_buckets, stored_row_words] (or [0, ·])
    #: cache mirror of tree_leaf (recursive posmap only; u32[0] else)
    cache_leaf: jax.Array
    #: per-slot leaf assignment plane, recursive posmap only (u32[0]
    #: under a flat map): with the map demoted to its own ORAM, eviction
    #: can no longer gather the whole working set's leaves from a
    #: private array, so each tree slot carries its block's leaf — the
    #: classic recursive-construction bucket metadata (upstream
    #: mc-oblivious stores leaves in buckets for exactly this reason).
    #: Same shape/standing as tree_idx; encrypted at rest alongside it
    #: (leaf_plane_cipher — a leaf is a *future* fetch path, strictly
    #: snapshot-sensitive). Invariant: for every live block, this plane
    #: equals what the position map answers (both are written from the
    #: same op's new_leaf at its last within-round occurrence).
    tree_leaf: jax.Array  # u32[n_buckets * Z] flat (or u32[0])
    stash_idx: jax.Array  # u32[S]
    stash_val: jax.Array  # u32[S, V]
    #: stash mirror of tree_leaf (u32[S] recursive, u32[0] flat)
    stash_leaf: jax.Array
    #: position map: u32[blocks + 1] private table under a flat map
    #: (last entry backs the dummy index), or a RecursivePosMapState
    #: pytree (oram/posmap.py) when cfg.posmap is a PosMapSpec
    posmap: jax.Array
    overflow: jax.Array  # u32 scalar, sticky count of dropped blocks
    #: at-rest cipher state (zero-sized semantics when cfg.cipher_rounds
    #: == 0): per-bucket 64-bit write-epoch nonce (0 = never written ⇒
    #: identity keystream), the ChaCha key, and the global epoch counter
    nonces: jax.Array  # u32[n_buckets_padded, 2] (lo, hi)
    cipher_key: jax.Array  # u32[8]
    epoch: jax.Array  # u32[2] (lo, hi), next write epoch (starts at 1)


def init_oram(cfg: OramConfig, key: jax.Array) -> OramState:
    """Empty tree; position map initialized with uniform random leaves.

    With the cipher enabled the all-zero initial tree is its own
    ciphertext (epoch-0 convention, oblivious/bucket_cipher.py). The
    posmap pytree comes from oram/posmap.py: the flat u32[blocks+1]
    table under ``cfg.posmap is None`` (bit-for-bit the pre-PR-7 draw),
    or a RecursivePosMapState packing the same table values into an
    internal Path ORAM. The recursive layout also activates the
    per-slot leaf-metadata planes (zero-length otherwise)."""
    from .posmap import init_posmap

    z, v = cfg.bucket_slots, cfg.value_words
    k_pos, k_cipher = jax.random.split(key)
    n_leaf = cfg.n_buckets_padded * z if cfg.posmap is not None else 0
    n_sleaf = cfg.stash_size if cfg.posmap is not None else 0
    cb = cfg.cache_buckets
    n_cleaf = cb * z if cfg.posmap is not None else 0
    return OramState(
        tree_idx=jnp.full((cfg.n_buckets_padded * z,), SENTINEL, U32),
        tree_val=jnp.zeros(
            (cfg.n_buckets_padded, *cfg.stored_row_shape), U32),
        cache_idx=jnp.full((cb * z,), SENTINEL, U32),
        cache_val=jnp.zeros((cb, cfg.stored_row_words), U32),
        cache_leaf=jnp.zeros((n_cleaf,), U32),
        tree_leaf=jnp.zeros((n_leaf,), U32),
        stash_idx=jnp.full((cfg.stash_size,), SENTINEL, U32),
        stash_val=jnp.zeros((cfg.stash_size, v), U32),
        stash_leaf=jnp.zeros((n_sleaf,), U32),
        posmap=init_posmap(cfg, k_pos),
        overflow=jnp.zeros((), U32),
        nonces=jnp.zeros((cfg.n_buckets_padded, 2), U32),
        cipher_key=jax.random.bits(k_cipher, (8,), U32),
        epoch=jnp.array([1, 0], U32),
    )


def leaf_plane_cipher(
    cfg: OramConfig,
    key: jax.Array,
    buckets: jax.Array,  # u32[R] heap bucket ids
    epochs: jax.Array,  # u32[R, 2] per-row (lo, hi) nonce (0 = identity)
    pleaf: jax.Array,  # u32[R, Z]
) -> jax.Array:
    """XOR leaf-metadata rows with their keystream (encrypt ≡ decrypt).

    Recursive-posmap only: a slot's leaf value is the block's *future*
    fetch path — at least as snapshot-sensitive as the slot index — so
    the plane rides the bucket cipher. Domain separation from the
    idx/val row keystream (cipher_rows) is the nonce's bucket word
    offset by ``n_buckets_padded``: heap ids never reach that range, so
    the leaf stream can never two-time-pad against the row stream under
    the same (bucket, epoch). Kept out of ``cipher_rows`` on purpose —
    the Pallas kernel covers only the idx/val planes, and this jnp path
    composes with both cipher_impls."""
    if not cfg.encrypted:
        return pleaf
    ks = row_keystream(
        key, buckets + U32(cfg.n_buckets_padded), epochs,
        cfg.bucket_slots, cfg.cipher_rounds,
    )
    return pleaf ^ ks


def path_bucket_indices(cfg: OramConfig, leaf: jax.Array) -> jax.Array:
    """Heap indices of the root→leaf path buckets. leaf: u32 → u32[path_len]."""
    depths = jnp.arange(cfg.path_len, dtype=U32)
    return ((jnp.uint32(1) << depths) - 1) + (leaf >> (cfg.height - depths))


def _common_prefix_depth(cfg: OramConfig, leaves_a: jax.Array, leaf_b: jax.Array):
    """Deepest path level where a block with leaf ``leaves_a[i]`` may live on
    the path to ``leaf_b``: the length of the common prefix of the two
    height-bit leaf numbers. Exact integer computation, unrolled over the
    (static) height."""
    # range argument (rangelint): the shifts stay in the u32 leaf lane
    # (shift amounts are trace-time constants in [0, height-1]) and the
    # int32 accumulator is bounded by height <= MAX_U32_HEIGHT — the
    # depth never approaches either lane's ceiling.
    d = jnp.zeros(leaves_a.shape, jnp.int32)
    for j in range(1, cfg.height + 1):
        shift = U32(cfg.height - j)
        d = d + (leaves_a >> shift == leaf_b >> shift).astype(jnp.int32)
    return d  # in [0, height]


def _path_gather(tree: jax.Array, path_b: jax.Array, axis_name: str | None):
    """Fetch the path bucket rows from a (possibly device-sharded) array.

    With ``axis_name`` set, the call runs inside ``shard_map`` and ``tree``
    is the local shard (contiguous range per device along axis 0). Each
    chip contributes the rows it owns, masked to zero elsewhere, and one
    ``psum`` over ICI assembles the full path on every chip — the
    collective form of BASELINE config 5's sharded bucket tree. The
    addresses touched remain exactly the public path, preserving the
    transcript."""
    with device_phase("path_gather"):
        if axis_name is None:
            return tree[path_b]
        n_local = tree.shape[0]
        base = (jax.lax.axis_index(axis_name) * n_local).astype(U32)
        loc = path_b - base
        mine = (path_b >= base) & (path_b < base + U32(n_local))
        vals = tree[jnp.where(mine, loc, 0)]
        mask = mine.reshape(mine.shape + (1,) * (vals.ndim - 1))
        with device_phase("psum_assembly"):
            return jax.lax.psum(
                jnp.where(mask, vals, jnp.zeros_like(vals)), axis_name
            )


def places_by_dma(tree: jax.Array) -> bool:
    """Whether ``_path_scatter`` places this plane's rows by DMA
    (oblivious/pallas_place.py): a plane that stores its rows as whole
    memory tiles (``OramConfig.stored_row_shape``), on a TPU. Read off
    what the function is handed; no tree's name and no option."""
    return tree.ndim == 3 and _on_tpu()


def _path_scatter(
    tree: jax.Array,
    path_b: jax.Array,
    new_vals: jax.Array,
    axis_name: str | None,
    owner: jax.Array | None = None,
):
    """Write the path rows back; each chip writes only rows it owns
    (every heap index has exactly one owner, so the global write is
    consistent with no collective). ``owner`` optionally masks out slots
    that must not be written at all (round.py's duplicate-bucket copies);
    masked slots are dropped via out-of-range targets.

    Which rows are written is a function of the public path, of
    ``owner`` (itself a function of the public leaves: the lowest row
    that meets a bucket owns it) and of the chip's index: never of a
    block's content. A plane of wide rows on a TPU has them placed by
    one DMA each (:func:`places_by_dma`), where XLA's scatter of a
    share of rows this large streams the whole plane through the core;
    the targets, and so the addresses written, are the same either
    way."""
    with device_phase("path_scatter"):
        n_local = tree.shape[0]
        new_vals = new_vals.reshape(new_vals.shape[:1] + tree.shape[1:])
        keep = owner
        if axis_name is not None:
            base = (jax.lax.axis_index(axis_name) * n_local).astype(U32)
            mine = (path_b >= base) & (path_b < base + U32(n_local))
            keep = mine if owner is None else mine & owner
            path_b = path_b - base
        by_dma = places_by_dma(tree)
        if keep is None and not by_dma:
            return tree.at[path_b].set(new_vals, unique_indices=True)
        # in-bounds targets are unique by construction: the owner map
        # gives every heap bucket exactly one owning column and a heap
        # index has one owning chip, so at most one write lands on any
        # row (the rest drop out of bounds)
        tgt = path_b if keep is None else jnp.where(
            keep, path_b, U32(n_local))
        if by_dma:
            from ..oblivious.pallas_place import place_rows

            return place_rows(
                tree, tgt.astype(jnp.int32), new_vals,
                interpret=not _on_tpu())
        return tree.at[tgt].set(new_vals, mode="drop", unique_indices=True)


def logical_rows(cfg: OramConfig, rows: jax.Array) -> jax.Array:
    """Stored value rows ``[R, stored_row_words]`` cut to their ``Z*V``
    block words (the rows themselves where they are that wide already:
    a row without a pad adds no op to the program)."""
    zv = cfg.val_row_words
    return rows if rows.shape[1] == zv else rows[:, :zv]


def stored_rows(cfg: OramConfig, rows: jax.Array) -> jax.Array:
    """Plaintext value rows ``[R, Z*V]`` brought to the stored width:
    zeros past the blocks (``logical_rows``' inverse; rows already that
    wide are returned as they are)."""
    pad = cfg.stored_row_words - rows.shape[1]
    return jnp.pad(rows, ((0, 0), (0, pad))) if pad else rows


def path_slot_indices(cfg: OramConfig, path_b: jax.Array) -> jax.Array:
    """Flat tree_idx slot indices for path buckets: [...,] → [..., Z]."""
    z = cfg.bucket_slots
    return path_b[..., None] * U32(z) + jnp.arange(z, dtype=U32)[None, :]


def working_leaves(
    state_posmap: jax.Array, cfg: OramConfig, idxs: jax.Array
) -> jax.Array:
    """Leaf assignment for working-set entries from the private posmap.

    SENTINEL/dummy slots read the throwaway posmap entry (cfg.blocks);
    their value is never used (eviction masks invalid entries)."""
    safe = jnp.where(idxs < U32(cfg.blocks), idxs, U32(cfg.blocks))
    return state_posmap[safe]


def oram_access(
    cfg: OramConfig,
    state: OramState,
    idx: jax.Array,  # u32 scalar block index (or cfg.dummy_index)
    new_leaf: jax.Array,  # u32 scalar, fresh uniform in [0, leaves)
    operand,
    fn: Callable,
    axis_name: str | None = None,
    pm_leaf: jax.Array | None = None,
):
    """One oblivious read-modify-write access.

    ``fn(value u32[V], present bool, operand) -> (new_value u32[V],
    keep bool, insert bool, out pytree)``:

    - if the block is present, its value becomes ``new_value``; ``keep``
      False removes it (DELETE);
    - if absent and ``insert``, ``(idx, new_value)`` is added (CREATE);
    - ``out`` is returned to the caller (fetched fields, status bits).

    ``fn`` must itself be branchless; it receives the *masked* value
    (zeros when absent). Returns ``(state', out, leaf)`` where ``leaf`` is
    the public transcript entry for this access — a u32 scalar under a
    flat map, u32[2] (payload leaf, internal posmap leaf) under a
    recursive one (``cfg.posmap`` set; ``pm_leaf`` must then supply a
    fresh uniform internal leaf — oram/posmap.py:lookup_remap_one).

    With ``axis_name`` set (inside ``shard_map``), the tree arrays are
    sharded along the bucket axis across the mesh and path fetch/write-back
    become masked collectives; stash, position map, and all decision logic
    are replicated — every chip runs the identical branchless program.
    """
    z, v, plen = cfg.bucket_slots, cfg.value_words, cfg.path_len
    recursive = cfg.posmap is not None

    if recursive:
        from .posmap import lookup_remap_one

        posmap, leaf, inner_leaf = lookup_remap_one(
            cfg, state.posmap, idx, new_leaf, pm_leaf
        )
    else:
        leaf = state.posmap[idx]
        posmap = state.posmap.at[idx].set(new_leaf)

    path_b = path_bucket_indices(cfg, leaf)  # u32[plen]

    # tree-top cache split: levels [0, kc) live decrypted in the cache
    # planes; only the bottom plen−kc levels touch the encrypted HBM
    # tree (and pay cipher work). kc=0 degenerates to the full path.
    # Slot-plane HBM addressing is bucket-axis ([n, Z] reshape views —
    # free, layout-identical): flat slot ids (bucket·Z + slot) escape
    # u32/int32 one geometry doubling before bucket ids do, so the
    # certified bound rides the bucket axis (rangelint; OPERATIONS.md
    # §18). The tiny cache planes keep flat slot addressing.
    kc = cfg.top_cache_levels
    bot_b = path_b[kc:]
    # runtime identity: top-kc heap ids are < cache_buckets by level
    # structure (see the matching clamp in round.py)
    top_b = jnp.minimum(path_b[:kc], U32(max(cfg.cache_buckets, 1) - 1))
    top_slots = path_slot_indices(cfg, top_b).reshape(-1)

    # --- fetch path ∪ stash into the working set -----------------------
    with device_phase("oram_fetch"):
        pidx = _path_gather(state.tree_idx.reshape(-1, z), bot_b, axis_name)
        pval = _path_gather(state.tree_val, bot_b, axis_name)
        pnonce = _path_gather(state.nonces, bot_b, axis_name)
        pidx, pval = cipher_rows(
            cfg, state.cipher_key, bot_b, pnonce, pidx, pval,
        )
        if kc:
            # cached top levels: plain private gathers (same standing as
            # the stash concatenate below — every path touches them)
            pidx = jnp.concatenate(
                [state.cache_idx[top_slots].reshape(kc, z), pidx]
            )
            pval = jnp.concatenate([state.cache_val[top_b], pval], axis=0)
        if recursive:
            pleaf = _path_gather(
                state.tree_leaf.reshape(-1, z), bot_b, axis_name
            )
            pleaf = leaf_plane_cipher(
                cfg, state.cipher_key, bot_b, pnonce, pleaf,
            )
            if kc:
                pleaf = jnp.concatenate(
                    [state.cache_leaf[top_slots].reshape(kc, z), pleaf]
                )
            pleaf = pleaf.reshape(-1)
    pidx = pidx.reshape(-1)
    pval = logical_rows(cfg, pval).reshape(-1, v)
    widx = jnp.concatenate([state.stash_idx, pidx])
    wval = jnp.concatenate([state.stash_val, pval], axis=0)
    if recursive:
        # leaves ride the per-slot metadata plane (the map can no longer
        # be gathered); the accessed block reads its fresh leaf below
        wleaf = jnp.concatenate([state.stash_leaf, pleaf])
    else:
        # leaves come from the (already remapped) private posmap: for the
        # accessed block that is new_leaf, for others their current leaf
        wleaf = working_leaves(posmap, cfg, widx)

    valid = widx != SENTINEL
    match = valid & (widx == idx)
    if recursive:
        # posmap↔metadata invariant: the map's entry for idx is already
        # new_leaf (remapped above), so the metadata row follows suit
        wleaf = jnp.where(match, new_leaf, wleaf)
    present = jnp.any(match)
    value = onehot_select(match, wval)

    new_value, keep, insert, out = fn(value, present, operand)

    # --- apply the modification obliviously ----------------------------
    wval = jnp.where(match[:, None], new_value[None, :], wval)
    drop = match & ~keep
    widx = jnp.where(drop, SENTINEL, widx)

    do_insert = insert & ~present & (idx != cfg.dummy_index)
    free = widx == SENTINEL
    ins_slot = first_true_onehot(free) & do_insert
    inserted = jnp.any(ins_slot)
    widx = jnp.where(ins_slot, idx, widx)
    wleaf = jnp.where(ins_slot, new_leaf, wleaf)
    wval = jnp.where(ins_slot[:, None], new_value[None, :], wval)
    # a full working set on insert is an overflow (cannot happen at sane
    # geometry: the path fetch alone frees plen*z slots)
    insert_dropped = do_insert & ~inserted

    # --- greedy deepest-first eviction ---------------------------------
    with device_phase("oram_evict"):
        valid = widx != SENTINEL
        depth = _common_prefix_depth(cfg, wleaf, leaf)  # int32[W]
        assign = jnp.full(valid.shape, -1, jnp.int32)  # path level, -1 = stash
        pos = jnp.zeros(valid.shape, jnp.int32)  # slot within the bucket
        placed = jnp.zeros(valid.shape, jnp.bool_)
        for level in range(cfg.height, -1, -1):
            eligible = valid & ~placed & (depth >= level)
            r = rank_of(eligible)
            chosen = eligible & (r < z)
            assign = jnp.where(chosen, level, assign)
            pos = jnp.where(chosen, r, pos)
            placed = placed | chosen

        # scatter placed entries into fresh path arrays (conflict-free:
        # each (level, pos) pair is chosen at most once)
        target = jnp.where(placed, assign * z + pos, plen * z)  # OOB = dropped
        new_pidx = jnp.full((plen * z,), SENTINEL, U32).at[target].set(widx, mode="drop")
        new_pval = jnp.zeros((plen * z, v), U32).at[target].set(wval, mode="drop")

    if recursive:
        new_pleaf = jnp.zeros((plen * z,), U32).at[target].set(wleaf, mode="drop")

    # --- compact the leftovers back into the stash ---------------------
    leftover = valid & ~placed
    srank = rank_of(leftover)
    starget = jnp.where(leftover, srank, cfg.stash_size)  # OOB = dropped
    stash_idx = jnp.full((cfg.stash_size,), SENTINEL, U32).at[starget].set(
        widx, mode="drop"
    )
    stash_val = jnp.zeros((cfg.stash_size, v), U32).at[starget].set(wval, mode="drop")
    stash_leaf = (
        jnp.zeros((cfg.stash_size,), U32).at[starget].set(wleaf, mode="drop")
        if recursive
        else state.stash_leaf
    )
    # == n_left - min(n_left, stash_size), interval-transparent form
    stash_dropped = jnp.maximum(
        jnp.sum(leftover.astype(jnp.int32)) - cfg.stash_size, 0
    )

    overflow = (
        state.overflow
        + stash_dropped.astype(U32)
        + insert_dropped.astype(U32)
    )

    # --- write the path back (write transcript ≡ read transcript) ------
    with device_phase("oram_writeback"):
        epochs_w = jnp.broadcast_to(state.epoch[None, :], (plen - kc, 2))
        new_rows = new_pval.reshape(plen, z * v)
        enc_pidx, enc_pval = cipher_rows(
            cfg,
            state.cipher_key,
            bot_b,
            epochs_w,
            new_pidx.reshape(plen, z)[kc:],
            new_rows[kc:],
        )
        nonces = (
            _path_scatter(state.nonces, bot_b, epochs_w, axis_name)
            if cfg.encrypted
            else state.nonces
        )
        if kc:
            # cached levels write back plaintext into the cache planes
            # (a single path's buckets are distinct → unique targets)
            cache_idx = state.cache_idx.at[top_slots].set(
                new_pidx[: kc * z], unique_indices=True
            )
            cache_val = state.cache_val.at[top_b].set(
                stored_rows(cfg, new_rows[:kc]), unique_indices=True
            )
        else:
            cache_idx, cache_val = state.cache_idx, state.cache_val
        cache_leaf = state.cache_leaf
        if recursive:
            enc_pleaf = leaf_plane_cipher(
                cfg, state.cipher_key, bot_b, epochs_w,
                new_pleaf.reshape(plen, z)[kc:],
            )
            tree_leaf = _path_scatter(
                state.tree_leaf.reshape(-1, z), bot_b, enc_pleaf, axis_name
            ).reshape(-1)
            if kc:
                cache_leaf = state.cache_leaf.at[top_slots].set(
                    new_pleaf[: kc * z], unique_indices=True
                )
        else:
            tree_leaf = state.tree_leaf
    new_state = OramState(
        tree_idx=_path_scatter(
            state.tree_idx.reshape(-1, z), bot_b, enc_pidx, axis_name
        ).reshape(-1),
        tree_val=_path_scatter(state.tree_val, bot_b, enc_pval, axis_name),
        cache_idx=cache_idx,
        cache_val=cache_val,
        cache_leaf=cache_leaf,
        tree_leaf=tree_leaf,
        stash_idx=stash_idx,
        stash_val=stash_val,
        stash_leaf=stash_leaf,
        posmap=posmap,
        overflow=overflow,
        nonces=nonces,
        cipher_key=state.cipher_key,
        epoch=epoch_next(state.epoch),
    )
    if recursive:
        leaf = jnp.stack([leaf, inner_leaf])
    return new_state, out, leaf


def oram_access_batch(
    cfg: OramConfig,
    state: OramState,
    idxs: jax.Array,  # u32[B]
    new_leaves: jax.Array,  # u32[B]
    operands,  # pytree with leading batch axis
    fn: Callable,
    axis_name: str | None = None,
    pm_leaves: jax.Array | None = None,  # u32[B] (recursive posmap only)
):
    """Sequentially-committed batch of accesses under one ``lax.scan``.

    Within-batch ordering is "commit in slot order" — the semantics this
    framework documents for batch hazards (two ops on one key in a round;
    SURVEY.md §7.6). Each scan iteration is itself a wide vector program,
    so the device pipelines the per-op work without host round-trips.

    Returns ``(state', outs, leaves)`` with outs/leaves batched; under a
    recursive posmap (``cfg.posmap`` set) ``pm_leaves`` supplies one
    fresh uniform internal leaf per access and ``leaves`` is u32[B, 2].
    """
    recursive = cfg.posmap is not None
    if recursive and pm_leaves is None:
        raise ValueError(
            "recursive posmap batch needs pm_leaves (fresh uniform "
            "internal leaves, one per access)"
        )

    def step(carry, xs):
        idx, new_leaf, pm_leaf, opnd = xs
        carry, out, leaf = oram_access(
            cfg, carry, idx, new_leaf, opnd, fn, axis_name, pm_leaf=pm_leaf
        )
        return carry, (out, leaf)

    pm = pm_leaves if recursive else jnp.zeros_like(new_leaves)
    state, (outs, leaves) = jax.lax.scan(
        step, state, (idxs, new_leaves, pm, operands)
    )
    return state, outs, leaves


def tree_cache_private_bytes(cfg: OramConfig) -> int:
    """Decrypted-resident bytes the tree-top cache pins for this tree
    (sizing helper for OPERATIONS.md §14 and bench.py tree_cache_ab):
    2^k−1 bucket rows of idx + val (+ leaf-metadata under a recursive
    posmap), all plaintext private state with the stash's standing."""
    z = cfg.bucket_slots
    leaf = z if cfg.posmap is not None else 0
    return cfg.cache_buckets * 4 * (z + cfg.stored_row_words + leaf)


def stash_occupancy(state: OramState) -> jax.Array:
    """Number of live stash entries (test/metrics helper)."""
    return jnp.sum(state.stash_idx != SENTINEL)


def tree_occupancy(state: OramState) -> jax.Array:
    """Number of live blocks in the tree (test/metrics helper)."""
    return jnp.sum(state.tree_idx != SENTINEL)
