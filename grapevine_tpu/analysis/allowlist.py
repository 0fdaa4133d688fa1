"""The reviewed allowlist: every secret-indexed sink the engine is
*allowed* to contain, each with its one-line leak argument.

Contract (enforced by tools/check_oblivious.py):

- any taint-flagged sink NOT listed here fails the audit — a new
  secret-derived gather/scatter/predicate cannot land without a review
  adding its entry and its argument;
- any entry never *reached* in the swept knob matrix fails the audit —
  dead entries rot into blanket permissions and are exactly how a later
  leak hides behind an old review.

The arguments fall into four standings, all rooted in the threat model
(oram/path_oram.py): the public transcript is the HBM bucket-tree
access sequence; the stash, position map, tree-top cache, and per-round
working set are EPC-analog **private working memory** (ciphertext at
rest IS public — which is why the cipher key is a taint anchor).

1. *one-time uniform paths*: tree accesses indexed by leaves that are
   consumed exactly once then remapped to fresh uniform draws — the
   Path-ORAM invariant; the transcript is i.i.d. uniform whatever the
   ops were.
2. *private working memory*: accesses into stash/posmap/cache/working
   rows; the round executes a fixed schedule of them (the census gates
   pin this), only their *contents* vary.
3. *oblivious permutation plumbing*: sort/rank/segmented-scan data
   movement over fixed [B]/[W] arrays — every row moves exactly once
   per pass; the permutation's value is secret, its shape is not.
4. *fixed full sweeps*: iota-scheduled walks that touch every row
   regardless of the data (the expiry sweep; the level-dense top of
   ``oram_round``, whose heap range is a constant of the shapes).
"""

from __future__ import annotations

from .oblint import AllowEntry

_A = AllowEntry

#: one ORAM access round (oram/round.py and everything under it)
_ORAM_CORE = (
    _A("gather", "oram/path_oram.py:_path_gather",
       "path fetch indexed by one-time leaves: each position is read "
       "once then remapped, so every fetched path is an independent "
       "uniform draw (Path-ORAM invariant); the level-dense top of "
       "oram_round rides the same gather as a fixed sweep — the heap "
       "range [2^k-1, 2^Ld-1) at constant indices, every round"),
    _A("scatter", "oram/path_oram.py:_path_scatter",
       "write-back of exactly the fetched rows — the fixed dense heap "
       "range (all-true owner mask) and the fetched paths below it, "
       "owner-masked — the write transcript is identical to the read "
       "transcript. On a TPU a plane of wide rows is handed the SAME "
       "target vector as one DMA a row (oblivious/pallas_place.py): "
       "rows written, not a plane walked; a dropped target starts no "
       "copy, and which targets drop is the owner mask and the chip's "
       "index, functions of the public leaves alone"),
    _A("gather", "oram/path_oram.py:working_leaves",
       "leaf lookup in the flat position table, private working memory "
       "(one fixed [W]-shaped gather per round)"),
    _A("gather", "oram/round.py:oram_round",
       "private working-set reads: block->row map and initial-value "
       "rows — stash-standing memory on a fixed per-round schedule "
       "(the cache-top planes join the working set whole, no gather)"),
    _A("scatter", "oram/round.py:oram_round",
       "commits into private planes (working rows, eviction slots, "
       "stash recompaction): fixed shapes, unique in-bounds targets; "
       "the cache-top planes leave as whole slices of the eviction "
       "output (an elementwise select keeps a bucket no path met)"),
    _A("scatter", "oram/round.py:_bucket_owner_map",
       "owner election: one scatter-min over exactly the per-path "
       "heap slots of the round — B*(path_len-Le) under the levels "
       "the batch covers — into a private map of fixed shape, "
       "whatever the leaves; covered buckets are never looked up in "
       "it"),
    _A("gather", "oram/round.py:_assign_evictions",
       "eviction assignment: the bucket-map lookup of a per-path level "
       "and nothing else — one fixed [W]-shaped read of the private "
       "owner map per level under the covered ones (bucket -> output "
       "row: covered buckets are their own id without a lookup, deeper "
       "buckets their owner copy); ranks come from scans and the "
       "sorted keys from the sort's own payload, never from a gather"),
    _A("scatter", "oram/round.py:_assign_evictions",
       "eviction assignment: the one inverse-permutation scatter back "
       "to working-set order — every row of the fixed working set "
       "written exactly once"),
)

#: position-map resolution (flat table and recursive internal ORAM)
_POSMAP = (
    _A("gather", "oram/posmap.py:lookup_remap_round",
       "flat position-map read: the table is private working memory; "
       "exactly one [B]-gather per round"),
    _A("scatter", "oram/posmap.py:lookup_remap_round",
       "flat position-map remap write: same private table, one "
       "[B]-scatter per round, OOB-dropped for non-winners"),
    _A("gather", "oram/posmap.py:apply_pm",
       "recursive map entry extract/merge inside the internal round's "
       "private working set (fixed per-round schedule)"),
    _A("scatter", "oram/posmap.py:apply_pm",
       "recursive map entry writes onto committed internal rows — "
       "private working set, unique in-bounds targets"),
)

#: the admission walk's grouping sort
_SORTS = (
    _A("gather", "oblivious/segmented.py:group_sort",
       "bounded-key group sort: permutation gathers over fixed [B]"),
)

#: slot-order semantics + admission (engine/vphases.py): all of it runs
#: over per-op [B] working rows — private memory with a per-round
#: schedule that is a constant of the geometry (the quota-admission
#: *aggregate* branch is the one documented exception, and it selects
#: between two always-executed programs, never skips one)
_VPHASES = (
    _A("gather", "engine/vphases.py:_admission_fast",
       "quota-decoupled admission: rank/slot gathers over [B] counters "
       "in private working memory"),
    _A("gather", "engine/vphases.py:apply_batch",
       "slot-order chain resolution: same-key row gathers over the "
       "fixed [B] working set"),
    _A("scatter", "engine/vphases.py:apply_batch",
       "slot-order chain commits: [B]-row scatters into private "
       "working rows, unique in-bounds targets"),
    _A("scatter", "engine/vphases.py:step",
       "exact-admission scan body: per-op counter updates, private [B] "
       "state, fixed trip count"),
    _A("dynamic_slice", "engine/vphases.py:step",
       "exact-admission scan body: the scan's own per-op row slice — "
       "trip count and slice shape are constants of B"),
)

#: engine round glue + expiry sweep
_ENGINE = (
    _A("scatter", "engine/round_step.py:engine_round_step",
       "freed-block push: rank-compaction scatter into the private "
       "freelist — at most B unique in-bounds targets, fixed shape"),
    _A("scatter", "engine/expiry.py:expiry_sweep",
       "sweep bookkeeping (freelist rebuild, recipient release): "
       "rank-compaction scatters into private tables after an "
       "iota-scheduled full-tree walk"),
    _A("scatter", "engine/expiry.py:rec_body",
       "per-chunk liveness marking: presence bits scattered by private "
       "block ids into a private [max_messages] table; every tree row "
       "is visited on the fixed chunk schedule"),
)

#: the one reviewed list the driver sweeps (tools/check_oblivious.py)
ENGINE_ALLOWLIST: tuple = _ORAM_CORE + _POSMAP + _SORTS + _VPHASES + _ENGINE


def entries_by_key() -> dict:
    return {e.key: e for e in ENGINE_ALLOWLIST}


#: ----------------------------------------------------------------------
#: Rangelint's reviewed allowlist (analysis/rangelint.py; swept by
#: tools/check_ranges.py with the same dead-entry rule as the taint
#: list): every *intentionally* mod-2^32 operation in the compiled
#: round, each with its one-line range argument. The shape of every
#: argument is the same: the wrap is the operation's DEFINITION (a
#: cipher/mixer round, a two-lane carry), not an accident of geometry —
#: the pair/primitive downstream restores or never needed the
#: mathematical value. Anything wrapping outside these sites fails the
#: audit.
RANGE_ALLOWLIST: tuple = (
    # ChaCha (oblivious/bucket_cipher.py): ARX is arithmetic mod 2^32
    # by RFC 7539 — the keystream is DEFINED over the wrapped lanes
    _A("add", "oblivious/bucket_cipher.py:_qr",
       "ChaCha quarter-round addition is mod-2^32 by cipher definition"),
    _A("shift_left", "oblivious/bucket_cipher.py:_rotl",
       "rotate-left: the bits shifted past 32 re-enter via the OR'd "
       "logical right shift — no information leaves the lane"),
    _A("add", "oblivious/bucket_cipher.py:chacha_words",
       "the state+init feedforward of the ChaCha block function, "
       "mod-2^32 by RFC 7539"),
    _A("add", "oblivious/bucket_cipher.py:epoch_next",
       "u64 write-epoch as (lo, hi) u32 lanes: the lo lane wraps by "
       "design and the explicit carry feeds hi — the PAIR is the "
       "counter, 64-bit and unwrappable in any feasible lifetime"),
    # u64 two-lane helpers (oblivious/primitives.py)
    _A("add", "oblivious/primitives.py:u64_add_u32",
       "u64 carry arithmetic in u32 lanes: lo wraps mod 2^32, the "
       "comparison-derived carry moves the overflow into hi"),
    _A("sub", "oblivious/primitives.py:u64_sub",
       "u64 borrow arithmetic in u32 lanes: lo wraps mod 2^32, the "
       "comparison-derived borrow moves the underflow into hi"),
    # keyed mixers: mb_bucket_hash (engine/state.py) and the Feistel
    # PRP round function (oblivious/prp.py) — murmur-style ARX whose
    # output is masked to the table/domain width at the call site
    _A("mul", "engine/state.py:mb_bucket_hash",
       "keyed bucket-hash mixing multiplies are mod-2^32 by design; "
       "the result is masked to the (power-of-two) table width"),
    _A("add", "engine/state.py:mb_bucket_hash",
       "keyed bucket-hash mixing adds are mod-2^32 by design; the "
       "result is masked to the (power-of-two) table width"),
    _A("shift_left", "engine/state.py:mb_bucket_hash",
       "bucket-hash rotates: dropped high bits re-enter via the OR'd "
       "right shift"),
    _A("mul", "oblivious/prp.py:_f",
       "Feistel round-function multiplies are mod-2^32 by design; the "
       "half is masked to its domain width after each round"),
    _A("shift_left", "oblivious/prp.py:_f",
       "Feistel round-function rotate: dropped high bits re-enter via "
       "the OR'd right shift"),
    # invariant-backed sites: the wrap/blowup is impossible by a
    # reviewed program invariant an oracle-equality suite pins, which
    # a non-relational interval domain cannot express
    _A("sub", "engine/round_step.py:engine_round_step",
       "free_top - n_allocs: phase-A admission never allocates more "
       "blocks than the freelist holds (quota invariant, oracle-"
       "pinned); the adjacent min re-bounds the result for downstream"),
    _A("reduce_sum", "engine/vphases.py:_oldest_first",
       "masked one-hot row select (recipient-key slot match, at most "
       "one key matches per bucket — mailbox uniqueness invariant): "
       "the sum IS the selected slot's entries, never an accumulation"),
    _A("reduce_sum", "engine/vphases.py:_pth_entry",
       "position-equality one-hot select over the cap axis: exactly "
       "one position equals the clipped p, so the masked sum is that "
       "entry"),
    _A("reduce_sum", "engine/vphases.py:select_by_rank",
       "rank-equality one-hot select: at most one lane of a group has "
       "rank q, so the masked sum is a private row select"),
    _A("add", "oblivious/primitives.py:partition_rank",
       "counting-rank recombination: zeros-rank + ones-rank of one "
       "stable partition is a permutation of [0, B) (sums below B "
       "pointwise, 2B only in interval arithmetic); the adjacent clip "
       "re-bounds the lane for downstream"),
    # owner-masked sharded write-back (parallel/mesh.py composition;
    # ISSUE 18): each chip rebases global heap rows into its local
    # shard range before the drop-mode scatter
    _A("sub", "oram/path_oram.py:_path_scatter",
       "path_b - axis_index*n_local rebase: non-owned lanes wrap mod "
       "2^32 by construction and the owner mask routes exactly those "
       "lanes to the out-of-range drop sentinel — a wrapped value is "
       "never a landing address, of the scatter or of the placement "
       "kernel's DMAs, which skip a target past the shard "
       "(sharded==single-chip bit-equality, tests/test_parallel.py, "
       "tests/test_pallas_place.py)"),
    _A("convert_element_type", "oram/path_oram.py:_path_scatter",
       "drop-mode scatter target cast u32->int32: owned lanes are "
       "< n_local (fits, at every certified geometry) by the owner "
       "mask the interval domain cannot relate; non-owned lanes carry "
       "the wrapped rebase and drop out of bounds — write-drop is the "
       "documented masking idiom, so the cast only ever narrows the "
       "drop sentinel (the placement kernel reads the same int32 "
       "targets from SMEM and starts a copy only below n_local)"),
)
