"""Configuration for the grapevine-tpu engine.

The reference fixes its knobs as compile-time constants (record size,
62-message mailbox cap, reference README.md:78-80,137-139) plus CLI flags
(expiry period, reference README.md:90). Here everything lives in one
dataclass; the device-engine geometry (tree heights, bucket slots, stash
size, batch size) are the TPU analogs of "how much EPC the enclave maps".

Capacity story: the records store is a Path-ORAM bucket tree with
``2**records_height`` leaves and a dense block space of the same size; the
mailbox store is a keyed two-choice hash table (K mailboxes per bucket)
over its own Path-ORAM, run at a load where bucket overflow is negligible.
Maximum in-flight messages = ``max_messages`` (bounded by the free-block
list); maximum distinct recipients with mail = ``max_recipients`` (also
soft-bounded by table load; overflow reports TOO_MANY_RECIPIENTS).
"""

from __future__ import annotations

import dataclasses
import math
import os as _os

from .wire import constants as C


def on_tpu() -> bool:
    """True when the default JAX backend is a TPU (Mosaic compiles the
    Pallas kernels, the TPU-side ``auto`` knob defaults apply), False on
    the CPU (interpret mode, CPU defaults). Any other platform is an
    error: a backend-name surprise must not silently select interpret
    mode or a CPU default on what might be an accelerator."""
    import jax

    platform = jax.default_backend()
    if platform == "tpu":
        return True
    if platform == "cpu":
        return False
    raise RuntimeError(
        f"unsupported JAX platform {platform!r}: grapevine-tpu runs on "
        "'tpu' (compiled Mosaic kernels) or 'cpu' (tests, interpret mode)"
    )


def setup_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    Call once per process before the first compile (server CLI,
    bench.py, profile_tpu.py, chip_smoke.py, tools/chaos_run.py). A
    full-size round compiles in minutes, so whether a run is affordable
    is decided by whether it finds the previous run's programs.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it — nothing
    else is set here, the cache is placed from outside. Unset: the fixed
    path ``<checkout>/.jax_cache`` (git-ignored). The path is part of
    the cache key's environment, so it must not move between runs — no
    per-process temp dirs (tools/chaos_run.py's SIGKILLed children
    share it too). Checked on the installed jax 0.9.0: ``LRUCache.put``
    is a plain ``write_bytes``, not a rename, so a process killed
    mid-write can leave a torn entry — but entries are zstd frames, a
    torn one fails decompression on read, ``_cache_read`` turns that
    into a warning and a recompile, and nothing wrong is ever loaded
    (tried: truncating an entry gives "ZstdError ... did not decompress
    full frame", then the correct result). The torn file stays (``put``
    skips existing keys), so that one program recompiles per process
    until the file is deleted.
    """
    env = _os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = _os.path.join(
        _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
        ".jax_cache",
    )
    jax.config.update("jax_compilation_cache_dir", path)
    return path


@dataclasses.dataclass(frozen=True)
class GrapevineConfig:
    # --- semantic capacities -------------------------------------------
    #: max in-flight messages on the bus (reference README.md:75-76)
    max_messages: int = 1 << 14
    #: max distinct recipients with in-flight messages
    max_recipients: int = 1 << 12
    #: per-recipient in-flight cap (reference README.md:78-80)
    mailbox_cap: int = C.MAILBOX_CAP
    #: message expiry period in seconds; 0 disables (reference README.md:86-98)
    expiry_period: int = 0

    # --- device engine geometry ----------------------------------------
    #: Path-ORAM bucket capacity (Z); upstream mc-oblivious uses Z=4 with
    #: 4096B buckets of 1024B blocks (SURVEY.md §7.4)
    bucket_slots: int = 4
    #: fixed stash slots per ORAM (overflow is a sticky internal error)
    stash_size: int = 96
    #: client ops per jit'd access round; host pads with dummy ops
    batch_size: int = 8
    #: mailboxes per hash bucket (one bucket = one mailbox-ORAM block)
    mailbox_slots: int = 4
    #: within-batch commit schedule: "phase" = phase-major batched rounds
    #: (engine/round_step.py — the production path: one path fetch per
    #: ORAM round instead of one per op), "op" = op-major sequential
    #: commits (engine/step.py — the original reference-shaped engine).
    #: Identical semantics for single-op batches; batch-hazard semantics
    #: documented in round_step.py.
    commit: str = "phase"
    #: ChaCha rounds for at-rest bucket-tree encryption in HBM — the EPC
    #: analog (oblivious/bucket_cipher.py). 8 = ChaCha8 (default),
    #: 20 = RFC ChaCha20, 0 = plaintext trees.
    bucket_cipher_rounds: int = 8
    #: cipher implementation: "jnp" (XLA: the compiler splits the rounds
    #: over a dozen fusions and hands state words and keystream through
    #: HBM — the reference and the CPU path), "pallas" (one pass: the
    #: keystream made in VMEM on the row's own lane tiles and XORed
    #: where it is made, oblivious/pallas_cipher.py; interpret mode on
    #: the CPU, where tests hold it to "jnp"). Bit-identical ciphertext
    #: in both. None = by backend: "pallas" on a TPU, "jnp" on the CPU
    #: (PERF.md §5/§6, PR 40: the chip's A/B in backlog-1chip-r2p16).
    bucket_cipher_impl: str | None = None
    #: per-request signature scheme: "schnorrkel" (sr25519, byte-compatible
    #: with the reference's sign_schnorrkel clients — README.md:193-199,
    #: session/schnorrkel.py) or "rfc9496" (the same-shape plain Schnorr
    #: this repo shipped first, session/ristretto.py). Server and clients
    #: must agree.
    signature_scheme: str = "schnorrkel"

    def __post_init__(self):
        if self.commit not in ("phase", "op"):
            raise ValueError(
                f"commit must be 'phase' or 'op', got {self.commit!r}"
            )
        # 0 = plaintext; otherwise an even round count ≥ 8 (ChaCha rounds
        # come in column+diagonal pairs; odd values would silently floor,
        # and rounds < 8 have no security story — a 0-round "cipher"
        # exposes 2*key in every keystream block)
        r = self.bucket_cipher_rounds
        if r != 0 and (r < 8 or r % 2 != 0):
            raise ValueError(
                f"bucket_cipher_rounds must be 0 or an even value >= 8, got {r}"
            )
        if self.bucket_cipher_impl not in (None, "jnp", "pallas"):
            raise ValueError(
                f"bucket_cipher_impl must be None, 'jnp' or 'pallas', "
                f"got {self.bucket_cipher_impl!r}"
            )
        if self.signature_scheme not in ("schnorrkel", "rfc9496"):
            raise ValueError(
                f"signature_scheme must be 'schnorrkel' or 'rfc9496', got "
                f"{self.signature_scheme!r}"
            )
        if self.max_messages < 2 or self.max_messages & (self.max_messages - 1):
            raise ValueError("max_messages must be a power of two >= 2")
        if self.tree_density not in (1, 2, 4):
            raise ValueError(
                f"tree_density must be 1, 2, or 4, got {self.tree_density}"
            )
        if self.mailbox_choices not in (None, 1, 2):
            raise ValueError(
                f"mailbox_choices must be None, 1 or 2, got "
                f"{self.mailbox_choices}"
            )
        if self.commit == "op" and self.mailbox_choices == 2:
            raise ValueError(
                "commit='op' (the differential-oracle engine) supports "
                "only mailbox_choices=1"
            )
        if self.posmap_impl not in (None, "flat", "recursive"):
            raise ValueError(
                f"posmap_impl must be None, 'flat' or 'recursive', got "
                f"{self.posmap_impl!r}"
            )
        if self.commit == "op" and self.posmap_impl == "recursive":
            raise ValueError(
                "commit='op' (the differential-oracle engine) supports "
                "only posmap_impl='flat' — the recursive position map "
                "rides the phase-major batched round"
            )
        tc = self.tree_top_cache_levels
        if tc is not None and (not isinstance(tc, int) or tc < 0):
            raise ValueError(
                f"tree_top_cache_levels must be None (auto) or an int "
                f">= 0, got {tc!r}"
            )
        if self.pipeline_depth not in (None, 1, 2):
            raise ValueError(
                f"pipeline_depth must be None (auto), 1 or 2, got "
                f"{self.pipeline_depth!r}"
            )
        if self.commit == "op" and tc not in (None, 0):
            raise ValueError(
                "commit='op' (the differential-oracle engine) supports "
                "only tree_top_cache_levels=0 — the tree-top cache "
                "rides the phase-major batched round, and the op-major "
                "engine stays cache-free as the differential oracle"
            )
        sh = self.shards
        if not isinstance(sh, int) or sh < 1 or sh & (sh - 1):
            raise ValueError(
                f"shards must be a power-of-two int >= 1, got {sh!r} — "
                "the bucket trees shard as contiguous equal heap ranges "
                "(parallel/mesh.py)"
            )
        if self.commit == "op" and sh != 1:
            raise ValueError(
                "commit='op' (the differential-oracle engine) supports "
                "only shards=1 — the sharded step wraps "
                "the phase-major batched round (parallel/mesh.py "
                "make_sharded_step), and the op-major engine stays "
                "single-chip as the differential oracle"
            )
    #: position-map implementation for both ORAMs (oram/posmap.py):
    #: "flat" = the private u32[blocks+1] table in working memory —
    #: bit-for-bit the pre-PR-7 engine; "recursive" = the classic
    #: recursive construction (Path ORAM §"recursive construction",
    #: arXiv:1202.5150) one level deep — k ≈ sqrt(blocks) position
    #: entries packed per block of a smaller internal Path ORAM whose
    #: bucket tree lives in encrypted, shardable HBM, leaving only a
    #: blocks/k-entry table resident (the ≥2^30-record capacity path,
    #: ROADMAP item 5; geometry auto-derived from capacity, sizing
    #: table in OPERATIONS.md §13). Bit-identical responses and final
    #: payload-tree state either way (tests/test_posmap_ab.py); each
    #: outer round resolves ALL B positions through exactly B internal
    #: accesses, so the transcript's access count stays data-
    #: independent (CI-audited, tools/check_posmap_oblivious.py).
    #: None = auto: currently "flat" on every backend — the recursive
    #: map pays ~2× HBM path traffic per round for a ~k× smaller
    #: resident footprint, a trade that only *wins* once capacity
    #: exceeds private memory; flip per capacity (OPERATIONS.md §13)
    #: — not measured on the chip. Requires
    #: commit="phase" and power-of-two block spaces >= 8 on both trees.
    posmap_impl: str | None = None

    #: tree-top cache depth for every Path-ORAM bucket tree (records,
    #: mailbox, and — under posmap_impl="recursive" — the internal
    #: position trees; oram/path_oram.py). The top k levels (2^k−1
    #: buckets) are on EVERY root→leaf path, so they are promoted out of
    #: the per-access encrypted HBM gather/scatter into decrypted-
    #: resident cache planes with the stash's private standing: path
    #: fetch/write-back then touch only the bottom height+1−k levels of
    #: the big tree arrays and the per-access cipher work shrinks by the
    #: same fraction ("Optimizing Path ORAM for Cloud Storage
    #: Applications" measures the ~2-3× path-bandwidth cut; Palermo
    #: co-designs the same cache in hardware — ROADMAP item 1).
    #: Access-pattern-neutral by construction — the cached levels are
    #: touched by every access anyway, and the cache is read/written
    #: with constant-shape programs (CI-audited,
    #: tools/check_tree_cache_oblivious.py). Responses and logical state
    #: are bit-identical at every k (tests/test_tree_cache.py).
    #: 0 = off (bit-for-bit the uncached program); k is clamped to each
    #: tree's height (at least the leaf level stays in HBM); memory cost
    #: is (2^k−1)·bucket-row bytes per tree (OPERATIONS.md §14 sizing
    #: table). None = auto: 4 on the TPU AND on the CPU —
    #: the cache strictly removes gather/scatter/cipher rows rather than
    #: trading one algorithm for another; the k = 0 / k = 4 A/B is not
    #: measured on the chip. Requires commit="phase".
    tree_top_cache_levels: int | None = None

    #: round-pipeline depth: the number of dispatched-but-unresolved
    #: engine rounds a driver holds at rest (engine/batcher.py,
    #: server/scheduler.py; the scheduler's dispatch-then-settle order
    #: — the depth-1 legacy sequence — means depth+1 rounds are
    #: transiently in flight during each settle wait, so size device
    #: resp/transcript residency as depth+1 rounds). 1 = the serial pre-PR-10 program, bit for
    #: bit: a round fully settles (device wait + demux + delivery)
    #: before the next one's window would close behind it. 2 = the
    #: staged pipeline (ROADMAP item 2; Palermo's protocol/hardware
    #: pipelining, arXiv:2411.05400): while round k executes on the
    #: device, round k+1 is assembled and verified on the host and its
    #: journal frame is appended AND fsynced — the fsync overlaps
    #: device execution instead of serializing with it, so steady-state
    #: cadence approaches max(host, fsync, device) and p99 commit
    #: latency stops paying the fsync whenever a device round is in
    #: flight behind it. Durability ordering is unchanged: a round is
    #: journaled (and fsynced, per journal_fsync_every) strictly BEFORE
    #: it dispatches, and rounds dispatch in journal order, so replay
    #: order is journal order at every depth — never completion order
    #: (the chaos invariant; tools/chaos_run.py --pipeline-depth 2).
    #: Responses and final state are bit-identical at both depths
    #: (tests/test_pipeline.py). None = auto: 2 on the TPU, where with
    #: full rounds the host's ~42 ms a round (verify, dispatch, demux,
    #: settle) hide behind the device's 54-150 ms (PERF.md §5, the
    #: backlog cells), and 1 on the CPU: host-bound (bubble ratio ≈
    #: 0.0002) the second in-flight round has no device window to hide
    #: work behind. The depth bounds FULL rounds only. Part-empty
    #: rounds queued two deep cost an open-loop op 3.6 rounds for one
    #: round's work on the chip (`trickle-1chip`, 448 ops/s:
    #: ``rounds_ahead`` 2.0, ``round_ms`` 151 for 54.2 ms of device
    #: time, ``commit_p50_ms`` 196; ledger, PR 32), so since PR 33 the
    #: scheduler holds a short queue while a round is in flight
    #: (server/scheduler.py ``hold``): there the same cell reads
    #: ``rounds_ahead`` 0 and ``commit_p50_ms`` 134-139 (builder's
    #: chip runs, PR 33, PERF.md §5-6), and the rule gives way to the
    #: full depth by itself where a batch gathers within one serial
    #: period, at 20,000-24,000 ops/s offered in process.
    pipeline_depth: int | None = None

    #: bucket-tree shard count across the device mesh (parallel/mesh.py):
    #: 1 = single-chip (the default; no mesh machinery compiled), N > 1
    #: = both payload trees (+ nonce planes) shard as contiguous heap
    #: ranges over the first N devices, everything else replicated; the
    #: engine's round dispatches through make_sharded_step.
    #: Deliberately NOT part of EngineConfig and therefore NOT
    #: covered by the checkpoint/journal fingerprint: responses, final
    #: state, and the journal stream are bit-identical at every shard
    #: count (tests/test_parallel.py), so a journal written on one chip
    #: replays bit-identically on a mesh and vice versa — the same
    #: standing as pipeline_depth. Requires commit="phase", a
    #: power-of-two count that divides both trees' padded bucket counts,
    #: and at least that many JAX devices at engine construction.
    shards: int = 1

    #: hash choices per recipient in the mailbox table. 2 (default for
    #: the phase-major engine) = power-of-two-choices: a new recipient
    #: claims a slot in the emptier of two keyed-hash candidate buckets
    #: (occupancy read at round start; choice resolved obliviously —
    #: every op fetches BOTH candidate paths every time, so the
    #: transcript never reveals which bucket holds a recipient). 1 =
    #: the round-3 single-choice table (required by the op-major
    #: ``commit="op"`` differential-oracle engine, which keeps the
    #: simpler scheme). None = auto: 2 for phase commit, 1 for op.
    mailbox_choices: int | None = None

    #: per-slot load target; table buckets M = ceil(
    #: max_recipients / (mailbox_slots * load)). None = auto by choice
    #: count: 0.5 under two-choice, 0.125 under single-choice.
    #:
    #: The mailbox tier approximates the reference's bucketed-cuckoo map
    #: (README.md:78-80) with a RELOCATION-FREE two-choice table — no
    #: eviction chains on device. The quantified bargain
    #: (tests/test_mailbox_load.py):
    #:
    #: - **Early failures**: a recipient whose candidate bucket(s) are
    #:   full gets TOO_MANY_RECIPIENTS before the aggregate cap. At
    #:   K=4: single-choice load 0.125 gives Poisson(λ=0.5) tails —
    #:   ≈1.4 expected early failures at M=8192, fill 100%. Two-choice
    #:   at load 0.5 needs BOTH candidates full: simulated (20 trials,
    #:   M=4096) ≈0 failures through fill 75% and ≈0.3 expected at
    #:   fill 100% — strictly fewer failures than single-choice at
    #:   1/4 the bucket count. The spec permits TOO_MANY_RECIPIENTS at
    #:   any recipient count; the oracle models only the aggregate cap,
    #:   so randomized oracle-equality suites run at low fill.
    #: - **Memory**: mailbox-tier HBM per recipient is 1/load × the
    #:   mailbox size — 2× under two-choice vs the reference cuckoo's
    #:   ~1.2×, and vs 8× for round-3's single-choice table.
    #: - **Bandwidth**: every op pays a second mailbox path fetch in
    #:   rounds A and C (both candidates touched unconditionally). The
    #:   mailbox tree is the small tier, so this trades ~0.3 ms/round
    #:   of cheap bandwidth for 4× less mailbox HBM.
    mailbox_load: float | None = None

    #: blocks per tree leaf for both ORAMs. The classic Path ORAM shape
    #: is 1 (total slots = 8× blocks — 12.5% utilization); 2 halves tree
    #: HBM per block and shortens every path by one level at a still-
    #: conservative 25% utilization; 4 (50%) is the aggressive setting —
    #: stash occupancy under density is exercised in tests/test_oram.py.
    tree_density: int = 2

    @property
    def records_height(self) -> int:
        """Tree height of the records ORAM: leaves = blocks / density."""
        return max(
            1,
            math.ceil(math.log2(self.max_messages))
            - (self.tree_density.bit_length() - 1),
        )

    @property
    def records_leaves(self) -> int:
        return 1 << self.records_height

    @property
    def resolved_mailbox_choices(self) -> int:
        """1 or 2: the explicit knob, else 2 for phase / 1 for op."""
        if self.mailbox_choices is not None:
            return self.mailbox_choices
        return 2 if self.commit == "phase" else 1

    @property
    def resolved_mailbox_load(self) -> float:
        """Load target: the explicit knob, else by choice count."""
        if self.mailbox_load is not None:
            return self.mailbox_load
        return 0.5 if self.resolved_mailbox_choices == 2 else 0.125

    @property
    def mailbox_table_buckets(self) -> int:
        """Hash table size (power of two) for the mailbox map.

        Floor of 16: keeps the mailbox bucket tree shardable over an
        8-chip mesh at toy capacities and gives the two-choice hash a
        meaningful candidate space; the cost at tiny configs is a few
        KiB."""
        want = max(
            16,
            math.ceil(
                self.max_recipients
                / (self.mailbox_slots * self.resolved_mailbox_load)
            ),
        )
        return 1 << max(1, math.ceil(math.log2(want)))

    @property
    def mailbox_height(self) -> int:
        """Tree height of the mailbox ORAM: block space = hash-table buckets."""
        return max(
            1,
            math.ceil(math.log2(self.mailbox_table_buckets))
            - (self.tree_density.bit_length() - 1),
        )

    @property
    def mailbox_leaves(self) -> int:
        return 1 << self.mailbox_height


@dataclasses.dataclass(frozen=True)
class DurabilityConfig:
    """Crash-safety knobs (engine/checkpoint.py, engine/journal.py).

    With a ``state_dir`` set, the engine journals every admitted batch
    (sealed, fsync-batched) before dispatching it and periodically dumps
    a sealed whole-``EngineState`` checkpoint; restart = load the last
    checkpoint + deterministically replay the journal tail. Whole-state
    dumps and whole-batch journal records are access-pattern-free by
    construction — they are written for every round regardless of what
    the ops inside are, so durability adds no obliviousness leak
    (OPERATIONS.md §11).
    """

    #: directory holding checkpoints, journal segments, and (by default)
    #: the auto-generated root seal key
    state_dir: str
    #: rounds+sweeps between sealed checkpoints (RTO knob: recovery
    #: replays at most this many journal records)
    checkpoint_every_rounds: int = 64
    #: journal records per fsync. 1 (default) = every record is durable
    #: before its round dispatches (RPO 0 for acknowledged ops); larger
    #: values amortize the fsync at the cost of losing up to N-1
    #: acknowledged rounds on a *machine* crash (a process crash alone
    #: loses nothing — the page cache survives)
    journal_fsync_every: int = 1
    #: 32-byte root seal key file; None = ``<state_dir>/root.key``,
    #: auto-generated 0600 on first start. Point it at a separately
    #: mounted secret in production — a sealed checkpoint next to its
    #: key is integrity-protected but not confidential (OPERATIONS.md
    #: §11 key management)
    seal_key_file: str | None = None

    def __post_init__(self):
        if not self.state_dir:
            raise ValueError("durability requires a state_dir")
        if self.checkpoint_every_rounds < 1:
            raise ValueError("checkpoint_every_rounds must be >= 1")
        if self.journal_fsync_every < 1:
            raise ValueError("journal_fsync_every must be >= 1")

    @classmethod
    def coerce(cls, value) -> "DurabilityConfig | None":
        """``value`` as a DurabilityConfig: one as it is, None as None,
        and a mapping of the fields (what a JSON configuration file
        holds) built, its ``state_dir`` resolved against the working
        directory as the CLI's ``--state-dir`` is."""
        if value is None or isinstance(value, cls):
            return value
        fields = dict(value)
        if fields.get("state_dir"):
            fields["state_dir"] = _os.path.abspath(fields["state_dir"])
        return cls(**fields)


DEFAULT_CONFIG = GrapevineConfig()
