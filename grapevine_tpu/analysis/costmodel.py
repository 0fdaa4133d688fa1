"""Static round-cost model: two independent derivations of the compiled
round's resource footprint, required to agree bit-exactly.

The CI censuses (tools/check_tree_cache_oblivious.py) already derive the
round's HBM row traffic from the traced jaxpr — and then throw it away.
This module keeps it: the same numbers become a :class:`CostLedger` —
per-phase HBM bytes (gather/scatter rows × row bytes), cipher rows, sort
key-volume, scatter elements, and the steady-state round
— computed TWICE, from two sources that share no code path:

1. **Analytic** (:func:`oram_round_rows` / :func:`engine_round_rows` /
   :func:`expiry_sweep_rows`): a pure function of geometry × knobs
   (``vphases/sort/posmap/cache-k``), written from the round's
   documented schedule — the fetch moves each of the top ``Ld`` levels
   once and ``B·(path_len−Ld)`` per-path rows below them per HBM plane
   with the cache planes passed whole, the recursive leaf plane
   re-gathers the nonce plane, write-back mirrors the fetch, and the
   expiry sweep streams every tree plane through its chunked scan
   exactly once.
2. **Traced** (:func:`traced_access_rows` / :func:`traced_scan_rows`):
   an interpreter over the shared :mod:`.jaxpr_walk` equation stream —
   the identical accounting the obliviousness censuses gate on.

:func:`cross_validate_round` (and friends) require the two to agree
**bit-exactly per operand shape class**. Shape classes, not plane names:
``tree_idx`` and ``tree_leaf`` share the ``[n, Z]`` operand shape, and a
recursive position map's internal cache planes share the outer cache
planes' shapes, so name-level attribution double-counts where the
censuses only bound per-op rows — aggregating both derivations over
``(shape, divisor)`` classes makes the comparison exact by construction.

Seeded undercount mutants (:func:`run_cost_mutants`, reported through
the shared :func:`.mutants.control_failures` runner) corrupt the
analytic side one defect at a time — a dropped plane, a halved fetch,
a forgotten second nonce gather, a missed mailbox double-round — and
every one must trip :class:`CostModelMismatch`, proving the checker is
alive (the ISSUE-12/14 positive-control discipline).

Consumers: obs/costmon.py exports the ledger as ``grapevine_cost_*``
gauges plus the roofline-residual pairing against the tracer's device
spans; bench.py grades each A/B config's measured winner against
:func:`ab_verdict`; tools/check_cost_model.py is the tier-1 gate and
the trajectory grader; the achieved-bandwidth constants are not
measured on the chip (obs/costmon.py carries the published peak).
"""

from __future__ import annotations

import dataclasses

from .jaxpr_walk import plane_rows, walk_eqns

#: u32 word size — every HBM plane in the engine is u32-lane
WORD_BYTES = 4

#: phase labels the ledger (and the grapevine_cost_* gauges) aggregate
#: over — public schedule structure, never data
COST_PHASES = ("fetch", "writeback", "sweep")


class CostModelMismatch(AssertionError):
    """The analytic model and the traced census disagree.

    ``kind`` is the defect class (``gather-undercount`` /
    ``scatter-undercount`` / ``gather-overcount`` /
    ``scatter-overcount`` / ``arithmetic``) — the mutant controls match
    on it, exactly like the oblint/rangelint finding kinds."""

    def __init__(self, msg: str, kind: str):
        super().__init__(msg)
        self.kind = kind


@dataclasses.dataclass(frozen=True)
class PlaneRows:
    """One plane's predicted traffic for one traced program.

    ``hbm`` marks planes resident in device HBM (tree/nonce planes);
    the dense ``cache_*`` planes are private working state (the
    stash's standing) — their rows participate in the bit-exact
    cross-validation but are excluded from the ledger's HBM bytes."""

    shape: tuple  # operand shape the trace attributes on
    divisor: int  # flat slot planes report slots/divisor (jaxpr_walk)
    row_words: int  # u32 words per accounted row
    gather_rows: int
    scatter_rows: int
    hbm: bool = True

    def scaled(self, g_mult: int, s_mult: int | None = None) -> "PlaneRows":
        s_mult = g_mult if s_mult is None else s_mult
        return dataclasses.replace(
            self,
            gather_rows=self.gather_rows * g_mult,
            scatter_rows=self.scatter_rows * s_mult,
        )


# -- analytic derivation: rows as a pure function of geometry × knobs ---


def oram_planes(cfg, prefix: str = "") -> dict:
    """Every HBM plane one ``oram_round`` at geometry
    ``cfg`` can touch, in the shared ``plane_rows`` declaration format
    (name -> (shape, divisor)) — the tree-cache census's declarations
    plus the nonce plane's recursive alias and the internal posmap
    tree's planes (prefixed ``pm_``)."""
    z, sw = cfg.bucket_slots, cfg.stored_row_words
    n = cfg.n_buckets_padded
    cb = cfg.cache_buckets
    planes = {
        f"{prefix}tree_idx": ((n, z), 1),
        f"{prefix}tree_val": ((n, *cfg.stored_row_shape), 1),
        f"{prefix}nonces": ((n, 2), 1),
    }
    if cfg.posmap is not None:
        planes[f"{prefix}tree_leaf"] = ((n, z), 1)
    if cb:
        planes[f"{prefix}cache_idx"] = ((cb * z,), z)
        planes[f"{prefix}cache_val"] = ((cb, sw), 1)
        if cfg.posmap is not None:
            planes[f"{prefix}cache_leaf"] = ((cb * z,), z)
    if cfg.posmap is not None:
        from ..oram.posmap import inner_oram_config

        planes.update(oram_planes(inner_oram_config(cfg.posmap),
                                  prefix=f"{prefix}pm_"))
    return planes


def _round_dense_levels(cfg, b: int) -> int:
    """Top levels one ``oram_round`` of ``b`` paths moves whole:
    every level L with 2^L <= b, never fewer than the cached levels nor
    more than the tree has. Written from the documented schedule, not
    read off ``OramConfig.dense_levels`` — the trace is the referee."""
    ld = 0
    while (1 << ld) <= b:
        ld += 1
    return min(max(ld, cfg.top_cache_levels), cfg.path_len)


def _round_hbm_rows(cfg, b: int) -> int:
    """Bucket rows one ``oram_round`` moves per HBM plane and
    direction: the dense heap range below the cache plus the per-path
    rows of the levels under it."""
    k = cfg.top_cache_levels
    ld = _round_dense_levels(cfg, b)
    return ((1 << ld) - (1 << k)) + b * (cfg.path_len - ld)


def oram_round_rows(cfg, b: int, prefix: str = "") -> dict:
    """Predicted rows per plane for ONE ``oram_round(cfg, ·)`` with a
    batch of ``b`` indices.

    The schedule being priced (oram/round.py):

    - the round is level-dense above, per-path below: the top
      ``Ld = clamp(floor(log2 b) + 1, k, path_len)`` levels are each
      moved whole, once (`_round_hbm_rows`), so a bottom HBM plane
      (idx, val, nonces; + the leaf plane under a recursive map, which
      re-gathers the nonce plane for its own keystream — the second
      nonce gather) gathers ``R = (2^Ld − 2^k) + b·(path_len − Ld)``
      rows, and the cache planes join and leave the working set whole:
      no gather or scatter op ever names them (their rows are 0);
    - write-back scatters the same row counts back (nonces only
      when the at-rest cipher is on — plaintext trees commit no epoch);
    - a recursive position map resolves the batch through exactly one
      internal round of the same ``b`` (oram/posmap.py), composed here
      under the ``pm_`` prefix.
    """
    z, sw = cfg.bucket_slots, cfg.stored_row_words
    n = cfg.n_buckets_padded
    cb = cfg.cache_buckets
    recursive = cfg.posmap is not None
    R = _round_hbm_rows(cfg, b)

    rows = {
        f"{prefix}tree_idx": PlaneRows((n, z), 1, z, R, R),
        f"{prefix}tree_val": PlaneRows(
            (n, *cfg.stored_row_shape), 1, sw, R, R),
        # the fetch always gathers the nonce plane (the keystream input
        # precedes the encrypted? branch); the epoch commit scatter only
        # exists under the cipher. Recursive leaf decrypt re-gathers it.
        f"{prefix}nonces": PlaneRows(
            (n, 2), 1, 2, R * (2 if recursive else 1),
            R if cfg.encrypted else 0,
        ),
    }
    if recursive:
        rows[f"{prefix}tree_leaf"] = PlaneRows((n, z), 1, z, R, R)
    if cb:
        rows[f"{prefix}cache_idx"] = PlaneRows(
            (cb * z,), z, z, 0, 0, hbm=False
        )
        rows[f"{prefix}cache_val"] = PlaneRows(
            (cb, sw), 1, sw, 0, 0, hbm=False
        )
        if recursive:
            rows[f"{prefix}cache_leaf"] = PlaneRows(
                (cb * z,), z, z, 0, 0, hbm=False
            )
    if recursive:
        from ..oram.posmap import inner_oram_config

        rows.update(oram_round_rows(
            inner_oram_config(cfg.posmap), b, prefix=f"{prefix}pm_"
        ))
    return rows


def _sharded_plane(name: str) -> bool:
    """True for planes the mesh shards along the bucket axis: the outer
    tree/nonce planes of either engine tree. Inner posmap trees
    (``pm_``) and the tree-top cache planes replicate on every chip
    (parallel/mesh._oram_specs — the ROADMAP item 1/3 composition point
    keeps the internal map whole), so their scatters land in full per
    chip while the outer trees' owner-masked scatters partition."""
    if "pm_" in name:
        return False
    base = (name.split("_", 1)[1]
            if name.startswith(("rec_", "mb_")) else name)
    return base.startswith(("tree_", "nonces"))


def engine_planes(ecfg) -> dict:
    """Both trees' plane declarations for one engine round."""
    return {**oram_planes(ecfg.rec, "rec_"),
            **oram_planes(ecfg.mb, "mb_")}


def engine_round_rows(ecfg) -> dict:
    """One engine round = mailbox round A (``B·D`` fetches) + records
    round B (``B``) + mailbox round C (``B·D``) — the round_step.py
    composition, so the mailbox tree's per-round traffic is exactly
    twice its per-``oram_round`` traffic."""
    b, d = ecfg.batch_size, ecfg.mb_choices
    rows = {
        name: pr.scaled(1)
        for name, pr in oram_round_rows(ecfg.rec, b, "rec_").items()
    }
    for name, pr in oram_round_rows(ecfg.mb, b * d, "mb_").items():
        rows[name] = pr.scaled(2)
    return rows


# -- analytic derivation: the expiry sweep's chunked full-tree pass -----


def sweep_chunk_planes(cfg, prefix: str = "") -> dict:
    """The planes one tree's expiry sweep streams through its
    ``lax.scan`` (engine/expiry.py ``_chunked_tree_sweep``): plane name
    -> (shape as the scan holds it, rows per full pass). The scan
    carries the idx / val (and recursive leaf) planes whole, cutting
    each chunk out and writing it back over itself, and reads the nonce
    plane as a constant — whole-plane passes, not gathers, so the
    traced check reduces scan operands (:func:`traced_scan_rows`)
    instead of access primitives."""
    z, sw = cfg.bucket_slots, cfg.stored_row_words
    n = cfg.n_buckets_padded
    planes = {
        f"{prefix}tree_idx": ((n * z,), n),
        f"{prefix}tree_val": ((n, *cfg.stored_row_shape), n),
        f"{prefix}nonces": ((n, 2), n),
    }
    if cfg.posmap is not None and cfg.encrypted:
        planes[f"{prefix}tree_leaf"] = ((n * z,), n)
    return planes


def expiry_sweep_rows(ecfg) -> dict:
    """Predicted full-pass rows per tree plane for one expiry sweep:
    every chunked plane is read once and the idx/val (and recursive
    leaf) planes are written once — ``n_buckets_padded`` rows each.
    The nonce plane is re-keyed by a broadcast store outside the scan
    (counted in the ledger's sweep bytes, not in the scan check)."""
    out = {}
    for prefix, cfg in (("rec_", ecfg.rec), ("mb_", ecfg.mb)):
        n = cfg.n_buckets_padded
        z, sw = cfg.bucket_slots, cfg.stored_row_words
        out[f"{prefix}tree_idx"] = PlaneRows((n, z), 1, z, n, n)
        out[f"{prefix}tree_val"] = PlaneRows(
            (n, *cfg.stored_row_shape), 1, sw, n, n)
        out[f"{prefix}nonces"] = PlaneRows((n, 2), 1, 2, n, n)
        if cfg.posmap is not None and cfg.encrypted:
            out[f"{prefix}tree_leaf"] = PlaneRows((n, z), 1, z, n, n)
    return out


# -- traced derivation: the jaxpr_walk interpreter ----------------------


def _shape_classes(planes: dict) -> dict:
    """Collapse plane declarations to unique (shape, divisor) classes —
    the granularity at which trace attribution is exact (tree_idx and
    tree_leaf share ``[n, Z]``; an internal posmap's cache planes share
    the outer cache shapes)."""
    uniq = {}
    for _, (shape, div) in planes.items():
        uniq[(tuple(shape), int(div))] = (tuple(shape), int(div))
    return {f"{s}/{d}": (s, d) for (s, d) in uniq.values()}


def traced_access_rows(jaxpr, planes: dict) -> dict:
    """Derivation #2: total gather/scatter rows per shape class from the
    traced program, via the shared census accounting
    (:func:`.jaxpr_walk.plane_rows`). Returns
    ``{(shape, divisor): (gather_rows, scatter_rows)}``."""
    classes = _shape_classes(planes)
    moved = plane_rows(jaxpr, classes)
    out = {}
    for cname, (shape, div) in classes.items():
        g = sum(r for op, r in moved[cname] if op == "gather")
        s = sum(r for op, r in moved[cname] if op != "gather")
        out[(shape, div)] = (g, s)
    return out


def predicted_access_rows(rows: dict) -> dict:
    """The analytic side of the same aggregation: per shape class,
    summed over the planes that share it."""
    out: dict = {}
    for _, pr in rows.items():
        key = (tuple(pr.shape), int(pr.divisor))
        g, s = out.get(key, (0, 0))
        out[key] = (g + pr.gather_rows, s + pr.scatter_rows)
    return out


def traced_scan_rows(jaxpr, chunk_planes: dict) -> dict:
    """Sweep derivation #2: rows streamed per plane-shape class through
    ``lax.scan`` equations — a scan operand (read) or output (write)
    whose aval matches a declared plane shape accounts one full pass of
    that many rows. Returns ``{plane_shape: (read_rows, write_rows)}``."""
    classes = {}
    for _, (chunk_shape, pass_rows) in chunk_planes.items():
        classes[tuple(chunk_shape)] = int(pass_rows)
    out = {shape: [0, 0] for shape in classes}

    def plane_shape(var):
        # a plane is u32 words: the records pass also carries a bool
        # per message id, which a small tree's slot plane can match in
        # length
        aval = var.aval
        shape = tuple(getattr(aval, "shape", ()))
        if shape in classes and str(aval.dtype) == "uint32":
            return shape
        return None

    # a kernel's own loops run over blocks in VMEM, and a toy plane can
    # be one block
    for eqn in walk_eqns(jaxpr, into_kernels=False):
        if eqn.primitive.name != "scan":
            continue
        for side, variables in enumerate((eqn.invars, eqn.outvars)):
            for var in variables:
                shape = plane_shape(var)
                if shape is not None:
                    out[shape][side] += classes[shape]
    return {shape: (g, s) for shape, (g, s) in out.items()}


# -- trace builders (trace-only; no compile, the census discipline) -----


def _apply_noop(vals0, present0):
    import jax.numpy as jnp

    return jnp.sum(vals0, axis=1), vals0, present0


def trace_oram_round(cfg, b: int):
    """Jaxpr of one ``oram_round`` with concrete arange indices (the
    tree-cache census's tracing recipe — index choice cannot matter, by
    that census's own index-independence claim)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..oram.path_oram import init_oram
    from ..oram.round import oram_round

    state = jax.eval_shape(lambda: init_oram(cfg, jax.random.PRNGKey(0)))
    cidxs = jnp.asarray((np.arange(b) % cfg.blocks).astype(np.uint32))
    recursive = cfg.posmap is not None
    lf = jax.ShapeDtypeStruct((b,), jnp.uint32)

    def run(st, nl, dl, pm_nl, pm_dl):
        return oram_round(
            cfg, st, cidxs, nl, dl, _apply_noop,
            pm_new_leaves=pm_nl if recursive else None,
            pm_dummy_leaves=pm_dl if recursive else None,
        )

    return jax.make_jaxpr(run)(state, lf, lf, lf, lf)


def _engine_batch_spec(ecfg):
    import jax
    import numpy as np

    from ..engine.state import ID_WORDS, KEY_WORDS, PAYLOAD_WORDS

    b = ecfg.batch_size

    def s(*sh):
        return jax.ShapeDtypeStruct(sh, np.uint32)

    return {
        "req_type": s(b), "auth": s(b, KEY_WORDS),
        "msg_id": s(b, ID_WORDS), "recipient": s(b, KEY_WORDS),
        "payload": s(b, PAYLOAD_WORDS), "now": s(), "now_hi": s(),
    }


def trace_engine_round(ecfg):
    import jax

    from ..engine.round_step import engine_round_step
    from ..engine.state import init_engine

    state = jax.eval_shape(lambda: init_engine(ecfg, 0))
    return jax.make_jaxpr(
        lambda st, ba: engine_round_step(ecfg, st, ba)
    )(state, _engine_batch_spec(ecfg))


def trace_expiry_sweep(ecfg, kernel: bool = False):
    """Jaxpr of one expiry sweep; ``kernel``: as a TPU traces it, the
    chunk's two cipher passes the Pallas kernel (the value plane then
    leaves and re-enters the scan's carry through the kernel's aliased
    output, where the jnp form cuts and pastes it)."""
    import jax
    import numpy as np

    from ..engine.expiry import expiry_sweep
    from ..engine.state import init_engine
    from .jaxpr_walk import cipher_form

    state = jax.eval_shape(lambda: init_engine(ecfg, 0))
    scalar = jax.ShapeDtypeStruct((), np.uint32)
    with cipher_form(ecfg, kernel) as ecfg:
        return jax.make_jaxpr(
            lambda st, now, per, nh: expiry_sweep(ecfg, st, now, per, nh)
        )(state, scalar, scalar, scalar)


# -- cross-validation: the two derivations must agree bit-exactly -------


def _compare(predicted: dict, traced: dict, context: str) -> dict:
    """Exact per-shape-class comparison; raises CostModelMismatch with
    the dominant defect class. Returns the agreed totals."""
    diffs = []
    kind = None
    for key in sorted(set(predicted) | set(traced), key=repr):
        pg, ps = predicted.get(key, (0, 0))
        tg, ts = traced.get(key, (0, 0))
        if (pg, ps) == (tg, ts):
            continue
        if pg < tg:
            kind = kind or "gather-undercount"
        elif pg > tg:
            kind = kind or "gather-overcount"
        elif ps < ts:
            kind = kind or "scatter-undercount"
        else:
            kind = kind or "scatter-overcount"
        diffs.append(
            f"  shape {key}: model (g={pg}, s={ps}) != trace "
            f"(g={tg}, s={ts})"
        )
    if diffs:
        raise CostModelMismatch(
            f"{context}: the analytic cost model and the traced census "
            f"disagree on HBM rows:\n" + "\n".join(diffs),
            kind=kind,
        )
    return predicted


def cross_validate_round(cfg, b: int, *, _corrupt=None) -> dict:
    """One ``oram_round`` at geometry ``cfg``: analytic rows == traced
    rows, per shape class, bit-exactly. ``_corrupt`` is the mutant hook
    (a transform on the predicted rows dict) — production callers never
    pass it."""
    pred = oram_round_rows(cfg, b)
    if _corrupt is not None:
        pred = _corrupt(pred)
    return _compare(
        predicted_access_rows(pred),
        traced_access_rows(trace_oram_round(cfg, b), oram_planes(cfg)),
        f"oram_round(b={b}, plen={cfg.path_len}, k={cfg.top_cache_levels},"
        f" recursive={cfg.posmap is not None})",
    )


def cross_validate_engine_round(ecfg, *, _corrupt=None) -> dict:
    """One full engine round (rounds A+B+C): the composed analytic model
    — mailbox twice at ``B·D``, records once at ``B`` — against the
    traced ``engine_round_step`` census."""
    pred = engine_round_rows(ecfg)
    if _corrupt is not None:
        pred = _corrupt(pred)
    return _compare(
        predicted_access_rows(pred),
        traced_access_rows(trace_engine_round(ecfg), engine_planes(ecfg)),
        f"engine_round(B={ecfg.batch_size}, D={ecfg.mb_choices})",
    )


def cross_validate_sweep(ecfg, *, kernel: bool = False,
                         _corrupt=None) -> dict:
    """The expiry sweep: per chunk-shape class, the scan-streamed rows
    equal one full pass over each tree plane (reads) and one write pass
    over the idx/val/leaf planes (the nonce re-key is a broadcast store
    outside the scan — priced in the ledger, not checkable here).
    ``kernel``: the trace a TPU makes, the cipher kernel in the scan's
    body (:func:`trace_expiry_sweep`); the same planes, once each."""
    chunk = {**sweep_chunk_planes(ecfg.rec, "rec_"),
             **sweep_chunk_planes(ecfg.mb, "mb_")}
    pred_rows = expiry_sweep_rows(ecfg)
    if _corrupt is not None:
        pred_rows = _corrupt(pred_rows)
    # analytic side in chunk-shape space: reads for every chunked plane,
    # writes for the planes the scan carries back out (all but nonces)
    predicted: dict = {}
    for name, (chunk_shape, _) in chunk.items():
        pr = pred_rows.get(name)
        if pr is None:
            continue
        g, s = predicted.get(tuple(chunk_shape), (0, 0))
        writes = 0 if name.endswith("nonces") else pr.scatter_rows
        predicted[tuple(chunk_shape)] = (g + pr.gather_rows, s + writes)
    return _compare(
        predicted,
        traced_scan_rows(trace_expiry_sweep(ecfg, kernel), chunk),
        "expiry_sweep" + ("/kernel" if kernel else ""),
    )


# -- the ledger: bytes, cipher rows, sort volume, steady state ----------


@dataclasses.dataclass
class PhaseCost:
    """One phase's modeled resource footprint (all integers: counts)."""

    gather_rows: int = 0
    scatter_rows: int = 0
    gather_bytes: int = 0
    scatter_bytes: int = 0
    cipher_rows: int = 0  # rows through the bucket-cipher keystream
    sort_keys: int = 0  # keys entering sort/rank machinery
    scatter_elems: int = 0  # scattered u32 elements
    #: the subset of scatter_bytes landing in mesh-SHARDED planes
    #: (outer tree/nonce planes): under a sharded engine these
    #: partition by the owner mask, while the remainder (replicated
    #: inner-posmap trees) lands in full on every chip
    sharded_scatter_bytes: int = 0

    @property
    def hbm_bytes(self) -> int:
        return self.gather_bytes + self.scatter_bytes

    def per_chip_bytes(self, shards: int) -> float:
        """HBM bytes ONE chip of a ``shards``-way mesh moves for this
        phase: gathers keep their full uniform per-chip count (each
        chip reads the whole masked working set from its local range —
        the leak argument), owner-masked scatters partition (modeled
        uniform; the aggregate across chips is exactly the single-chip
        write set — shard counts are powers of two, so the binary
        division is exact), replicated-plane scatters land in full."""
        repl = self.scatter_bytes - self.sharded_scatter_bytes
        return (self.gather_bytes + repl
                + self.sharded_scatter_bytes / shards)

    def add_rows(self, rows: dict) -> "PhaseCost":
        """Accumulate the HBM-resident planes (private ``cache_*``
        planes carry no HBM traffic — they exist for the bit-exact
        row cross-validation, not the byte ledger)."""
        for name, pr in rows.items():
            if not pr.hbm:
                continue
            self.gather_rows += pr.gather_rows
            self.scatter_rows += pr.scatter_rows
            self.gather_bytes += pr.gather_rows * pr.row_words * WORD_BYTES
            self.scatter_bytes += (
                pr.scatter_rows * pr.row_words * WORD_BYTES
            )
            if _sharded_plane(name):
                self.sharded_scatter_bytes += (
                    pr.scatter_rows * pr.row_words * WORD_BYTES
                )
            self.scatter_elems += pr.scatter_rows * pr.row_words
        return self


@dataclasses.dataclass
class CostLedger:
    """Per-phase modeled costs for one engine geometry × knob setting,
    plus the steady-state round aggregate."""

    phases: dict  # phase name -> PhaseCost
    #: bucket-tree shard count the per-chip views divide over (ISSUE
    #: 18); 1 = single chip. Power of two, like the mesh it models.
    shards: int = 1

    @property
    def steady_round_bytes(self) -> float:
        """HBM bytes per steady-state engine round: fetch +
        write-back. The sweep is operator-cadenced and excluded — it
        has its own phase entry."""
        return float(self.phases["fetch"].hbm_bytes
                     + self.phases["writeback"].hbm_bytes)

    @property
    def steady_round_cipher_rows(self) -> float:
        return float(self.phases["fetch"].cipher_rows
                     + self.phases["writeback"].cipher_rows)

    @property
    def steady_round_sort_keys(self) -> float:
        return float(self.phases["fetch"].sort_keys
                     + self.phases["writeback"].sort_keys)

    @property
    def per_shard_steady_round_bytes(self) -> float:
        """HBM bytes ONE chip of the ``shards``-way mesh moves per
        steady-state round (ISSUE 18): gathers keep the full uniform
        per-chip row count (each chip reads the whole masked path
        working set from its local heap range — the leak argument),
        owner-masked scatters into the sharded outer trees partition
        (sum across chips = exactly the single-chip write set; the
        power-of-two division is exact in binary), and replicated-
        plane scatters (inner posmap trees) land in full per chip.
        ``shards=1`` reduces to :attr:`steady_round_bytes` exactly."""
        return float(self.phases["fetch"].per_chip_bytes(self.shards)
                     + self.phases["writeback"].per_chip_bytes(self.shards))

    def floor_ms(self, gbytes_per_s: float) -> float:
        """Roofline round-time floor at a calibrated achieved
        bandwidth: modeled per-chip steady-state bytes / bandwidth
        (per-chip == total on a single chip)."""
        return self.per_shard_steady_round_bytes / (gbytes_per_s * 1e6)


def _round_sort_keys(cfg, b: int) -> int:
    """Sort key-volume of one oram_round: the eviction leaf sort over
    the working set, composed recursively for the internal map round."""
    z = cfg.bucket_slots
    plen = cfg.path_len
    # working set: stash, every dense bucket once, the per-path copies
    # below, b insert rows
    ld = _round_dense_levels(cfg, b)
    rows = ((1 << ld) - 1) + b * (plen - ld)
    keys = cfg.stash_size + rows * z + b
    if cfg.posmap is not None:
        from ..oram.posmap import inner_oram_config

        keys += _round_sort_keys(inner_oram_config(cfg.posmap), b)
    return keys


def _round_cipher_rows(cfg, b: int) -> int:
    """Keystream rows of one oram_round: decrypt the fetched bottom
    rows (+ the recursive leaf plane's separate stream), and encrypt
    the same counts back."""
    if not cfg.encrypted:
        inner = 0
    else:
        R = _round_hbm_rows(cfg, b)
        streams = 2 if cfg.posmap is not None else 1  # idx/val + leaf
        inner = R * streams * 2  # fetch + write-back
    if cfg.posmap is not None:
        from ..oram.posmap import inner_oram_config

        inner += _round_cipher_rows(inner_oram_config(cfg.posmap), b)
    return inner


def engine_cost_ledger(ecfg, shards: int = 1) -> CostLedger:
    """The full modeled ledger for one engine geometry × knob setting —
    the object obs/costmon.py exports and bench.py grades. ``shards``
    is the bucket-tree mesh width (GrapevineConfig.shards — engine
    geometry that deliberately lives OUTSIDE EngineConfig, so it is a
    parameter here, not a field read off ``ecfg``)."""
    if shards < 1 or shards & (shards - 1):
        raise ValueError(f"shards={shards}: want a power of two >= 1")
    b, d = ecfg.batch_size, ecfg.mb_choices
    round_rows = engine_round_rows(ecfg)
    fetch = PhaseCost().add_rows({
        n: dataclasses.replace(pr, scatter_rows=0)
        for n, pr in round_rows.items()
    })
    wb = PhaseCost().add_rows({
        n: dataclasses.replace(pr, gather_rows=0)
        for n, pr in round_rows.items()
    })
    sweep = PhaseCost().add_rows(expiry_sweep_rows(ecfg))
    # the sweep's nonce re-key is a broadcast store over each tree's
    # whole nonce plane (outside the chunk scan)
    for cfg in (ecfg.rec, ecfg.mb):
        if cfg.encrypted:
            n = cfg.n_buckets_padded
            sweep.scatter_rows += n
            sweep.scatter_bytes += n * 2 * WORD_BYTES
            sweep.scatter_elems += n * 2
            sweep.cipher_rows += 2 * n * (
                2 if cfg.posmap is not None else 1
            )
    # round-phase cipher/sort volumes: records once, mailbox twice
    dec_total = (_round_cipher_rows(ecfg.rec, b)
                 + 2 * _round_cipher_rows(ecfg.mb, b * d))
    sort_total = (
        _round_sort_keys(ecfg.rec, b)
        + 2 * _round_sort_keys(ecfg.mb, b * d)
    )
    # the fetch/write-back split of the joint round program is half
    # decrypt, half re-encrypt; the eviction sort rides the write-back
    # half
    fetch.cipher_rows = dec_total // 2
    wb.cipher_rows = dec_total - dec_total // 2
    wb.sort_keys = sort_total
    return CostLedger(
        phases={"fetch": fetch, "writeback": wb, "sweep": sweep},
        shards=shards,
    )


# -- knob A/B verdicts (the model-graded decisions) ---------------------


def machinery_oram_cfg(cap_n: int, b: int, *, k: int = 0):
    """The records-shaped single-ORAM geometry the bench machinery
    A/Bs time (bench.py tree_cache_ab: density-2 payload
    shape, 64-word values, cipher on) — mirrored here so the model
    prices exactly the banked configuration."""
    from ..oram.path_oram import OramConfig

    height = max(1, cap_n.bit_length() - 2)
    return OramConfig(
        height=height, value_words=64, n_blocks=cap_n,
        cipher_rounds=8, stash_size=max(96, b // 2 + 96),
        top_cache_levels=min(k, height),
    )


def sweep_engine_ecfg(batch: int, *, cap_log2: int = 12,
                      recipients_log2: int = 9, mailbox_cap: int = 8,
                      **knobs):
    """The engine geometry the bench whole-round sweeps time."""
    from ..config import GrapevineConfig
    from ..engine.state import EngineConfig

    return EngineConfig.from_config(GrapevineConfig(
        max_messages=1 << cap_log2,
        max_recipients=1 << recipients_log2,
        batch_size=batch, mailbox_cap=mailbox_cap,
        stash_size=max(128, batch // 2 + 96), tree_density=2, **knobs,
    ))


def oram_steady_bytes(cfg, b: int) -> float:
    """HBM bytes per round of one isolated ORAM: the round's gather
    and write-back bytes."""
    return float(PhaseCost().add_rows(oram_round_rows(cfg, b)).hbm_bytes)


#: arms whose modeled bytes sit within this fraction of the best arm
#: are a byte-tie: the verdict then prefers the structurally smaller
#: arm (less machinery — no private cache)
TIE_BAND = 0.02


def _pick(arms: dict, order) -> str:
    """argmin bytes with the tie-band rule: among arms within TIE_BAND
    of the minimum, the first in ``order`` (least machinery) wins."""
    best = min(arms[a]["modeled_bytes"] for a in arms)
    for a in order:
        if arms[a]["modeled_bytes"] <= best * (1.0 + TIE_BAND):
            return a
    raise AssertionError("unreachable: some arm attains the minimum")


def ab_verdict(kind: str, *, scope: str = "machinery",
               cap_n: int = 65536, batch: int = 256, arms=None) -> dict:
    """The model's pick for one shipped A/B config — the number
    bench.py reports next to the measured winner and
    tools/check_cost_model.py grades against every banked
    BENCH_trajectory.jsonl line.

    The decision rule is modeled amortized HBM bytes with the
    :data:`TIE_BAND` preference for less machinery: a knob arm only
    wins when it actually removes traffic (tree-top cache converts
    HBM rows to private rows). ``pipeline`` swaps machinery without
    changing plane traffic, so its verdict is structural and flagged in
    ``basis``.
    """
    out: dict = {"kind": kind, "scope": scope, "arms": {}}
    if kind == "tree_cache":
        ks = tuple(arms) if arms else (0, 2, 4, 8)
        for k in ks:
            if scope == "machinery":
                cfg = machinery_oram_cfg(cap_n, batch, k=k)
                nbytes = oram_steady_bytes(cfg, batch)
            else:
                led = engine_cost_ledger(sweep_engine_ecfg(
                    batch, tree_top_cache_levels=k))
                nbytes = led.steady_round_bytes
            out["arms"][f"k{k}"] = {"modeled_bytes": int(nbytes)}
        out["winner"] = _pick(out["arms"], [f"k{k}" for k in ks])
        out["basis"] = (
            "a cached level leaves the HBM planes both directions: "
            "2^L rows/plane where the round is level-dense (2^L <= B), "
            "B rows/plane below that; bytes fall monotonically in k, "
            "so the deepest arm wins unless the cut is inside the tie "
            "band (a level the batch covers saves only its own 2^L "
            "rows: a cut of 2-11 % at the banked machinery configs, "
            "inside the band in the engine sweeps)"
        )
    elif kind == "pipeline":
        out["arms"] = {"depth1": {"model": "host + device serialized"},
                       "depth2": {"model": "max(host, device) overlap"}}
        out["winner"] = "depth2"
        out["basis"] = (
            "overlap is never negative: depth-2 throughput >= depth-1 "
            "whenever the host collection window is nonzero; the A/B "
            "prices the commit-latency cost of the extra in-flight "
            "round, not bytes"
        )
    else:
        raise ValueError(f"unknown A/B kind {kind!r}")
    return out


# -- seeded undercount mutants (the checker's positive controls) --------

#: name -> (corruption transform on the predicted rows dict,
#:          validator it must trip, validator kwargs, expected kind)
_COST_MUTANTS: dict = {}


def _cost_mutant(name: str, validator: str, kind: str, **vkw):
    def deco(fn):
        _COST_MUTANTS[name] = (fn, validator, vkw, kind)
        return fn
    return deco


def _scale_plane(rows, suffix, *, g=None, s=None):
    out = dict(rows)
    for name, pr in rows.items():
        if name.endswith(suffix):
            out[name] = dataclasses.replace(
                pr,
                gather_rows=pr.gather_rows if g is None
                else int(pr.gather_rows * g),
                scatter_rows=pr.scatter_rows if s is None
                else int(pr.scatter_rows * s),
            )
    return out


@_cost_mutant("halve_fetch_rows", "round", "gather-undercount")
def _halve_fetch(rows):
    """A model that forgets half the fetched path — the classic
    B·path_len vs B·(path_len)/2 slip."""
    return _scale_plane(rows, "tree_val", g=0.5)


@_cost_mutant("drop_recursive_nonce_regather", "round_recursive",
              "gather-undercount")
def _drop_nonce_regather(rows):
    """A model unaware the recursive leaf plane re-gathers the nonce
    plane for its own keystream (the second nonce gather)."""
    return _scale_plane(rows, "nonces", g=0.5)


@_cost_mutant("forget_dense_range", "round_cached", "gather-undercount")
def _forget_dense_range(rows):
    """A model that prices only the per-path rows of the round and
    forgets the dense heap range read above them (at the audit
    geometry b=8, height=5, k=2: 28 rows a plane, 12 of them dense)."""
    return _scale_plane(rows, "tree_val", g=16 / 28)


@_cost_mutant("price_dense_levels_per_path", "round_cached",
              "gather-overcount")
def _price_per_path(rows):
    """The pre-dense model: every level below the cache priced once
    per path, b·(path_len−k) = 32 rows where the round moves 28 — the
    checker must see an over-count as loudly as an under-count, or a
    round that silently fell back to per-path rows would pass."""
    return _scale_plane(rows, "tree_val", g=32 / 28, s=32 / 28)


@_cost_mutant("forget_writeback_half", "round", "scatter-undercount")
def _forget_writeback(rows):
    """A model that treats the round as fetch-only."""
    out = {}
    for name, pr in rows.items():
        out[name] = dataclasses.replace(pr, scatter_rows=0)
    return out


@_cost_mutant("forget_inner_posmap_round", "round_recursive",
              "gather-undercount")
def _forget_inner(rows):
    """A model that prices the recursive map's internal ORAM round as
    free — exactly the B internal accesses the posmap docs pin."""
    out = {}
    for name, pr in rows.items():
        if "pm_" in name:
            pr = dataclasses.replace(pr, gather_rows=0, scatter_rows=0)
        out[name] = pr
    return out


@_cost_mutant("forget_mailbox_double_round", "engine",
              "gather-undercount")
def _forget_mb_double(rows):
    """A model that counts the mailbox tree once per engine round —
    the round A + round C composition missed."""
    out = {}
    for name, pr in rows.items():
        if name.startswith("mb_"):
            pr = dataclasses.replace(
                pr,
                gather_rows=pr.gather_rows // 2,
                scatter_rows=pr.scatter_rows // 2,
            )
        out[name] = pr
    return out


@_cost_mutant("forget_sweep_value_pass", "sweep", "gather-undercount")
def _forget_sweep_val(rows):
    """A model that forgets the sweep streams the value planes."""
    return _scale_plane(rows, "tree_val", g=0, s=0)


def audit_oram_configs():
    """The shipped trace-only knob matrix the smoke gate and the tests
    cross-validate over: (name, cfg, b) per ``oram_round`` geometry,
    spanning cache-k × posmap.

    Audit-geometry discipline (the tree-cache census's caveat, made
    load-bearing here): shape-class attribution is exact only while no
    *private* intermediate shares a declared plane shape — so batch
    sizes are chosen with ``b·(path_len−k)`` (and its cipher-stream
    doubling) distinct from every padded bucket count.
    A violated assumption shows up as a loud mismatch, never a silent
    undercount."""
    from ..oram.path_oram import OramConfig
    from ..oram.posmap import derive_posmap_spec

    flat = OramConfig(height=5, value_words=8, n_blocks=32,
                      cipher_rounds=8, top_cache_levels=0)
    cached = OramConfig(height=5, value_words=8, n_blocks=32,
                        cipher_rounds=8, top_cache_levels=2)
    plaintext = OramConfig(height=5, value_words=8, n_blocks=32,
                           top_cache_levels=2)
    recursive = OramConfig(
        height=5, value_words=8, n_blocks=32, cipher_rounds=8,
        top_cache_levels=2,
        posmap=derive_posmap_spec(32, top_cache_levels=2),
    )
    # a value row of eight lane tiles: stored ``(8, 128)``, and on a TPU
    # written back by the row-placement kernel, which the census counts
    # as the scatter it stands in for (tests/test_pallas_place.py traces
    # this geometry as a TPU does)
    wide_row = OramConfig(height=5, value_words=256, n_blocks=32,
                          cipher_rounds=8, top_cache_levels=2)
    return [
        ("flat_k0", flat, 8),
        ("flat_k2", cached, 8),
        ("flat_k2_plaintext", plaintext, 8),
        ("recursive_k2", recursive, 6),
        ("flat_k2_wide_row", wide_row, 8),
    ]


def audit_engine_configs():
    """The engine-level audit geometry, sized so no private cipher
    working set matches a plane's padded bucket count."""
    from ..config import GrapevineConfig
    from ..engine.state import EngineConfig

    return [("engine", EngineConfig.from_config(GrapevineConfig(
        max_messages=1 << 8, max_recipients=1 << 7, batch_size=4,
    )))]


def _mutant_fixtures():
    """Small trace-only geometries, one per validator context."""
    by_name = {name: (cfg, b) for name, cfg, b in audit_oram_configs()}
    engines = dict(audit_engine_configs())
    flat, flat_b = by_name["flat_k0"]
    cached, cached_b = by_name["flat_k2"]
    recursive, rec_b = by_name["recursive_k2"]
    return {
        "round": (cross_validate_round, {"cfg": flat, "b": flat_b}),
        "round_cached": (cross_validate_round,
                         {"cfg": cached, "b": cached_b}),
        "round_recursive": (cross_validate_round,
                            {"cfg": recursive, "b": rec_b}),
        "engine": (cross_validate_engine_round,
                   {"ecfg": engines["engine"]}),
        "sweep": (cross_validate_sweep, {"ecfg": engines["engine"]}),
    }


class _MutantReport:
    """Minimal report shape for mutants.control_failures (its
    ``findings`` protocol)."""

    def __init__(self, findings):
        self.findings = findings


def run_cost_mutants() -> dict:
    """Run every seeded undercount mutant through the same
    cross-validators the production smoke runs; returns
    ``name -> (report, expected_kind, failed_as_expected)`` — the
    shape :func:`.mutants.control_failures` reports over."""
    fixtures = _mutant_fixtures()
    out = {}
    for name, (corrupt, context, vkw, kind) in _COST_MUTANTS.items():
        validator, base_kw = fixtures[context]
        try:
            validator(**base_kw, **vkw, _corrupt=corrupt)
            findings, hit = [], False
        except CostModelMismatch as m:
            findings, hit = [m], m.kind == kind
        out[name] = (_MutantReport(findings), kind, hit)
    return out
