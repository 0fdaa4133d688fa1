#!/usr/bin/env python
"""CI gate: geometry-scale overflow certification of the compiled round.

Rangelint (grapevine_tpu/analysis/rangelint.py) abstract-interprets the
closed jaxpr of the full engine round, the expiry sweep, and the
standalone library sub-rounds (oram_round, lookup_remap_round) with a
per-dtype interval domain: geometry-derived input ranges are declared at
the RANGELINT_BOUNDS anchors (oram/path_oram.py, oram/posmap.py,
engine/round_step.py, engine/expiry.py; engine/journal.py holds the
host-side byte-length guard) and propagated through every primitive with
a scan/while carry fixpoint, flagging u32/int32 wraparound, truncating
casts, and gather/slice indices that can leave their axis (XLA clamps
would hide those). Intentional mod-2^32 sites (ChaCha ARX, the keyed
mixers, u64 two-lane carries) pass through the reviewed RANGE_ALLOWLIST,
each entry with its one-line range argument; dead entries fail the run.

Sweep: the 2x2 knob matrix {posmap_impl} x {tree_top_cache_levels}
at the declared
``--geometry`` (log2 records; default 30 — the max certified per-tree
capacity, where every allowlist entry genuinely fires), engine round +
expiry sweep + standalone oram_round/lookup_remap_round per combo, plus
the write-back scatter under a 2-shard ``shard_map`` (the lanes only
the mesh has). ``--smoke`` is the tier-1 budget: one combo at toy
geometry, traces only, zero engine compiles.

Geometry certification: ``--geometry 30`` certifies today's capacity
point clean; ``--geometry 36`` (the ROADMAP item 4 design point) must be
*refused* by the construction-time guard (oram/path_oram.py
OramConfig.__post_init__ — the certified u32 bound is height <= 29 /
blocks <= 2^30), and this report cites that refusal plus the certified
composition: 2^36 records = 2^6 recipient-space shards x 2^30 (ROADMAP
item 2), each shard's compiled round certified clean here — or a deeper
recursion with widened lanes (item 4). A beyond-bound geometry that
constructs WITHOUT refusing fails this gate.

Teeth: the seeded overflow mutants (grapevine_tpu/analysis/mutants.py
_RANGE_REGISTRY — u32 leaf-arith wrap, truncating cast, off-by-one axis
bound, unbounded scan counter, int32 byte-size product) run under the
production range allowlist on every invocation and must each FAIL.

Standalone: ``python tools/check_ranges.py [--smoke|--full]
[--geometry N]``; tier-1: tests/test_rangelint.py (next to the
telemetry/seal/oblint gates).
"""

from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

#: the knob matrix (posmap, k) — the check_oblivious set, so the two
#: analyzers certify the identical program matrix
DEFAULT_COMBOS = (
    ("flat", 0),
    ("recursive", 2),
    ("flat", 2),
    ("recursive", 0),
)
#: tier-1 budget: ONE combo (check_oblivious's smoke)
SMOKE_COMBO = ("flat", 0)

#: default certification geometry (log2 records) for the standalone
#: sweep: the max certified per-tree capacity — several allowlist
#: entries (e.g. partition_rank's rank recombination) only *fire* once
#: the lanes get tight, so reachability at toy geometry would misread
#: them as dead. --smoke uses the toy engine regardless.
DEFAULT_GEOMETRY = 30

#: the ROADMAP item 4 design point: must be REFUSED at construction
DESIGN_POINT = 36

#: the largest per-tree records capacity the u32 lanes certify (density
#: 2: height 29 payload trees) — the shard size of the 2^36 composition
MAX_CERTIFIED_GEOMETRY = 30


def _engine(log2_msgs: int, pmi: str, k: int, batch: int = 4):
    from grapevine_tpu.config import GrapevineConfig
    from grapevine_tpu.engine.state import EngineConfig

    cfg = GrapevineConfig(
        max_messages=1 << log2_msgs,
        max_recipients=max(16, 1 << min(log2_msgs, 20)),
        batch_size=batch,
        posmap_impl=pmi, tree_top_cache_levels=k,
    )
    return EngineConfig.from_config(cfg)


def _batch_spec(ecfg):
    import jax
    import numpy as np

    from grapevine_tpu.engine.state import (
        ID_WORDS, KEY_WORDS, PAYLOAD_WORDS,
    )

    b = ecfg.batch_size

    def s(*sh):
        return jax.ShapeDtypeStruct(sh, np.uint32)

    return {
        "req_type": s(b), "auth": s(b, KEY_WORDS),
        "msg_id": s(b, ID_WORDS), "recipient": s(b, KEY_WORDS),
        "payload": s(b, PAYLOAD_WORDS), "now": s(), "now_hi": s(),
    }


def audit_engine_round(ecfg, allowlist, name: str):
    """Interval-audit one full engine round (trace only, no compile)."""
    import jax

    from grapevine_tpu.analysis.rangelint import analyze_ranges
    from grapevine_tpu.engine import round_step
    from grapevine_tpu.engine.state import init_engine

    state = jax.eval_shape(lambda: init_engine(ecfg, 0))
    return analyze_ranges(
        lambda st, ba: round_step.engine_round_step(ecfg, st, ba),
        {"state": state, "batch": _batch_spec(ecfg)},
        bounds=round_step.RANGELINT_BOUNDS(ecfg),
        allowlist=allowlist,
        name=f"engine_round/{name}",
    )


def audit_expiry_sweep(ecfg, allowlist, name: str, kernel: bool = False):
    """Range-audit one expiry sweep (trace only). ``kernel``: the sweep
    as a TPU runs it, the Pallas cipher kernel in the scan's body (its
    block arithmetic, from the chunk's index, walked with the rest),
    where the CPU's form holds the jnp keystream."""
    import jax
    import numpy as np

    from grapevine_tpu.analysis.jaxpr_walk import cipher_form
    from grapevine_tpu.analysis.rangelint import analyze_ranges
    from grapevine_tpu.engine import expiry
    from grapevine_tpu.engine.state import init_engine

    state = jax.eval_shape(lambda: init_engine(ecfg, 0))
    scalar = jax.ShapeDtypeStruct((), np.uint32)
    with cipher_form(ecfg, kernel) as ecfg:
        rep = analyze_ranges(
            lambda st, now, per, nh: expiry.expiry_sweep(
                ecfg, st, now, per, nh),
            {"state": state, "now": scalar, "period": scalar,
             "now_hi": scalar},
            bounds=expiry.RANGELINT_BOUNDS(ecfg),
            allowlist=allowlist,
            name=f"expiry_sweep/{name}" + ("/kernel" if kernel else ""),
        )
    assert bool(rep.census.get("pallas_call")) == kernel, rep.census
    return rep


def _oram_cfg(log2_blocks: int, recursive: bool, k: int):
    from grapevine_tpu.oram.path_oram import OramConfig
    from grapevine_tpu.oram.posmap import derive_posmap_spec

    blocks = 1 << log2_blocks
    pm = (derive_posmap_spec(blocks, top_cache_levels=k)
          if recursive else None)
    return OramConfig(
        height=max(1, log2_blocks - 1), value_words=4, n_blocks=blocks,
        cipher_rounds=8, posmap=pm, top_cache_levels=k,
    )


def audit_sharded_path_scatter(allowlist, log2_blocks: int,
                               shards: int = 2, by_dma: bool = False):
    """Interval-audit the owner-masked write-back of the sharded round
    (``_path_scatter`` under ``shard_map`` over a bucket-axis mesh):
    the lanes only the mesh has — ``axis_index`` (bounded
    [0, shards-1] by the rangelint mesh rule) and the per-chip rebase,
    whose non-owned lanes wrap mod 2^32 by construction and land on the
    drop sentinel, a reviewed RANGE_ALLOWLIST pair. Trace-only, like
    every audit here.

    ``by_dma`` audits the form a TPU traces for a wide value plane (a
    row of eight lane tiles, stored ``(8, 128)``): the same rebase and
    cast feed the row-placement kernel (oblivious/pallas_place.py),
    whose own arithmetic (the semaphore a row's copy takes, the row it
    waits for) must stay in range with no entry of its own."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from grapevine_tpu.analysis.rangelint import analyze_ranges
    from grapevine_tpu.oram.path_oram import _path_scatter
    from grapevine_tpu.parallel.mesh import TREE_AXIS, make_mesh

    import contextlib

    from grapevine_tpu.analysis.jaxpr_walk import as_a_tpu_traces

    cfg = _oram_cfg(log2_blocks, False, 0)
    n, w, rows = cfg.n_buckets_padded, cfg.bucket_slots, 4 * cfg.path_len
    stored = (8, 128) if by_dma else (w,)
    w = 1024 if by_dma else w

    def sds(shape, dtype=jnp.uint32):
        return jax.ShapeDtypeStruct(shape, dtype)

    fn = jax.shard_map(
        lambda tree, path_b, vals, owner: _path_scatter(
            tree, path_b, vals, TREE_AXIS, owner),
        mesh=make_mesh(jax.devices()[:shards]),
        in_specs=(P(TREE_AXIS), P(), P(), P()), out_specs=P(TREE_AXIS),
        check_vma=False,
    )
    with as_a_tpu_traces() if by_dma else contextlib.nullcontext():
        rep = analyze_ranges(
            fn,
            {"tree": sds((n, *stored)), "path_b": sds((rows,)),
             "vals": sds((rows, w)), "owner": sds((rows,), jnp.bool_)},
            bounds={"path_b": (0, n - 1)},
            allowlist=allowlist,
            name=(f"sharded_path_scatter/2^{log2_blocks}_s{shards}"
                  + ("_dma" if by_dma else "")),
        )
    assert bool(rep.census.get("dma_start")) == by_dma, rep.census
    return rep


def audit_oram_round(allowlist, log2_blocks: int, recursive: bool,
                     k: int):
    import jax
    import jax.numpy as jnp

    from grapevine_tpu.analysis.rangelint import analyze_ranges
    from grapevine_tpu.oram import posmap as pmod
    from grapevine_tpu.oram import round as oround
    from grapevine_tpu.oram.path_oram import (
        RANGELINT_BOUNDS as tree_bounds, init_oram,
    )

    cfg = _oram_cfg(log2_blocks, recursive, k)
    state = jax.eval_shape(lambda: init_oram(cfg, jax.random.PRNGKey(0)))
    b = 4

    def sds(*sh):
        return jax.ShapeDtypeStruct(sh, jnp.uint32)

    def apply_batch(vals0, present0):
        # pass-through callback: the audit certifies the round machinery
        return vals0[:, 0], vals0, present0

    def run(state, idxs, new_leaves, dummy_leaves, pm_new_leaves,
            pm_dummy_leaves):
        return oround.oram_round(
            cfg, state, idxs, new_leaves, dummy_leaves, apply_batch,
            pm_new_leaves=pm_new_leaves if recursive else None,
            pm_dummy_leaves=pm_dummy_leaves if recursive else None,
        )

    bounds = {
        **tree_bounds(cfg, prefix="state"),
        **pmod.RANGELINT_BOUNDS(cfg, prefix="state.posmap"),
    }
    # the posmap anchor's pm_state.* labels do not apply here (the map
    # rides inside state.posmap, covered by the tree anchor)
    bounds = {k2: v for k2, v in bounds.items()
              if not k2.startswith("pm_state")}
    return analyze_ranges(
        run,
        {"state": state, "idxs": sds(b), "new_leaves": sds(b),
         "dummy_leaves": sds(b), "pm_new_leaves": sds(b),
         "pm_dummy_leaves": sds(b)},
        bounds=bounds,
        allowlist=allowlist,
        name=f"oram_round/2^{log2_blocks}_"
             f"{'rec' if recursive else 'flat'}_k{k}",
    )


def audit_lookup_remap(allowlist, log2_blocks: int, recursive: bool):
    import jax
    import jax.numpy as jnp

    from grapevine_tpu.analysis.rangelint import analyze_ranges
    from grapevine_tpu.oram import posmap as pmod
    from grapevine_tpu.oram.posmap import init_posmap

    cfg = _oram_cfg(log2_blocks, recursive, 0)
    pm_state = jax.eval_shape(
        lambda: init_posmap(cfg, jax.random.PRNGKey(0))
    )
    b = 4

    def sds(*sh, dt=jnp.uint32):
        return jax.ShapeDtypeStruct(sh, dt)

    def run(pm_state, idxs, new_leaves, dummy_leaves, first_occ,
            last_occ, pm_new_leaves, pm_dummy_leaves):
        return pmod.lookup_remap_round(
            cfg, pm_state, idxs, new_leaves, dummy_leaves,
            first_occ, last_occ,
            pm_new_leaves=pm_new_leaves if recursive else None,
            pm_dummy_leaves=pm_dummy_leaves if recursive else None,
        )

    return analyze_ranges(
        run,
        {"pm_state": pm_state, "idxs": sds(b), "new_leaves": sds(b),
         "dummy_leaves": sds(b), "first_occ": sds(b, dt=jnp.bool_),
         "last_occ": sds(b, dt=jnp.bool_), "pm_new_leaves": sds(b),
         "pm_dummy_leaves": sds(b)},
        bounds=pmod.RANGELINT_BOUNDS(cfg),
        allowlist=allowlist,
        name=f"lookup_remap/2^{log2_blocks}_"
             f"{'rec' if recursive else 'flat'}",
    )


def run_range_mutant_controls(allowlist) -> list:
    """Every seeded overflow mutant must FAIL under the production
    range allowlist (the shared control reporter both drivers use)."""
    from grapevine_tpu.analysis.mutants import (
        control_failures, run_range_mutants,
    )

    log = lambda line: print(f"[check_ranges] {line}")  # noqa: E731
    return control_failures(
        run_range_mutants(allowlist), "range mutant", log
    )


def run_audit(combos, geometry: int, allowlist=None, verbose=False,
              with_subrounds: bool = True):
    """Sweep the interval audit; returns (problems, allowlist_hits)."""
    from grapevine_tpu.analysis.allowlist import RANGE_ALLOWLIST

    if allowlist is None:
        allowlist = RANGE_ALLOWLIST
    problems: list = []
    hits: dict = {}

    def absorb(rep):
        for k2, n in rep.allowed.items():
            hits[k2] = hits.get(k2, 0) + n
        if verbose or rep.findings:
            print(rep.summary())
        problems.extend(f"{rep.name}: {f}" for f in rep.findings)

    # engine geometry: max_messages = 2^geometry; sub-round geometry:
    # the same block count standalone
    for pmi, k in combos:
        name = f"2^{geometry}_{pmi}_k{k}"
        ecfg = _engine(geometry, pmi, k)
        absorb(audit_engine_round(ecfg, allowlist, name))
        for kernel in (False, True):
            absorb(audit_expiry_sweep(ecfg, allowlist, name, kernel))
        if with_subrounds:
            absorb(audit_oram_round(
                allowlist, geometry, recursive=(pmi == "recursive"), k=k,
            ))
            absorb(audit_lookup_remap(
                allowlist, geometry, recursive=(pmi == "recursive"),
            ))
    if with_subrounds:
        import jax

        if len(jax.devices()) >= 2:
            # 2 shards is where every sharded-only lane (axis_index,
            # the _path_scatter rebase) exists
            absorb(audit_sharded_path_scatter(allowlist, geometry))
            absorb(audit_sharded_path_scatter(
                allowlist, geometry, by_dma=True))
        else:  # pragma: no cover - bootstrap in main()
            problems.append(
                "sharded scatter audit needs >= 2 devices (got 1) — "
                "run standalone (main() forces a virtual 2-device CPU "
                "mesh) or under the test harness's 8-device conftest"
            )
    return problems, hits


def check_allowlist_reachability(hits: dict) -> list:
    """Every reviewed range entry must fire somewhere in the sweep."""
    from grapevine_tpu.analysis.allowlist import RANGE_ALLOWLIST

    dead = [e for e in RANGE_ALLOWLIST if e.key not in hits]
    return [
        f"dead range-allowlist entry {e.key!r} ({e.reason!r}): never "
        "reached in any swept knob combination — delete it or sweep the "
        "combo that exercises it (dead entries rot into blanket "
        "permissions)"
        for e in dead
    ]


def certify_design_point(log2_records: int) -> "tuple[list, str]":
    """A beyond-bound geometry must REFUSE at construction, citing the
    certified bound; returns (problems, the refusal text this report
    cites)."""
    try:
        _engine(log2_records, "flat", 0)
    except ValueError as exc:
        return [], str(exc)
    return [
        f"2^{log2_records} records constructed WITHOUT a certified-"
        "geometry refusal — the u32 lanes are not certified there; the "
        "construction guard (oram/path_oram.py OramConfig) must refuse "
        "beyond the certified bound"
    ], ""


def main(argv=None) -> int:
    import argparse

    # the sharded scatter audit traces a 2-device shard_map: force a
    # virtual CPU mesh if jax has not initialized yet (standalone
    # invocation; in-process the test conftest already forces 8)
    if ("jax" not in sys.modules
            and "xla_force_host_platform_device_count"
            not in os.environ.get("XLA_FLAGS", "")):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=2"
        ).strip()

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tier-1 budget: one toy-geometry combo, engine "
                         "trace + range mutants + design-point refusal; "
                         "zero compiles")
    ap.add_argument("--geometry", type=int, default=None, metavar="LOG2",
                    help=f"records capacity to certify (log2; default "
                         f"{DEFAULT_GEOMETRY}; {DESIGN_POINT} = the "
                         "design point, certified via refusal + the "
                         "max certified shard geometry)")
    ap.add_argument("--skip-mutants", action="store_true")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)

    from grapevine_tpu.analysis.allowlist import RANGE_ALLOWLIST

    problems: list = []
    geometry = args.geometry if args.geometry is not None else (
        DEFAULT_GEOMETRY
    )

    if args.smoke:
        pmi, k = SMOKE_COMBO
        ecfg = _engine(5, pmi, k)
        rep = audit_engine_round(
            ecfg, RANGE_ALLOWLIST, f"smoke_{pmi}_k{k}",
        )
        print(rep.summary())
        problems.extend(f"{rep.name}: {f}" for f in rep.findings)
        import jax

        if len(jax.devices()) >= 2:
            # always-on sharded lane coverage (trace-only): the
            # owner-masked write-back's rebase arithmetic at toy geometry
            for by_dma in (False, True):
                rep = audit_sharded_path_scatter(
                    RANGE_ALLOWLIST, 5, by_dma=by_dma)
                print(rep.summary())
                problems.extend(f"{rep.name}: {f}" for f in rep.findings)
        dp, refusal = certify_design_point(DESIGN_POINT)
        problems.extend(dp)
        if refusal:
            print(f"[check_ranges] 2^{DESIGN_POINT} design point: "
                  f"REFUSED at construction (certified) — {refusal}")
    else:
        sweep_geometry = geometry
        refusal = ""
        if geometry > MAX_CERTIFIED_GEOMETRY:
            dp, refusal = certify_design_point(geometry)
            problems.extend(dp)
            if refusal:
                print(
                    f"[check_ranges] 2^{geometry} records: REFUSED at "
                    f"construction (certified) — {refusal}\n"
                    f"[check_ranges] certifying the composition shard "
                    f"instead: 2^{geometry} = "
                    f"2^{geometry - MAX_CERTIFIED_GEOMETRY} recipient-"
                    f"space shards x 2^{MAX_CERTIFIED_GEOMETRY} records "
                    "(ROADMAP item 2), or a deeper recursion with "
                    "widened lanes (item 4)"
                )
            sweep_geometry = MAX_CERTIFIED_GEOMETRY
        swept, hits = run_audit(
            DEFAULT_COMBOS, sweep_geometry, verbose=args.verbose,
        )
        problems.extend(swept)
        problems.extend(check_allowlist_reachability(hits))

    if not args.skip_mutants:
        problems.extend(run_range_mutant_controls(RANGE_ALLOWLIST))

    if problems:
        print(f"[check_ranges] FAIL: {len(problems)} problem(s)")
        for p in problems:
            print(f"  - {p}")
        return 1
    scope = (
        "smoke combo" if args.smoke
        else f"knob matrix @ 2^{geometry}"
    )
    reach = "" if args.smoke else "; every range-allowlist entry reachable"
    teeth = "" if args.skip_mutants else "; all overflow mutants caught"
    print(f"[check_ranges] PASS ({scope}): no wraparound, truncating "
          f"cast, or clamped-OOB index outside the reviewed mod-2^32 "
          f"allowlist{reach}{teeth}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
