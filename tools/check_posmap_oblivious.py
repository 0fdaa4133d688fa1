#!/usr/bin/env python
"""CI gate: the position-map lookup's access schedule is index-blind.

The recursive position map's obliviousness claim (oram/posmap.py) is
that resolving a batch of B positions performs a FIXED schedule of
device memory accesses — the same number of gathers and scatters, in
the same program, no matter which indices are queried (duplicates,
all-same, all-dummy, anything). The access census:

1. trace ``lookup_remap_round`` with the *indices baked in as concrete
   constants* for several adversarially different index sets (all
   distinct, all identical, all dummy, mixed duplicates). Constants are
   the strongest form of the check: a data-dependent implementation —
   a Python-level branch on duplicates, a shortcut for dummy batches, a
   per-unique-index loop — would trace to *different* programs, which
   tracer-level (shape-only) audits can never see;
2. assert the full primitive census (every equation, recursively into
   sub-jaxprs) is IDENTICAL across all index sets, and in particular
   the gather/scatter counts are a fixed positive constant of the
   geometry;
3. assert no data-dependent control flow anywhere in the traced lookup
   (``cond``/``while``: a predicate on secret indices could skip
   accesses at run time even under a fixed trace);
4. positive control: the flat impl's census differs from the recursive
   one's (one gather + one scatter vs the internal ORAM round), proving
   the census actually distinguishes access schedules rather than
   vacuously passing.

Wired into tier-1 next to check_telemetry_policy / check_perf_regression
via tests/test_posmap.py; standalone: ``python tools/check_posmap_oblivious.py``.

Since ISSUE 12 this is a thin wrapper over the shared analyzer core
(grapevine_tpu/analysis/jaxpr_walk.py) — the census here, the tree-cache
tool's, and the taint analyzer's all walk the identical equation stream,
so the three gates cannot drift. CLI and exit codes are unchanged.
"""

from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from grapevine_tpu.analysis.jaxpr_walk import (  # noqa: E402
    ACCESS_PRIMS as _ACCESS_PRIMS,
    CONTROL_PRIMS as _CONTROL_PRIMS,
    census as _census,
)


def _index_sets(cfg, b: int):
    """Adversarially different query batches (concrete u32[b])."""
    import numpy as np

    dummy = cfg.dummy_index
    distinct = np.arange(b, dtype=np.uint32) % np.uint32(cfg.blocks)
    same = np.zeros(b, np.uint32)
    all_dummy = np.full(b, dummy, np.uint32)
    rng = np.random.default_rng(7)
    mixed = rng.integers(0, cfg.blocks + 1, b).astype(np.uint32)
    return {
        "distinct": distinct,
        "all_same": same,
        "all_dummy": all_dummy,
        "mixed_dups": mixed,
    }


def _trace_lookup(cfg, idxs, b: int):
    """Jaxpr of one whole-batch lookup+remap with ``idxs`` constant."""
    import jax
    import jax.numpy as jnp

    from grapevine_tpu.oram.path_oram import init_oram
    from grapevine_tpu.oram.posmap import lookup_remap_round
    from grapevine_tpu.oram.round import occurrence_masks

    state = jax.eval_shape(lambda: init_oram(cfg, jax.random.PRNGKey(0)))
    pm_shape = state.posmap
    il = cfg.posmap.inner_leaves if cfg.posmap is not None else 1
    cidxs = jnp.asarray(idxs)

    def run(pm, nl, dl, pm_nl, pm_dl):
        fo, lo, _ = occurrence_masks(cidxs, cfg.dummy_index)
        return lookup_remap_round(
            cfg, pm, cidxs, nl, dl, fo, lo,
            pm_new_leaves=pm_nl if cfg.posmap is not None else None,
            pm_dummy_leaves=pm_dl if cfg.posmap is not None else None,
        )

    u32 = jnp.uint32
    lf = jax.ShapeDtypeStruct((b,), u32)
    return jax.make_jaxpr(run)(
        pm_shape, lf, lf,
        jax.ShapeDtypeStruct((b,), u32) if il else lf, lf,
    )


def check_posmap_access_schedule(b: int = 16, verbose: bool = False) -> dict:
    """Run the audit; returns the census summary, raises AssertionError
    on any violation."""
    from grapevine_tpu.oram.path_oram import OramConfig
    from grapevine_tpu.oram.posmap import derive_posmap_spec

    flat_cfg = OramConfig(height=4, value_words=4, n_blocks=32)
    rec_cfg = OramConfig(
        height=4, value_words=4, n_blocks=32,
        posmap=derive_posmap_spec(32),
    )

    out = {}
    for name, cfg in (("flat", flat_cfg), ("recursive", rec_cfg)):
        censuses = {}
        for iname, idxs in _index_sets(cfg, b).items():
            c = _census(_trace_lookup(cfg, idxs, b))
            censuses[iname] = c
        base_name, base = next(iter(censuses.items()))
        for iname, c in censuses.items():
            assert c == base, (
                f"{name} posmap lookup traces a DIFFERENT program for "
                f"index set {iname!r} vs {base_name!r}: "
                f"{(c - base) + (base - c)} — the access schedule "
                "depends on the queried indices"
            )
        n_access = sum(base[p] for p in _ACCESS_PRIMS)
        n_control = sum(base[p] for p in _CONTROL_PRIMS)
        assert n_access > 0, f"{name}: census saw no access primitives"
        assert n_control == 0, (
            f"{name} posmap lookup contains data-dependent control flow "
            f"({ {p: base[p] for p in _CONTROL_PRIMS if base[p]} }) — a "
            "run-time predicate could skip accesses under a fixed trace"
        )
        out[name] = {
            "accesses": n_access,
            "gathers": base["gather"],
            "scatters": sum(
                v for k, v in base.items() if k.startswith("scatter")
            ),
            "census_size": sum(base.values()),
        }
        if verbose:
            print(f"{name}: {out[name]}")

    # positive control: the audit distinguishes the two schedules
    assert out["recursive"]["accesses"] > out["flat"]["accesses"], (
        "positive control failed: the recursive lookup's access census "
        f"({out['recursive']}) does not exceed the flat one's "
        f"({out['flat']}) — the census is not seeing the internal ORAM"
    )
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--batch", type=int, default=16)
    args = ap.parse_args(argv)
    out = check_posmap_access_schedule(b=args.batch, verbose=True)
    print(f"[check_posmap_oblivious] OK {out}")
    print("[check_posmap_oblivious] PASS: position-map access schedule "
          "is a constant of the geometry")
    return 0


if __name__ == "__main__":
    sys.exit(main())
