#!/usr/bin/env python
"""CI gate: unified taint-based obliviousness audit of the engine round
(+ the host-path lock-discipline lint).

One analyzer (grapevine_tpu/analysis/oblint.py) replaces the per-feature
checkers' scattered proofs: secret engine inputs (recipient keys, msg
ids, positions, stash/cache contents, cipher keys, payloads — declared
as OBLINT_SECRETS anchors next to the code where each secret enters) are
tainted at trace time, and the closed jaxpr of the full engine round,
the expiry sweep, and the library sub-rounds (oram_round,
lookup_remap_round) is walked proving no gather/scatter index, no
cond/while predicate, no dynamic-slice start, and no host callback is
secret-derived — modulo the reviewed allowlist
(grapevine_tpu/analysis/allowlist.py), every entry of which carries its
one-line leak argument AND must be *reached* somewhere in the swept knob
matrix (dead entries fail the run).

Sweep: the 2x2 knob matrix {posmap_impl} x {tree_top_cache_levels};
``--full`` (the -m slow tier) adds the program-equality census on
every combination, not only the first. ``--smoke`` is the tier-1
budget: one representative combo, one engine trace, no compile.

Teeth: the seeded mutants (grapevine_tpu/analysis/mutants.py) run under
the production allowlists on every invocation and must each FAIL — the
seven leak classes (position-dependent branch, key-indexed gather,
data-dependent early exit, secret-shaped output, un-allowlisted
scatter, leaky debug print, python-level branch) AND, since ISSUE 14,
the five overflow classes through the rangelint sibling analyzer (one
shared runner proves both analyzers alive from this one tier-1 gate;
tools/check_ranges.py is the overflow analyzer's own driver). A
passing mutant fails this gate.

The host prong: grapevine_tpu/analysis/locklint.py statically asserts
the PR-10 pipeline discipline (journal+dispatch in exactly one engine
lock hold, stage-1 outside every lock, lock-free journal, acyclic lock
ordering, role-covered shared attributes).

Standalone: ``python tools/check_oblivious.py [--smoke|--full]``;
tier-1: tests/test_oblint.py (next to the telemetry/seal/perf gates).
"""

from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

#: the knob matrix (posmap, k): flat+recursive x uncached+cached, so
#: that every allowlist entry is reachable
DEFAULT_COMBOS = (
    ("flat", 0),
    ("recursive", 2),
    ("flat", 2),
    ("recursive", 0),
)
#: tier-1 budget: ONE combo
SMOKE_COMBO = ("flat", 0)


def _small_engine(pmi: str, k: int):
    from grapevine_tpu.config import GrapevineConfig
    from grapevine_tpu.engine.state import EngineConfig

    cfg = GrapevineConfig(
        max_messages=32, max_recipients=16, batch_size=4,
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
    """Taint-audit one full engine round (trace only, no compile)."""
    import jax

    from grapevine_tpu.analysis.oblint import analyze
    from grapevine_tpu.engine import round_step
    from grapevine_tpu.engine.state import init_engine

    state = jax.eval_shape(lambda: init_engine(ecfg, 0))
    return analyze(
        lambda st, ba: round_step.engine_round_step(ecfg, st, ba),
        {"state": state, "batch": _batch_spec(ecfg)},
        secrets=round_step.OBLINT_SECRETS,
        allowlist=allowlist,
        name=f"engine_round/{name}",
    )


def audit_expiry_sweep(ecfg, allowlist, name: str, kernel: bool = False):
    """Taint-audit one expiry sweep (trace only). ``kernel``: the sweep
    as a TPU runs it, both cipher passes of a chunk the Pallas kernel
    (its body walked, the chunk's place in the plane a scalar-prefetched
    iota), where the CPU's form holds the jnp keystream."""
    import jax
    import numpy as np

    from grapevine_tpu.analysis.jaxpr_walk import cipher_form
    from grapevine_tpu.analysis.oblint import analyze
    from grapevine_tpu.engine import expiry
    from grapevine_tpu.engine.state import init_engine

    state = jax.eval_shape(lambda: init_engine(ecfg, 0))
    scalar = jax.ShapeDtypeStruct((), np.uint32)
    with cipher_form(ecfg, kernel) as ecfg:
        rep = analyze(
            lambda st, now, per, nh: expiry.expiry_sweep(
                ecfg, st, now, per, nh),
            {"state": state, "now": scalar, "period": scalar,
             "now_hi": scalar},
            secrets=expiry.OBLINT_SECRETS,
            allowlist=allowlist,
            name=f"expiry_sweep/{name}" + ("/kernel" if kernel else ""),
        )
    # else the walk saw the other form
    assert bool(rep.census.get("pallas_call")) == kernel, rep.census
    return rep


def _small_oram_cfg(recursive: bool, k: int):
    from grapevine_tpu.oram.path_oram import OramConfig
    from grapevine_tpu.oram.posmap import derive_posmap_spec

    pm = derive_posmap_spec(16, top_cache_levels=k) if recursive else None
    return OramConfig(
        height=4, value_words=4, n_blocks=16, cipher_rounds=8,
        posmap=pm, top_cache_levels=k,
    )


def audit_oram_round(allowlist, recursive: bool, k: int):
    """Taint-audit the library sub-rounds standalone: oram_round (and
    through it lookup_remap_round) at a small geometry."""
    import jax
    import jax.numpy as jnp

    from grapevine_tpu.analysis.oblint import analyze
    from grapevine_tpu.oram import round as oround
    from grapevine_tpu.oram.path_oram import init_oram

    cfg = _small_oram_cfg(recursive, k)
    state = jax.eval_shape(lambda: init_oram(cfg, jax.random.PRNGKey(0)))
    b = 4

    def sds(*sh):
        return jax.ShapeDtypeStruct(sh, jnp.uint32)

    def apply_batch(vals0, present0):
        return jnp.sum(vals0, axis=1), vals0, present0

    def run(state, idxs, new_leaves, dummy_leaves, pm_new_leaves,
            pm_dummy_leaves):
        return oround.oram_round(
            cfg, state, idxs, new_leaves, dummy_leaves, apply_batch,
            pm_new_leaves=pm_new_leaves if recursive else None,
            pm_dummy_leaves=pm_dummy_leaves if recursive else None,
        )

    return analyze(
        run,
        {"state": state, "idxs": sds(b), "new_leaves": sds(b),
         "dummy_leaves": sds(b), "pm_new_leaves": sds(b),
         "pm_dummy_leaves": sds(b)},
        secrets=oround.OBLINT_SECRETS,
        allowlist=allowlist,
        name=f"oram_round/{'rec' if recursive else 'flat'}_k{k}",
    )


def audit_lookup_remap(allowlist, recursive: bool):
    """Taint-audit lookup_remap_round standalone against ITS OWN
    anchors (oram/posmap.py OBLINT_SECRETS — the occurrence masks are
    secrets here, which the engine-round audit derives internally)."""
    import jax
    import jax.numpy as jnp

    from grapevine_tpu.analysis.oblint import analyze
    from grapevine_tpu.oram import posmap as pmod
    from grapevine_tpu.oram.path_oram import OramConfig
    from grapevine_tpu.oram.posmap import derive_posmap_spec, init_posmap

    pm = derive_posmap_spec(16) if recursive else None
    cfg = OramConfig(height=4, value_words=4, n_blocks=16, posmap=pm)
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

    return analyze(
        run,
        {"pm_state": pm_state, "idxs": sds(b), "new_leaves": sds(b),
         "dummy_leaves": sds(b), "first_occ": sds(b, dt=jnp.bool_),
         "last_occ": sds(b, dt=jnp.bool_), "pm_new_leaves": sds(b),
         "pm_dummy_leaves": sds(b)},
        secrets=pmod.OBLINT_SECRETS,
        allowlist=allowlist,
        name=f"lookup_remap/{'rec' if recursive else 'flat'}",
    )


def audit_dma_write_back():
    """The write-back of a wide value plane as a TPU traces it: the
    row-placement kernel of oblivious/pallas_place.py under
    ``_path_scatter``, on one chip and under ``shard_map``. With the
    CONTENTS secret (the rows written and the plane written into) and
    the allowlist empty, nothing may reach a sink: the kernel's DMA
    targets and its skip predicate are functions of the path, the owner
    mask and the chip's index alone. (That the path itself may decide
    them is the scatter's reviewed argument, allowlist.py
    ``scatter@oram/path_oram.py:_path_scatter``; the whole-round audits
    above run on the CPU, which keeps the jnp scatter, and hold that
    form to it.)"""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from grapevine_tpu.analysis.jaxpr_walk import as_a_tpu_traces
    from grapevine_tpu.analysis.oblint import analyze
    from grapevine_tpu.oram.path_oram import _path_scatter
    from grapevine_tpu.parallel.mesh import TREE_AXIS, make_mesh

    def sds(shape, dtype=jnp.uint32):
        return jax.ShapeDtypeStruct(shape, dtype)

    n, rows = 64, 20
    args = {"tree": sds((n, 8, 128)), "path_b": sds((rows,)),
            "vals": sds((rows, 1024)), "owner": sds((rows,), jnp.bool_)}
    forms = {"one_chip": lambda tree, path_b, vals, owner: _path_scatter(
        tree, path_b, vals, None, owner)}
    if len(jax.devices()) >= 2:
        forms["sharded"] = jax.shard_map(
            lambda tree, path_b, vals, owner: _path_scatter(
                tree, path_b, vals, TREE_AXIS, owner),
            mesh=make_mesh(jax.devices()[:2]),
            in_specs=(P(TREE_AXIS), P(), P(), P()), out_specs=P(TREE_AXIS),
            check_vma=False,
        )
    reports = []
    with as_a_tpu_traces():
        for form, fn in forms.items():
            rep = analyze(fn, args, secrets=("tree", "vals"), allowlist=(),
                          name=f"dma_write_back/{form}")
            # else the kernel did not engage and the walk saw the scatter
            assert rep.census.get("dma_start"), rep.census
            reports.append(rep)
    return reports


def census_variants(ecfg):
    """Adversarially different CONCRETE batches for the program-equality
    check: the full engine round must trace to the identical program
    whatever the ops are (the legacy checkers' constants-baked-in
    stance, lifted to the whole round)."""
    import numpy as np

    from grapevine_tpu.engine.state import (
        ID_WORDS, KEY_WORDS, PAYLOAD_WORDS,
    )

    b = ecfg.batch_size

    def batch(rt, fill):
        rng = np.random.default_rng(fill + 1)

        def col(w):
            return (
                rng.integers(1, 2**31, (b, w)).astype(np.uint32)
                if fill else np.zeros((b, w), np.uint32)
            )

        return {
            "req_type": np.full((b,), rt, np.uint32),
            "auth": col(KEY_WORDS), "msg_id": col(ID_WORDS),
            "recipient": col(KEY_WORDS), "payload": col(PAYLOAD_WORDS),
            "now": np.uint32(1000), "now_hi": np.uint32(0),
        }

    dup = batch(1, fill=3)
    dup["recipient"][:] = dup["recipient"][0]  # every op same recipient
    dup["msg_id"][:] = dup["msg_id"][0]
    out = {
        "all_padding": batch(0, fill=0),
        "all_create": batch(1, fill=1),
        "all_read_dup_ids": dup,
        "mixed": {**batch(2, fill=2),
                  "req_type": (np.arange(b) % 5).astype(np.uint32)},
    }
    # device constants, not host ndarrays: the engine indexes batch
    # columns with traced values, which numpy arrays reject
    import jax.numpy as jnp

    return {
        vname: {k: jnp.asarray(v) for k, v in b.items()}
        for vname, b in out.items()
    }


def census_equal_engine(ecfg, name: str):
    import jax

    from grapevine_tpu.analysis.oblint import census_equal
    from grapevine_tpu.engine import round_step
    from grapevine_tpu.engine.state import init_engine

    state = jax.eval_shape(lambda: init_engine(ecfg, 0))
    variants = {
        vname: (
            lambda st, b=b: round_step.engine_round_step(ecfg, st, b),
            (state,),
        )
        for vname, b in census_variants(ecfg).items()
    }
    return census_equal(variants, name=f"engine_round/{name}")


def run_mutant_controls(allowlist) -> list:
    """Every seeded mutant must FAIL under the production allowlists.

    One shared runner for BOTH analyzers (ISSUE 14): the oblint leak
    mutants under the taint allowlist and the rangelint overflow mutants
    under the range allowlist — a single tier-1 gate proves both
    analyzers still have teeth."""
    from grapevine_tpu.analysis.allowlist import RANGE_ALLOWLIST
    from grapevine_tpu.analysis.mutants import (
        control_failures, run_mutants, run_range_mutants,
    )

    log = lambda line: print(f"[check_oblivious] {line}")  # noqa: E731
    return control_failures(
        run_mutants(allowlist), "mutant", log
    ) + control_failures(
        run_range_mutants(RANGE_ALLOWLIST), "range mutant", log
    )


def run_locklint() -> list:
    from grapevine_tpu.analysis.locklint import lint_repo

    vs = lint_repo(os.path.join(REPO, "grapevine_tpu"))
    for v in vs:
        print(f"[check_oblivious] locklint VIOLATION {v}")
    return [str(v) for v in vs]


def run_audit(combos, allowlist=None, with_census="first",
              with_subrounds: bool = True, verbose: bool = False):
    """Sweep the taint audit; returns (problems, allowlist_hits).

    ``with_census``: "first" = program-equality on the lead combo (the
    default tier), "all" = on every combo (--full), False = skip."""
    from grapevine_tpu.analysis.allowlist import ENGINE_ALLOWLIST

    if allowlist is None:
        allowlist = ENGINE_ALLOWLIST
    problems: list = []
    hits: dict = {}

    def absorb(rep):
        for k, n in rep.allowed.items():
            hits[k] = hits.get(k, 0) + n
        if verbose or rep.violations:
            print(rep.summary())
        problems.extend(f"{rep.name}: {v}" for v in rep.violations)

    for pmi, k in combos:
        name = f"{pmi}_k{k}"
        absorb(audit_engine_round(_small_engine(pmi, k), allowlist, name))
        for kernel in (False, True):
            absorb(audit_expiry_sweep(_small_engine(pmi, k),
                                      allowlist, name, kernel))
        if with_subrounds:
            absorb(audit_oram_round(
                allowlist, recursive=(pmi == "recursive"), k=k,
            ))
            absorb(audit_lookup_remap(
                allowlist, recursive=(pmi == "recursive"),
            ))
    if with_subrounds:
        for rep in audit_dma_write_back():
            absorb(rep)
    if with_census:
        census_combos = combos if with_census == "all" else combos[:1]
        for pmi, k in census_combos:
            for v in census_equal_engine(
                _small_engine(pmi, k), f"{pmi}_k{k}",
            ):
                problems.append(str(v))
    return problems, hits


def check_allowlist_reachability(hits: dict) -> list:
    """Every reviewed entry must fire somewhere in the sweep."""
    from grapevine_tpu.analysis.allowlist import ENGINE_ALLOWLIST

    dead = [e for e in ENGINE_ALLOWLIST if e.key not in hits]
    return [
        f"dead allowlist entry {e.key!r} ({e.reason!r}): never reached "
        "in any swept knob combination — delete it or sweep the combo "
        "that exercises it (dead entries rot into blanket permissions)"
        for e in dead
    ]


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tier-1 budget: one combo, engine trace + "
                         "mutants + locklint; no census sweep, no "
                         "reachability check")
    ap.add_argument("--full", action="store_true",
                    help="census equality on every combo of the knob "
                         "matrix, not only the first (the -m slow tier)")
    ap.add_argument("--skip-mutants", action="store_true")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)

    from grapevine_tpu.analysis.allowlist import ENGINE_ALLOWLIST

    problems: list = []
    if args.smoke:
        pmi, k = SMOKE_COMBO
        rep = audit_engine_round(
            _small_engine(pmi, k), ENGINE_ALLOWLIST, f"{pmi}_k{k}",
        )
        print(rep.summary())
        problems.extend(f"{rep.name}: {v}" for v in rep.violations)
    else:
        swept, hits = run_audit(
            DEFAULT_COMBOS, with_census="all" if args.full else "first",
            with_subrounds=True, verbose=args.verbose,
        )
        problems.extend(swept)
        problems.extend(check_allowlist_reachability(hits))

    if not args.skip_mutants:
        problems.extend(run_mutant_controls(ENGINE_ALLOWLIST))
    problems.extend(run_locklint())

    if problems:
        print(f"[check_oblivious] FAIL: {len(problems)} problem(s)")
        for p in problems:
            print(f"  - {p}")
        return 1
    scope = (
        "smoke combo" if args.smoke
        else "knob matrix, census on all" if args.full else "knob matrix"
    )
    reach = "" if args.smoke else "; every allowlist entry reachable"
    teeth = "" if args.skip_mutants else "; all mutants caught"
    print(f"[check_oblivious] PASS ({scope}): no secret-derived access "
          f"decision outside the reviewed allowlist{reach}{teeth}; "
          "lock discipline holds")
    return 0


if __name__ == "__main__":
    sys.exit(main())
