#!/usr/bin/env python
"""CI gate: the tree-top-cached, level-dense round is index-blind AND
moves exactly the HBM rows its layout says: the dense heap range below
the cache once, and per-path rows only under the dense levels.

Two claims, both jaxpr-level (the PR-3/5/7 audit pattern — trace-time
facts, not runtime sampling):

1. **Index-independence.** Trace ``oram_round`` with the batch indices
   baked in as concrete constants for adversarially different index
   sets (all distinct, all identical, all dummy, mixed duplicates) and
   assert the full primitive census is IDENTICAL across them, with no
   data-dependent control flow anywhere. The tree-top cache moves the
   top k levels into private cache planes — this proves the move never
   introduces an index-dependent shortcut (e.g. skipping the cache
   concat for dummy batches).

2. **HBM row-count accounting.** Every gather/scatter whose operand is
   one of the big HBM tree planes (``tree_idx`` u32[n·Z], ``tree_val``
   u32[n, Z·V] or, a wide row, u32[n, tiles, 128], ``nonces`` u32[n, 2],
   ``tree_leaf`` u32[n·Z]), and the row-placement kernel that stands in
   for a wide plane's scatter on a TPU (counted as rows written, one
   DMA each: analysis/jaxpr_walk.py ``plane_rows``), must move
   exactly ``(2^Ld − 2^k) + B·(path_len − Ld)`` bucket rows, where
   ``Ld = clamp(floor(log2 B) + 1, k, path_len)`` is the count of
   levels the batch covers (ISSUE 26: such a level is moved whole,
   once, at constant addresses; only the levels under them move one
   row per path). ``k=0`` is the positive control: the same census
   shows ``2^k − 1`` more rows — the cached buckets — proving the
   counter sees the traffic the cache cuts; and three geometries pin
   the three regimes of the rule (``Ld = path_len``: no per-path row
   at all; ``k < Ld < path_len``; ``Ld = k``: no dense HBM row). At
   ``k>0`` the cache planes must pass through the round whole: no
   gather or scatter names them (a per-path read of a cached level
   would be one), and the round must return NEW cache planes (the top
   levels are really evicted into, not passed around).

Wired into tier-1 via tests/test_tree_cache.py; standalone:
``python tools/check_tree_cache_oblivious.py``.

Since ISSUE 12 the equation walk / census / plane row accounting live in
the shared analyzer core (grapevine_tpu/analysis/jaxpr_walk.py) — this
tool, the posmap gate, and the taint analyzer cannot drift. CLI and
exit codes are unchanged. ISSUE 12 also closed a matrix gap: the
``k=0, posmap_impl=recursive`` cell now has its own always-on census
(:func:`check_k0_recursive_census`) instead of riding only the heavy
``-m slow`` recursive audit.
"""

from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from grapevine_tpu.analysis.jaxpr_walk import (  # noqa: E402
    ACCESS_PRIMS as _ACCESS_PRIMS,  # noqa: F401 - part of the gate's API
    CONTROL_PRIMS as _CONTROL_PRIMS,
    census as _census,
    plane_rows as _shared_plane_rows,
)


def _index_sets(cfg, b: int):
    import numpy as np

    rng = np.random.default_rng(11)
    return {
        "distinct": (np.arange(b) % cfg.blocks).astype(np.uint32),
        "all_same": np.zeros(b, np.uint32),
        "all_dummy": np.full(b, cfg.dummy_index, np.uint32),
        "mixed_dups": rng.integers(0, cfg.blocks + 1, b).astype(np.uint32),
    }


def _trace_round(cfg, idxs, b: int):
    """Jaxpr of one whole ORAM round with ``idxs`` concrete constants."""
    import jax
    import jax.numpy as jnp

    from grapevine_tpu.oram.path_oram import init_oram
    from grapevine_tpu.oram.round import oram_round

    state = jax.eval_shape(lambda: init_oram(cfg, jax.random.PRNGKey(0)))
    cidxs = jnp.asarray(idxs)
    recursive = cfg.posmap is not None

    def apply_batch(vals0, present0):
        return jnp.sum(vals0, axis=1), vals0, present0

    u32 = jnp.uint32
    lf = jax.ShapeDtypeStruct((b,), u32)

    def run(st, nl, dl, pm_nl, pm_dl):
        return oram_round(
            cfg, st, cidxs, nl, dl, apply_batch,
            pm_new_leaves=pm_nl if recursive else None,
            pm_dummy_leaves=pm_dl if recursive else None,
        )

    return jax.make_jaxpr(run)(state, lf, lf, lf, lf)


def _tree_planes(cfg) -> dict:
    """This geometry's HBM tree planes (and cache planes at k>0) in the
    shared ``plane_rows`` declaration format: name -> (shape, divisor)."""
    z, sw = cfg.bucket_slots, cfg.stored_row_words
    n = cfg.n_buckets_padded
    cb = cfg.cache_buckets
    # tree_idx/tree_leaf are stored flat [n·Z] but fetched/written
    # through bucket-axis [n, Z] reshape views since ISSUE 14 (the u32
    # certified-geometry refactor), so the gather/scatter operands the
    # accounting matches on are the 2-D views at divisor 1
    planes = {
        "tree_idx": ((n, z), 1),
        "tree_val": ((n, *cfg.stored_row_shape), 1),
        "nonces": ((n, 2), 1),
    }
    if cfg.posmap is not None:
        planes["tree_leaf"] = ((n, z), 1)
    if cb:
        planes["cache_idx"] = ((cb * z,), z)
        planes["cache_val"] = ((cb, sw), 1)
        if cfg.posmap is not None:
            planes["cache_leaf"] = ((cb * z,), z)
    return planes


def _plane_rows(jaxpr, cfg) -> dict:
    """Rows moved per HBM tree plane (and cache plane): the shared
    analyzer core's accounting over this geometry's plane declarations."""
    return _shared_plane_rows(jaxpr, _tree_planes(cfg))


def dense_round_rows(b: int, plen: int, k: int) -> int:
    """HBM bucket rows one round of ``b`` paths moves per plane and
    direction, from the layout's rule alone (this gate's own
    arithmetic — never read off the program's ``OramConfig``)."""
    ld = min(max(b.bit_length(), k), plen)  # floor(log2 b) + 1, clamped
    return ((1 << ld) - (1 << k)) + b * (plen - ld)


def check_tree_cache_schedule(
    b: int = 8, height: int = 5, verbose: bool = False, recursive: bool = False
) -> dict:
    """Run both audits over k ∈ {0, 2}; raises AssertionError on any
    violation, returns the per-k row accounting."""
    from grapevine_tpu.oram.path_oram import OramConfig, OramState
    from grapevine_tpu.oram.posmap import derive_posmap_spec

    out = {}
    for k in (0, 2):
        pm = (
            derive_posmap_spec(1 << height, top_cache_levels=k)
            if recursive
            else None
        )
        cfg = OramConfig(
            height=height, value_words=8, n_blocks=1 << height,
            cipher_rounds=8, top_cache_levels=k, posmap=pm,
        )
        plen = cfg.path_len
        want = dense_round_rows(b, plen, k)

        # -- 1. index-independence ---------------------------------------
        censuses = {
            iname: _census(_trace_round(cfg, idxs, b))
            for iname, idxs in _index_sets(cfg, b).items()
        }
        base_name, base = next(iter(censuses.items()))
        for iname, c in censuses.items():
            assert c == base, (
                f"k={k}: cached round traces a DIFFERENT program for "
                f"index set {iname!r} vs {base_name!r}: "
                f"{(c - base) + (base - c)} — the access schedule "
                "depends on the queried indices"
            )
        n_control = sum(base[p] for p in _CONTROL_PRIMS)
        assert n_control == 0, (
            f"k={k}: data-dependent control flow in the round "
            f"({ {p: base[p] for p in _CONTROL_PRIMS if base[p]} })"
        )

        # -- 2. HBM row accounting ---------------------------------------
        jaxpr = _trace_round(cfg, _index_sets(cfg, b)["mixed_dups"], b)
        rows = _plane_rows(jaxpr, cfg)
        for pname in ("tree_idx", "tree_val", "nonces"):
            moved = rows[pname]
            assert moved, f"k={k}: no accesses seen on {pname}"
            bad = [r for _, r in moved if r != want]
            assert not bad, (
                f"k={k}: {pname} moves {sorted(set(bad))} bucket rows "
                f"per round — every HBM tree access must move exactly "
                f"(2^Ld − 2^k) + B·(path_len − Ld) = {want} "
                f"(B={b}, path_len={plen})"
            )
        if recursive:
            assert rows["tree_leaf"], f"k={k}: no tree_leaf accesses"
            assert all(r == want for _, r in rows["tree_leaf"]), (
                f"k={k}: tree_leaf rows diverge from {want}"
            )
        if k:
            # the cached levels are the first dense levels: their planes
            # join and leave the working set whole. A gather or scatter
            # on one is a per-path read of a cached level come back.
            for pname in ("cache_idx", "cache_val"):
                assert not rows[pname], (
                    f"k={k}: {pname} is gathered/scattered "
                    f"({rows[pname]}) — the cached levels must pass "
                    "through the round as whole planes"
                )
                # flat pytree order = field order up to the posmap (the
                # only nested field, after every cache plane)
                i = OramState._fields.index(pname)
                assert jaxpr.jaxpr.outvars[i] is not jaxpr.jaxpr.invars[i], (
                    f"k={k}: the round returns its input {pname} — the "
                    "cached levels are not evicted into"
                )
        out[f"k{k}"] = {
            p: sorted({r for _, r in rs}) for p, rs in rows.items() if rs
        }
        if verbose:
            print(f"k={k} ({'recursive' if recursive else 'flat'}): "
                  f"{out[f'k{k}']}")

    # positive control across k: the counter must SEE the cut — the
    # 2^k − 1 cached buckets leave the HBM planes (k=2 stays under the
    # default geometry's dense levels, so the cut is dense rows)
    full = out["k0"]["tree_val"][0]
    cut = out["k2"]["tree_val"][0]
    assert (full, cut) == (dense_round_rows(b, height + 1, 0),
                           dense_round_rows(b, height + 1, 2)) and (
        full - cut == 3
    ), (
        f"positive control failed: k=0 moves {full} rows, k=2 moves "
        f"{cut} — expected the 2^2−1 = 3 cached buckets between them"
    )
    return out


def check_dense_regimes(verbose: bool = False) -> dict:
    """The three regimes of the level-dense rule, each at a geometry of
    its own (flat posmap, cipher on), rows per HBM plane against the
    rule's arithmetic AND against the per-path count it replaces:

    - ``Ld = path_len`` (tree smaller than the batch): the whole tree
      once, no per-path row — far under ``B·(path_len−k)``;
    - ``k < Ld < path_len``: the dense range plus per-path rows;
    - ``Ld = k`` (batch smaller than the cache top): no dense HBM row,
      exactly the per-path count — the rule may never move MORE than
      the layout it replaced.
    """
    from grapevine_tpu.oram.path_oram import OramConfig

    out = {}
    for name, b, height, k, want_ld in (
        ("tree_under_batch", 16, 3, 1, 4),
        ("mixed", 4, 5, 1, 3),
        ("batch_under_cache", 2, 5, 3, 3),
    ):
        cfg = OramConfig(height=height, value_words=8,
                         n_blocks=1 << height, cipher_rounds=8,
                         top_cache_levels=k)
        plen = cfg.path_len
        want = dense_round_rows(b, plen, k)
        assert cfg.dense_levels(b) == want_ld, (name, cfg.dense_levels(b))
        assert cfg.fetched_bucket_rows(b) == want, name
        per_path = b * (plen - k)
        assert want <= per_path, (
            f"{name}: the dense rule moves {want} rows where per-path "
            f"moved {per_path}"
        )
        rows = _plane_rows(
            _trace_round(cfg, _index_sets(cfg, b)["mixed_dups"], b), cfg
        )
        for pname in ("tree_idx", "tree_val", "nonces"):
            got = sorted({r for _, r in rows[pname]})
            assert got == [want], (
                f"{name}: {pname} moves {got} rows per access op — want "
                f"{want} (B={b}, path_len={plen}, k={k}, Ld={want_ld})"
            )
        out[name] = {"rows": want, "per_path": per_path, "Ld": want_ld}
        if verbose:
            print(f"dense regime {name}: {out[name]}")
    assert out["tree_under_batch"]["rows"] == 15 - 1
    assert out["batch_under_cache"]["rows"] == (
        out["batch_under_cache"]["per_path"]
    )
    return out


def check_k0_recursive_census(b: int = 4, height: int = 5) -> dict:
    """The matrix cell the pre-ISSUE-12 wiring missed: ``k=0`` with
    ``posmap_impl=recursive``.

    Tier-1 ran the full two-claim audit flat-only (the recursive variant
    rode ``-m slow``), so the uncached-recursive round — the exact
    program a `--posmap-impl recursive --tree-top-cache-levels 0` server
    runs — had no always-on index-blindness census. This runs claim 1
    (identical census across adversarial index sets, zero data-dependent
    control flow) plus the tree_leaf-plane row accounting for that one
    cell at a deliberately small geometry; returns the per-plane rows."""
    from grapevine_tpu.oram.path_oram import OramConfig
    from grapevine_tpu.oram.posmap import derive_posmap_spec

    cfg = OramConfig(
        height=height, value_words=8, n_blocks=1 << height,
        cipher_rounds=8, top_cache_levels=0,
        posmap=derive_posmap_spec(1 << height, top_cache_levels=0),
    )
    censuses = {
        iname: _census(_trace_round(cfg, idxs, b))
        for iname, idxs in _index_sets(cfg, b).items()
    }
    base_name, base = next(iter(censuses.items()))
    for iname, c in censuses.items():
        assert c == base, (
            f"k=0 recursive round traces a DIFFERENT program for index "
            f"set {iname!r} vs {base_name!r}: {(c - base) + (base - c)}"
        )
    n_control = sum(base[p] for p in _CONTROL_PRIMS)
    assert n_control == 0, (
        f"k=0 recursive: data-dependent control flow "
        f"({ {p: base[p] for p in _CONTROL_PRIMS if base[p]} })"
    )
    rows = _plane_rows(
        _trace_round(cfg, _index_sets(cfg, b)["mixed_dups"], b), cfg
    )
    # k=0: every level on the HBM planes — the dense ones once
    want = dense_round_rows(b, cfg.path_len, 0)
    for pname in ("tree_idx", "tree_val", "nonces", "tree_leaf"):
        moved = rows[pname]
        assert moved, f"k=0 recursive: no accesses seen on {pname}"
        bad = [r for _, r in moved if r != want]
        assert not bad, (
            f"k=0 recursive: {pname} moves {sorted(set(bad))} rows — "
            f"want (2^Ld − 1) + B·(path_len − Ld) = {want}"
        )
    assert "cache_idx" not in rows, "k=0 must declare no cache planes"
    return {p: sorted({r for _, r in rs}) for p, rs in rows.items() if rs}


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--height", type=int, default=5)
    args = ap.parse_args(argv)
    for recursive in (False, True):
        out = check_tree_cache_schedule(
            b=args.batch, height=args.height, verbose=True,
            recursive=recursive,
        )
        print(f"[check_tree_cache_oblivious] recursive={recursive}: OK {out}")
    out = check_k0_recursive_census(b=4, height=5)
    print(f"[check_tree_cache_oblivious] k0-recursive cell: OK {out}")
    out = check_dense_regimes(verbose=True)
    print(f"[check_tree_cache_oblivious] dense regimes: OK {out}")
    print("[check_tree_cache_oblivious] PASS: cached round is index-blind "
          "and HBM tree traffic is exactly (2^Ld − 2^k) + B·(path_len − Ld) "
          "rows per plane")
    return 0


if __name__ == "__main__":
    sys.exit(main())
