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
   u32[n, Z·V], ``nonces`` u32[n, 2], ``tree_leaf`` u32[n·Z]) must move
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
    z, v = cfg.bucket_slots, cfg.value_words
    n = cfg.n_buckets_padded
    cb = cfg.cache_buckets
    # tree_idx/tree_leaf are stored flat [n·Z] but fetched/written
    # through bucket-axis [n, Z] reshape views since ISSUE 14 (the u32
    # certified-geometry refactor), so the gather/scatter operands the
    # accounting matches on are the 2-D views at divisor 1
    planes = {
        "tree_idx": ((n, z), 1),
        "tree_val": ((n, z * v), 1),
        "nonces": ((n, 2), 1),
    }
    if cfg.posmap is not None:
        planes["tree_leaf"] = ((n, z), 1)
    if cb:
        planes["cache_idx"] = ((cb * z,), z)
        planes["cache_val"] = ((cb, z * v), 1)
        if cfg.posmap is not None:
            planes["cache_leaf"] = ((cb * z,), z)
    return planes


def _plane_rows(jaxpr, cfg) -> dict:
    """Rows moved per HBM tree plane (and cache plane): the shared
    analyzer core's accounting over this geometry's plane declarations."""
    return _shared_plane_rows(jaxpr, _tree_planes(cfg))


def dense_round_rows(b: int, plen: int, k: int) -> int:
    """HBM bucket rows one E=1 round of ``b`` paths moves per plane and
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


def _evict_cfg(b: int, height: int, k: int, window: int,
               recursive: bool = False):
    from grapevine_tpu.oram.path_oram import OramConfig
    from grapevine_tpu.oram.posmap import derive_posmap_spec

    pm = (
        derive_posmap_spec(1 << height, top_cache_levels=k,
                           evict_window=window, evict_fetch_count=b)
        if recursive
        else None
    )
    return OramConfig(
        height=height, value_words=8, n_blocks=1 << height,
        cipher_rounds=8, top_cache_levels=k, posmap=pm,
        evict_window=window, evict_fetch_count=b,
        evict_buffer_slots=4 * b * window,
    )


def check_evict_round_accounting(
    b: int = 8, height: int = 7, k: int = 2, window: int = 2,
    verbose: bool = False, recursive: bool = False,
) -> dict:
    """The delayed-eviction (PR 15) extension of this gate: the E-round
    schedule's HBM row accounting, trace-level.

    Three claims over one ``evict_window = E`` geometry:

    1. **Fetch rounds are read-only on HBM.** The fetch-only round's
       census is identical across adversarial index sets (index-blind,
       claim 1 of the per-round audit), its tree-plane GATHERS move
       exactly ``B·(path_len−k)`` bucket rows per plane — the
       per-path fetch (the E=1 round's level-dense layout is not this
       program's: ISSUE 26 left the delayed pair as it was) — and it
       contains ZERO scatters
       on any tree/nonce/cache plane: the scatter+encrypt half of the
       round is really gone from the steady state.
    2. **The flush writes exactly the window, deduplicated.** One
       ``oram_flush`` scatters exactly ``flush_target_slots =
       min(E·B·path_len, n_buckets_padded)`` bucket rows per plane —
       the union of the window's fetched paths written ONCE each
       (write transcript ≡ the deduplicated union of the window's read
       transcripts; the ``min`` is the amortization: past tree
       saturation, extra window rounds add fetch traffic but no write
       traffic) — with ZERO tree-plane gathers (the live rows were
       already pulled into the buffer at fetch time). Cache planes see
       the same ``t``-row shape at k>0 (cached targets peel off by the
       heap-prefix mask).
    3. **Recipient-independence of the cadence.** Both programs trace
       with the batch indices baked in as constants; identical censuses
       across index sets plus a bucket-target set that is a pure
       function of the (public) leaves means nothing about which
       recipients were touched can move a row or a flush.

    Returns the per-program row accounting.
    """
    from grapevine_tpu.oram.round import flush_target_slots

    cfg = _evict_cfg(b, height, k, window, recursive)
    plen = cfg.path_len
    want_fetch = b * (plen - k)
    want_flush = flush_target_slots(cfg)
    # the audit needs the UNSATURATED dedup regime: at t =
    # n_buckets_padded the compacted output planes coincide in shape
    # with the HBM tree planes and shape-based attribution would count
    # private scatters as tree traffic (a false positive, not a leak).
    # The saturated cap is pure arithmetic, pinned below.
    assert want_flush < cfg.n_buckets_padded, (
        "audit geometry must keep the flush target set unsaturated "
        f"(t={want_flush} vs n_buckets_padded={cfg.n_buckets_padded}) — "
        "raise height or lower window/batch"
    )
    # the saturation clamp itself (the amortization bound): arithmetic,
    # no trace needed
    sat = _evict_cfg(b, 3, 0, 8, False)
    assert flush_target_slots(sat) == sat.n_buckets_padded

    # -- 1. fetch round: index-blind + read-only ------------------------
    censuses = {
        iname: _census(_trace_round(cfg, idxs, b))
        for iname, idxs in _index_sets(cfg, b).items()
    }
    base_name, base = next(iter(censuses.items()))
    for iname, c in censuses.items():
        assert c == base, (
            f"E={window}: fetch round traces a DIFFERENT program for "
            f"index set {iname!r} vs {base_name!r}: "
            f"{(c - base) + (base - c)}"
        )
    n_control = sum(base[p] for p in _CONTROL_PRIMS)
    assert n_control == 0, (
        f"E={window}: data-dependent control flow in the fetch round "
        f"({ {p: base[p] for p in _CONTROL_PRIMS if base[p]} })"
    )
    rows = _plane_rows(
        _trace_round(cfg, _index_sets(cfg, b)["mixed_dups"], b), cfg
    )
    fetch_acct = {}
    tree_planes = ["tree_idx", "tree_val", "nonces"]
    if recursive:
        tree_planes.append("tree_leaf")
    for pname in tree_planes:
        moved = rows[pname]
        gathers = [r for op, r in moved if op == "gather"]
        scatters = [(op, r) for op, r in moved if op != "gather"]
        assert not scatters, (
            f"E={window}: fetch round SCATTERS to {pname} ({scatters}) "
            "— the steady-state round must be read-only on the HBM tree"
        )
        if pname != "nonces" or cfg.encrypted:
            assert gathers and all(r == want_fetch for r in gathers), (
                f"E={window}: {pname} fetch gathers move "
                f"{sorted(set(gathers))} rows — want exactly "
                f"B·(path_len−k) = {want_fetch}"
            )
        fetch_acct[pname] = sorted(set(gathers))
    if k:
        for pname in ("cache_idx", "cache_val"):
            moved = rows[pname]
            assert all(op == "gather" for op, _ in moved), (
                f"E={window}: fetch round writes the cache plane "
                f"{pname} — cached levels flush with everything else"
            )

    # -- 2. flush: writes exactly the window, reads nothing -------------
    import jax

    from grapevine_tpu.oram.path_oram import init_oram
    from grapevine_tpu.oram.round import oram_flush

    state = jax.eval_shape(lambda: init_oram(cfg, jax.random.PRNGKey(0)))
    fl_jaxpr = jax.make_jaxpr(lambda st: oram_flush(cfg, st))(state)
    frows = _shared_plane_rows(fl_jaxpr, _tree_planes(cfg))
    flush_acct = {}
    for pname in tree_planes:
        moved = frows[pname]
        gathers = [r for op, r in moved if op == "gather"]
        scatters = [r for op, r in moved if op != "gather"]
        assert not gathers, (
            f"E={window}: flush GATHERS from {pname} — the window's "
            "live rows were already pulled into the buffer at fetch "
            "time; a flush-time read is a second, unaccounted pass"
        )
        if pname != "nonces" or cfg.encrypted:
            assert scatters and all(r == want_flush for r in scatters), (
                f"E={window}: {pname} flush scatters move "
                f"{sorted(set(scatters))} rows — want exactly "
                f"flush_target_slots = min(E·B·path_len, "
                f"n_buckets_padded) = {want_flush}"
            )
        flush_acct[pname] = sorted(set(scatters))
    if k:
        # recursive geometries: the INNER tree's cache planes share the
        # outer cache planes' shape (both (2^k−1)·Z), so shape-based
        # attribution folds the inner flush's cache writes in — accept
        # the inner t-row shape alongside the outer one
        want_cache = {want_flush}
        if recursive:
            from grapevine_tpu.oram.posmap import inner_oram_config

            want_cache.add(flush_target_slots(inner_oram_config(cfg.posmap)))
        for pname in ("cache_idx", "cache_val"):
            moved = frows[pname]
            scatters = [r for op, r in moved if op != "gather"]
            assert scatters and set(scatters) <= want_cache and (
                want_flush in scatters
            ), (
                f"E={window}: cache plane {pname} flush moves "
                f"{moved} — want the t-row target shape(s) {want_cache}"
            )
    out = {"fetch": fetch_acct, "flush": flush_acct,
           "want_fetch_rows": want_fetch, "want_flush_rows": want_flush}
    if verbose:
        print(f"E={window} k={k} "
              f"({'recursive' if recursive else 'flat'}): {out}")
    return out


def _trace_sharded(cfg, what, mesh, idxs=None, b=0):
    """Jaxpr of one SHARDED fetch round or flush: the oram program wrapped
    in the same shard_map geometry the engine uses (parallel/mesh.py),
    so ``walk_eqns`` recurses into the shard body where every tree-plane
    operand carries its SHARD-LOCAL shape."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from grapevine_tpu.oram.path_oram import init_oram
    from grapevine_tpu.oram.round import oram_flush, oram_round
    from grapevine_tpu.parallel.mesh import TREE_AXIS, _oram_specs

    state = jax.eval_shape(lambda: init_oram(cfg, jax.random.PRNGKey(0)))
    specs = _oram_specs()
    if what == "flush":
        fn = jax.shard_map(
            lambda st: oram_flush(cfg, st, TREE_AXIS),
            mesh=mesh, in_specs=(specs,), out_specs=specs,
            check_vma=False,
        )
        return jax.make_jaxpr(fn)(state)
    cidxs = jnp.asarray(idxs)
    recursive = cfg.posmap is not None

    def apply_batch(vals0, present0):
        return jnp.sum(vals0, axis=1), vals0, present0

    def run(st, nl, dl, pm_nl, pm_dl):
        return oram_round(
            cfg, st, cidxs, nl, dl, apply_batch, axis_name=TREE_AXIS,
            pm_new_leaves=pm_nl if recursive else None,
            pm_dummy_leaves=pm_dl if recursive else None,
        )

    lf = jax.ShapeDtypeStruct((b,), jnp.uint32)
    fn = jax.shard_map(
        run, mesh=mesh, in_specs=(specs, P(), P(), P(), P()),
        out_specs=(specs, P(), P()), check_vma=False,
    )
    return jax.make_jaxpr(fn)(state, lf, lf, lf, lf)


def _local_tree_planes(cfg, n_shards: int) -> dict:
    """Shard-LOCAL plane declarations: the bucket axis shards as
    contiguous equal heap ranges, so each chip's tree/nonce operands are
    the full planes at ``n / n_shards`` rows; cache planes are
    replicated private state and keep their full shape."""
    planes = _tree_planes(cfg)
    out = {}
    for name, (shape, div) in planes.items():
        if name.startswith(("tree_", "nonces")):
            shape = (shape[0] // n_shards,) + tuple(shape[1:])
        out[name] = (shape, div)
    return out


def _unmasked_scatter_mutant(orig):
    """The seeded defect the sharded audit exists to catch: a sharded
    ``_path_scatter`` that keeps the dedup owner mask but DROPS the
    shard-ownership mask — every chip writes every target into its local
    plane at wrapped indices instead of dropping non-owned lanes, so the
    union across the mesh is no longer the single-chip flush."""
    import jax
    import jax.numpy as jnp

    def mutant(tree, path_b, new_vals, axis_name, owner=None):
        if axis_name is None:
            return orig(tree, path_b, new_vals, axis_name, owner)
        n_local = tree.shape[0]
        u32 = jnp.uint32
        base = (jax.lax.axis_index(axis_name) * n_local).astype(u32)
        loc = (path_b - base) % u32(n_local)  # wraps instead of dropping
        if owner is not None:
            loc = jnp.where(owner, loc, u32(n_local))
        return tree.at[loc].set(new_vals, mode="drop", unique_indices=True)

    return mutant


def check_sharded_evict_accounting(
    b: int = 6, height: int = 7, k: int = 2, window: int = 2,
    shards: int = 2, verbose: bool = False, recursive: bool = False,
    runtime: bool = True, _unmasked_scatter: bool = False,
) -> dict:
    """ISSUE-18 extension: the delayed-eviction schedule's accounting for
    the SHARDED program (parallel/mesh.py make_sharded_step/flush).

    Trace-level, per shard (walk_eqns recurses into the shard_map body,
    where operands carry shard-local shapes):

    1. **Per-shard fetch rounds are HBM-read-only at the uniform
       working-set shape.** Each chip's fetch round is index-blind
       (identical census across adversarial index sets, zero
       data-dependent control flow), its local tree-plane GATHER ops
       each carry exactly ``B·(path_len−k)`` rows — the full working-set
       shape, non-owned lanes masked, so per-chip row counts are a pure
       function of geometry, never of contents or ownership — and it
       contains ZERO scatters on any local tree/nonce plane.
    2. **Per-shard flush scatters carry exactly ``t`` rows.** Each
       chip's flush SCATTER ops carry all ``t = flush_target_slots``
       rows (the owner mask drops non-owned lanes via out-of-range
       targets — the static shape never shrinks), with ZERO local
       tree-plane gathers.

    Runtime, on a real mesh (the partition claim — where "sums to
    exactly the single-chip write set" lives):

    3. **Owner partition.** Running the window + flush sharded and
       single-chip from the same state: every bucket row the single-chip
       flush writes is written by EXACTLY ONE shard (its heap-range
       owner), the per-shard written-row counts sum to the single-chip
       count, and the assembled sharded state equals the single-chip
       state bit for bit.

    ``_unmasked_scatter=True`` seeds the control defect (shard mask
    dropped from the flush scatter) — the runtime partition check must
    FAIL; tests/test_evict.py pins both directions. ``runtime=False``
    runs only the (compile-free) trace claims — the always-on tier-1
    shape; the runtime partition + mutant ride ``-m slow`` and the
    standalone tool.
    """
    import jax

    from grapevine_tpu.oram.round import flush_target_slots
    from grapevine_tpu.parallel.mesh import make_mesh

    n_shards = min(shards, len(jax.devices()))
    mesh = make_mesh(jax.devices()[:n_shards])
    cfg = _evict_cfg(b, height, k, window, recursive)
    plen = cfg.path_len
    want_fetch = b * (plen - k)
    want_flush = flush_target_slots(cfg)
    n_local = cfg.n_buckets_padded // n_shards
    assert cfg.n_buckets_padded % n_shards == 0
    # shape-based attribution needs the local planes unambiguous: the
    # compacted flush working set is (t, ·) and the buffer is
    # (evict_buffer_slots, ·) — neither may coincide with a local tree
    # plane's (n/shards, ·) or private scatters count as tree traffic
    assert want_flush != n_local and cfg.evict_buffer_slots != n_local, (
        f"audit geometry ambiguity: t={want_flush} / buffer="
        f"{cfg.evict_buffer_slots} vs n_local={n_local} — pick b/height "
        "so the shard-local plane shape is unique"
    )

    # -- 1. per-shard fetch round: index-blind + read-only --------------
    censuses = {
        iname: _census(_trace_sharded(cfg, "round", mesh, idxs, b))
        for iname, idxs in _index_sets(cfg, b).items()
    }
    base_name, base = next(iter(censuses.items()))
    for iname, c in censuses.items():
        assert c == base, (
            f"shards={n_shards} E={window}: sharded fetch round traces "
            f"a DIFFERENT program for index set {iname!r} vs "
            f"{base_name!r}: {(c - base) + (base - c)}"
        )
    n_control = sum(base[p] for p in _CONTROL_PRIMS)
    assert n_control == 0, (
        f"shards={n_shards} E={window}: data-dependent control flow in "
        f"the sharded fetch round "
        f"({ {p: base[p] for p in _CONTROL_PRIMS if base[p]} })"
    )
    lplanes = _local_tree_planes(cfg, n_shards)
    rows = _shared_plane_rows(
        _trace_sharded(cfg, "round", mesh,
                       _index_sets(cfg, b)["mixed_dups"], b),
        lplanes,
    )
    tree_planes = ["tree_idx", "tree_val", "nonces"]
    if recursive:
        tree_planes.append("tree_leaf")
    fetch_acct = {}
    for pname in tree_planes:
        moved = rows[pname]
        gathers = [r for op, r in moved if op == "gather"]
        scatters = [(op, r) for op, r in moved if op != "gather"]
        assert not scatters, (
            f"shards={n_shards} E={window}: per-shard fetch round "
            f"SCATTERS to local {pname} ({scatters}) — the sharded "
            "steady-state round must be read-only on every chip's HBM"
        )
        assert gathers and all(r == want_fetch for r in gathers), (
            f"shards={n_shards} E={window}: per-shard {pname} fetch "
            f"gathers move {sorted(set(gathers))} rows — want the "
            f"uniform working-set shape B·(path_len−k) = {want_fetch} "
            "on every chip (non-owned lanes masked, never absent)"
        )
        fetch_acct[pname] = sorted(set(gathers))

    # -- 2. per-shard flush: t-row scatters, no local tree reads --------
    frows = _shared_plane_rows(
        _trace_sharded(cfg, "flush", mesh), lplanes
    )
    flush_acct = {}
    for pname in tree_planes:
        moved = frows[pname]
        gathers = [r for op, r in moved if op == "gather"]
        scatters = [r for op, r in moved if op != "gather"]
        assert not gathers, (
            f"shards={n_shards} E={window}: sharded flush GATHERS from "
            f"local {pname} — the window's live rows were already "
            "pulled at fetch time"
        )
        assert scatters and all(r == want_flush for r in scatters), (
            f"shards={n_shards} E={window}: per-shard {pname} flush "
            f"scatters move {sorted(set(scatters))} rows — want all "
            f"t = {want_flush} rows on every chip (the owner mask drops "
            "lanes via out-of-range targets; the static shape is the "
            "leak argument and never shrinks)"
        )
        flush_acct[pname] = sorted(set(scatters))

    if not runtime:
        out = {
            "fetch": fetch_acct, "flush": flush_acct,
            "want_fetch_rows": want_fetch, "want_flush_rows": want_flush,
            "shards": n_shards,
        }
        if verbose:
            print(f"sharded E={window} k={k} shards={n_shards} "
                  f"({'recursive' if recursive else 'flat'}, trace "
                  f"only): {out}")
        return out

    # -- 3. runtime owner partition (+ the seeded-mutant hook) ----------
    import functools

    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from grapevine_tpu.oram import round as round_mod
    from grapevine_tpu.oram.path_oram import init_oram
    from grapevine_tpu.parallel.mesh import TREE_AXIS, _oram_specs

    def apply_batch(vals0, present0):
        return jnp.sum(vals0, axis=1), vals0, present0

    def run_round(axis, st, idxs, nl, dl, pm_nl, pm_dl):
        return round_mod.oram_round(
            cfg, st, idxs, nl, dl, apply_batch, axis_name=axis,
            pm_new_leaves=pm_nl if recursive else None,
            pm_dummy_leaves=pm_dl if recursive else None,
        )

    specs = _oram_specs()
    s_round = jax.jit(jax.shard_map(
        functools.partial(run_round, TREE_AXIS),
        mesh=mesh, in_specs=(specs, P(), P(), P(), P(), P()),
        out_specs=(specs, P(), P()), check_vma=False,
    ))
    s_flush = jax.jit(jax.shard_map(
        lambda st: round_mod.oram_flush(cfg, st, TREE_AXIS),
        mesh=mesh, in_specs=(specs,), out_specs=specs,
        check_vma=False,
    ))
    one_round = jax.jit(functools.partial(run_round, None))
    one_flush = jax.jit(lambda st: round_mod.oram_flush(cfg, st, None))

    rng = np.random.default_rng(5)
    st_s = st_1 = init_oram(cfg, jax.random.PRNGKey(7))
    for _ in range(window):
        idxs = rng.integers(0, cfg.blocks + 1, b).astype(np.uint32)
        draws = [rng.integers(0, cfg.leaves, b).astype(np.uint32)
                 for _ in range(4)]
        st_s, out_s, tr_s = s_round(st_s, idxs, *draws)
        st_1, out_1, tr_1 = one_round(st_1, idxs, *draws)
        np.testing.assert_array_equal(np.asarray(tr_s), np.asarray(tr_1))
    pre = jax.tree.map(np.asarray, st_1)
    orig_scatter = round_mod._path_scatter
    if _unmasked_scatter:
        round_mod._path_scatter = _unmasked_scatter_mutant(orig_scatter)
    try:
        post_s = jax.tree.map(np.asarray, s_flush(st_s))
    finally:
        round_mod._path_scatter = orig_scatter
    post_1 = jax.tree.map(np.asarray, one_flush(st_1))

    # every flush rewrites its targets' nonces, so changed nonce rows ≡
    # written buckets; the assembled sharded planes concatenate each
    # chip's local writes in heap order, so shard s's slice holds
    # exactly what shard s wrote
    def _written(post):
        return np.nonzero(
            (post.nonces != pre.nonces).any(axis=1)
        )[0]

    oracle_rows = set(_written(post_1).tolist())
    per_shard, union = [], set()
    for s in range(n_shards):
        lo, hi = s * n_local, (s + 1) * n_local
        ch = {
            int(r) + lo
            for r in np.nonzero(
                (post_s.nonces[lo:hi] != pre.nonces[lo:hi]).any(axis=1)
            )[0]
        }
        assert all(lo <= r < hi for r in ch)
        per_shard.append(len(ch))
        union |= ch
    assert sum(per_shard) == len(oracle_rows) and union == oracle_rows, (
        f"shards={n_shards} E={window}: owner partition violated — "
        f"per-shard written rows {per_shard} (sum {sum(per_shard)}) vs "
        f"the single-chip flush's {len(oracle_rows)} written rows; "
        "every written bucket must be written by exactly its heap-range "
        "owner and the union must be the single-chip write set"
    )
    for name in ("tree_idx", "tree_val", "nonces", "tree_leaf"):
        np.testing.assert_array_equal(
            getattr(post_s, name), getattr(post_1, name),
            err_msg=f"shards={n_shards} E={window}: sharded flush "
            f"diverges from single-chip on {name}",
        )

    out = {
        "fetch": fetch_acct, "flush": flush_acct,
        "want_fetch_rows": want_fetch, "want_flush_rows": want_flush,
        "per_shard_written": per_shard,
        "oracle_written": len(oracle_rows),
        "shards": n_shards,
    }
    if verbose:
        print(f"sharded E={window} k={k} shards={n_shards} "
              f"({'recursive' if recursive else 'flat'}): {out}")
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--height", type=int, default=5)
    args = ap.parse_args(argv)
    if "jax" not in sys.modules:
        # the sharded audit needs a real (if simulated) multi-device
        # mesh; standalone runs get one before the backend initializes
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=2"
            ).strip()
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
    for recursive in (False, True):
        out = check_evict_round_accounting(verbose=True,
                                           recursive=recursive)
        print(f"[check_tree_cache_oblivious] evict schedule "
              f"(recursive={recursive}): OK")
    for recursive in (False, True):
        out = check_sharded_evict_accounting(verbose=True,
                                             recursive=recursive)
        print(f"[check_tree_cache_oblivious] sharded evict schedule "
              f"(recursive={recursive}): OK")
    try:
        check_sharded_evict_accounting(_unmasked_scatter=True)
    except AssertionError as exc:
        print("[check_tree_cache_oblivious] seeded unmasked-scatter "
              f"mutant: CAUGHT ({str(exc)[:72]}...)")
    else:
        print("[check_tree_cache_oblivious] FAIL: seeded unmasked-"
              "scatter mutant passed the sharded partition audit")
        return 1
    print("[check_tree_cache_oblivious] PASS: cached round is index-blind "
          "and HBM tree traffic is exactly (2^Ld − 2^k) + B·(path_len − Ld) "
          "rows per plane; "
          "delayed-eviction fetch rounds are HBM-read-only, each flush "
          "writes exactly the E-round window, and the sharded flush "
          "owner-partitions that window across the mesh")
    return 0


if __name__ == "__main__":
    sys.exit(main())
