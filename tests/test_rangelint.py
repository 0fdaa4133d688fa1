"""ISSUE 14: Rangelint — geometry-scale overflow certification.

Five suites:

1. interval-domain directed units, one per primitive class the tentpole
   names (shift, mul, cast, scan-carry fixpoint, clamped gather) plus
   the transfer refinements the engine's idioms rely on (where-clamp
   predicate narrowing through pjit, select branch feasibility,
   scatter-min/add, exclusive-rank forms);
2. the seeded overflow-mutant teeth matrix under the PRODUCTION range
   allowlist (tools/check_ranges.py and the shared check_oblivious
   mutant control run the same set);
3. the tier-1 smoke gate: one toy-geometry engine trace certifies
   clean, zero compiles;
4. geometry certification: 2^36 (the ROADMAP item 4 design point) is
   REFUSED at construction by the certified-bound guard with a message
   this report can cite, while the max certified per-tree geometry
   traces clean;
5. the allowlist contract: reachability accounting and family matching
   shared with oblint's AllowEntry.
"""

from __future__ import annotations

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from grapevine_tpu.analysis.allowlist import RANGE_ALLOWLIST
from grapevine_tpu.analysis.mutants import range_mutant_names, run_range_mutants
from grapevine_tpu.analysis.oblint import AllowEntry
from grapevine_tpu.analysis.rangelint import analyze_ranges, dtype_range

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

U32 = jnp.uint32


def _sds(*shape, dtype=np.uint32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _kinds(rep):
    return {f.kind for f in rep.findings}


# ----------------------------------------------------------------------
# 1. interval-domain directed units
# ----------------------------------------------------------------------


def test_dtype_range():
    assert dtype_range(np.uint32) == (0, 2**32 - 1)
    assert dtype_range(np.int32) == (-(2**31), 2**31 - 1)
    assert dtype_range(np.bool_) == (0, 1)
    assert dtype_range(np.float32) is None


def test_add_within_bounds_is_clean_and_escape_flags():
    def fn(x):
        return x + U32(100)

    ok = analyze_ranges(fn, {"x": _sds(4)}, {"x": (0, 1000)})
    assert ok.ok, ok.summary()
    bad = analyze_ranges(fn, {"x": _sds(4)}, {"x": (0, 2**32 - 50)})
    assert _kinds(bad) == {"overflow"}


def test_shift_left_overflow_and_masked_recovery():
    def fn(x):
        return (x << U32(8)) & U32(0xFFFF)

    rep = analyze_ranges(fn, {"x": _sds(4)}, {"x": (0, 2**30)})
    # the shift escapes u32; the AND afterwards cannot unflag it
    assert _kinds(rep) == {"overflow"}
    ok = analyze_ranges(fn, {"x": _sds(4)}, {"x": (0, 2**20)})
    assert ok.ok


def test_mul_interval_products():
    def fn(rows):
        return rows * U32(4096)

    assert analyze_ranges(
        fn, {"rows": _sds(2)}, {"rows": (0, 2**19)}
    ).ok
    assert _kinds(analyze_ranges(
        fn, {"rows": _sds(2)}, {"rows": (0, 2**21)}
    )) == {"overflow"}


def test_sub_unsigned_underflow_flags():
    def fn(a, b):
        return a - b

    rep = analyze_ranges(
        fn, {"a": _sds(2), "b": _sds(2)}, {"a": (0, 10), "b": (0, 10)}
    )
    assert _kinds(rep) == {"overflow"}
    ok = analyze_ranges(
        fn, {"a": _sds(2), "b": _sds(2)}, {"a": (10, 20), "b": (0, 10)}
    )
    assert ok.ok


def test_narrowing_cast_flags_and_bounded_cast_clean():
    def fn(x):
        return x.astype(jnp.int32)

    assert _kinds(analyze_ranges(fn, {"x": _sds(4)})) == {"trunc-cast"}
    assert analyze_ranges(fn, {"x": _sds(4)}, {"x": (0, 2**31 - 1)}).ok


def test_gather_oob_flags_and_clamped_gather_clean():
    def raw(idx, table):
        return table[idx]

    def clamped(idx, table):
        return table[jnp.minimum(idx, U32(15))]

    # the unbounded index flags OOB (and its int32 conversion truncates)
    assert "oob-index" in _kinds(analyze_ranges(
        raw, {"idx": _sds(4), "table": _sds(16)}
    ))
    assert analyze_ranges(
        clamped, {"idx": _sds(4), "table": _sds(16)}
    ).ok


def test_where_clamp_idiom_narrows_through_pjit():
    """The codebase's `where(x < N, x, M)` clamp must bound the index
    even though jnp.where wraps its select_n in a pjit body."""
    def fn(idx, table):
        safe = jnp.where(idx < U32(16), idx, U32(16))
        return table[safe]

    assert analyze_ranges(fn, {"idx": _sds(4), "table": _sds(17)}).ok


def test_negative_index_normalization_branch_pruned():
    """jnp lowers x[i] (signed i) to select(i < 0, i + n, i); for i
    provably >= 0 the dead branch must not widen the interval."""
    def fn(idx, table):
        return table[idx.astype(jnp.int32)]

    assert analyze_ranges(
        fn, {"idx": _sds(4), "table": _sds(16)}, {"idx": (0, 15)}
    ).ok


def test_drop_mode_scatter_oob_is_the_masking_idiom():
    """OOB-drops-the-write is documented semantics — never flagged; the
    sentinel itself fitting the index lane is what gets certified."""
    def fn(idx, plane):
        tgt = jnp.where(idx < U32(8), idx, U32(8))  # 8 = drop sentinel
        return plane.at[tgt].set(U32(1), mode="drop")

    assert analyze_ranges(fn, {"idx": _sds(4), "plane": _sds(8)}).ok


def test_scan_carry_fixpoint_budgets_trip_count():
    """A counter gaining at most `inc` per step certifies at exactly
    length·inc — clean when the budget fits, flagged when it does not
    (the affine-widening half of the unbounded-scan-counter mutant)."""
    def fn(inc):
        def body(c, x):
            return c + inc[0], x

        return jax.lax.scan(body, U32(0), jnp.zeros((1024,), U32))

    assert analyze_ranges(fn, {"inc": _sds(1)}, {"inc": (0, 2**20)}).ok
    assert "overflow" in _kinds(analyze_ranges(
        fn, {"inc": _sds(1)}, {"inc": (0, 2**23)}
    ))


def test_scan_carry_derived_increment_not_certified_affine():
    """Soundness regression (review finding): an increment derived from
    the carry itself (c + (c >> 10): exponential growth that looks flat
    across two narrow passes) must NOT be certified by affine
    extrapolation — the inductiveness check widens it to the lane and
    the wrap flags inside the body."""
    def fn(xs):
        def body(c, x):
            return c + (c >> U32(10)), x

        return jax.lax.scan(body, U32(1024), xs)

    rep = analyze_ranges(fn, {"xs": _sds(1 << 16)})
    assert "overflow" in _kinds(rep), rep.summary()


def test_while_carry_widens_to_lane_and_flags_inside_body():
    def fn(s):
        def cond(c):
            return c[0] < s[0]

        def body(c):
            return (c[0] + U32(1), c[1] * U32(2))

        return jax.lax.while_loop(cond, body, (U32(0), U32(1)))

    rep = analyze_ranges(fn, {"s": _sds(1)})
    assert "overflow" in _kinds(rep)


def test_scatter_min_transfer_bounds_owner_map():
    """The owner-election idiom: full(B).at[hb].min(cols) stays in
    [0, B] — its consumer arithmetic must not widen to the lane."""
    def fn(hb, cols):
        bmap = jnp.full((64,), U32(8)).at[hb].min(cols)
        return bmap * U32(4)  # would flag if bmap were full-range

    assert analyze_ranges(
        fn, {"hb": _sds(16), "cols": _sds(16)},
        {"hb": (0, 63), "cols": (0, 7)},
    ).ok


def test_scatter_add_accumulation_budget():
    def fn(x, upd):
        return x.at[jnp.zeros((8,), jnp.int32)].add(upd)

    ok = analyze_ranges(
        fn, {"x": _sds(4), "upd": _sds(8)},
        {"x": (0, 100), "upd": (0, 10)},
    )
    assert ok.ok  # 100 + 8*10 fits easily
    bad = analyze_ranges(
        fn, {"x": _sds(4), "upd": _sds(8)},
        {"x": (0, 100), "upd": (0, 2**30)},
    )
    assert _kinds(bad) == {"overflow"}


def test_allowlist_admits_by_site_and_counts_hits():
    def fn(a, b):
        return a + b

    bare = analyze_ranges(fn, {"a": _sds(2), "b": _sds(2)})
    assert len(bare.findings) == 1
    site = bare.findings[0].site
    entry = AllowEntry("add", site, "test: wrap is intended here")
    allowed = analyze_ranges(
        fn, {"a": _sds(2), "b": _sds(2)}, allowlist=(entry,)
    )
    assert allowed.ok
    assert allowed.allowed == {f"add@{site}": 1}


def test_trace_abort_is_a_finding_not_a_crash():
    def fn(x):
        return x + np.uint32(2**31)  # fine

    # a builder that raises at trace time (e.g. a geometry guard)
    def boom(x):
        raise ValueError("refused: certified bound exceeded")

    rep = analyze_ranges(boom, {"x": _sds(2)})
    assert _kinds(rep) == {"trace-abort"}
    assert "refused" in rep.findings[0].message
    assert analyze_ranges(fn, {"x": _sds(2)}, {"x": (0, 100)}).ok


# ----------------------------------------------------------------------
# 2. overflow-mutant teeth matrix (under the PRODUCTION allowlist)
# ----------------------------------------------------------------------


def test_range_mutant_matrix_all_caught():
    assert len(range_mutant_names()) == 5
    results = run_range_mutants(RANGE_ALLOWLIST)
    missed = {
        name: (kind, [f.kind for f in rep.findings])
        for name, (rep, kind, hit) in results.items()
        if not hit
    }
    assert not missed, f"range mutants NOT caught: {missed}"


def test_range_mutants_caught_for_the_right_reason():
    for name, (rep, kind, hit) in run_range_mutants(RANGE_ALLOWLIST).items():
        kinds = [f.kind for f in rep.findings]
        assert kinds.count(kind) >= 1, (name, kind, kinds)


# ----------------------------------------------------------------------
# 3. the tier-1 smoke gate (traces only, zero engine compiles)
# ----------------------------------------------------------------------


def test_check_ranges_smoke_gate():
    """tools/check_ranges.py --smoke wired into tier-1 next to the
    telemetry/seal/oblint gates: one toy-geometry engine trace certifies
    interval-clean, the design point refuses, all overflow mutants
    caught. Budget: ~1 engine trace, 0 compiles."""
    import check_ranges as gate

    assert gate.main(["--smoke"]) == 0


def test_smoke_engine_audit_exercises_the_allowlist():
    import check_ranges as gate

    rep = gate.audit_engine_round(
        gate._engine(5, *gate.SMOKE_COMBO), RANGE_ALLOWLIST,
        "tier1_smoke",
    )
    assert rep.ok, rep.summary()
    # not vacuous: the ChaCha/mixer/carry sites really were walked
    assert sum(rep.allowed.values()) > 100
    assert rep.n_eqns > 1000


# ----------------------------------------------------------------------
# 4. geometry certification: the 2^36 design point
# ----------------------------------------------------------------------


def test_design_point_refused_with_citable_message():
    """2^36 records must REFUSE at engine construction, citing the
    certified bound — the directed guard ISSUE 14 installs so item 4
    starts from a certified substrate (never a silent wraparound)."""
    import check_ranges as gate

    problems, refusal = gate.certify_design_point(gate.DESIGN_POINT)
    assert not problems
    assert "certified bound" in refusal
    assert "OPERATIONS.md" in refusal


def test_certified_bound_guard_edges():
    """The guard's edges: the max certified geometry constructs; one
    height past it refuses; oversubscribed block spaces refuse."""
    from grapevine_tpu.oram.path_oram import (
        MAX_U32_BLOCKS, MAX_U32_HEIGHT, OramConfig,
    )

    OramConfig(height=MAX_U32_HEIGHT, value_words=1,
               n_blocks=MAX_U32_BLOCKS)  # constructs
    with pytest.raises(ValueError, match="certified"):
        OramConfig(height=MAX_U32_HEIGHT + 1, value_words=1)
    with pytest.raises(ValueError, match="certified"):
        OramConfig(height=MAX_U32_HEIGHT, value_words=1,
                   n_blocks=2 * MAX_U32_BLOCKS)


def test_journal_frame_length_guard():
    """The host prong: a batch geometry whose sealed journal frame
    cannot fit the u32 blob_len wire field refuses at construction."""
    from grapevine_tpu.config import GrapevineConfig
    from grapevine_tpu.engine.journal import BatchJournal
    from grapevine_tpu.engine.state import EngineConfig

    class _HugeBatch:
        """EngineConfig stand-in: only batch_size is consulted."""

        batch_size = 1 << 23  # ~8.6 GB frame: past the u32 blob_len

    with pytest.raises(ValueError, match="blob_len"):
        BatchJournal("/tmp/x", b"\x00" * 32, _HugeBatch())
    # a sane geometry constructs (no files touched before open)
    ecfg = EngineConfig.from_config(GrapevineConfig(
        max_messages=32, max_recipients=16, batch_size=4,
    ))
    BatchJournal("/tmp/x", b"\x00" * 32, ecfg)


def test_full_certification_at_max_certified_geometry():
    """The acceptance sweep: every shipped knob combo at 2^30 AND the
    2^36 design point (refusal + shard certification), end to end."""
    import check_ranges as gate

    assert gate.main(["--geometry", "30"]) == 0
    assert gate.main(["--geometry", "36"]) == 0


def _served_ecfg(config_name: str):
    """The engine geometry of one benchmark configuration file."""
    import json

    from grapevine_tpu.config import GrapevineConfig
    from grapevine_tpu.engine.state import EngineConfig

    with open(os.path.join(REPO, "benchmarks", "configs",
                           f"{config_name}.json")) as f:
        spec = json.load(f)
    return EngineConfig.from_config(GrapevineConfig(
        **spec["grapevine_config"]))


@pytest.mark.parametrize("config_name", [
    "chipshare-2p20", "chipshare-2p20-r2p16", "chipshare-2p21-r2p17"])
def test_a_served_geometry_certifies_at_its_own_batch(config_name):
    """The one-chip deployments at B = 2048, traced, not compiled: the
    round and the sweep are interval-clean under the production
    allowlist. ``chipshare-2p21-r2p17`` is the chip's whole share of
    the bus (2^21 messages, 2^17 recipients): its records value plane
    holds 2^31 words. Before PR 36 the gate traced B = 4 only, and the
    declared per-round budget of the ``overflow`` counter (2^16) was
    smaller than the rows a B = 2048 round could drop."""
    import check_ranges as gate

    ecfg = _served_ecfg(config_name)
    assert ecfg.batch_size == 2048
    for audit in (gate.audit_engine_round, gate.audit_expiry_sweep,
                  functools.partial(gate.audit_expiry_sweep, kernel=True)):
        rep = audit(ecfg, RANGE_ALLOWLIST, config_name)
        assert rep.ok, rep.summary()
        assert rep.n_eqns > 1000


def test_overflow_mutants_keep_their_teeth_at_the_chips_real_share():
    """At 2^21 messages a records value row is 4,096 B and the plane
    2^21 rows: the byte-size product in int32 is the seeded mutant that
    this geometry makes real, and it must still be caught there, as
    must a round whose ``overflow`` counter is given no headroom."""
    import check_ranges as gate
    from grapevine_tpu.analysis import mutants
    from grapevine_tpu.engine import round_step
    from grapevine_tpu.engine.state import init_engine

    ecfg = _served_ecfg("chipshare-2p21-r2p17")
    rec = ecfg.rec
    assert rec.n_buckets_padded == 1 << 21
    assert rec.n_buckets_padded * rec.bucket_slots * rec.value_words == 1 << 31
    builder, kind = mutants._RANGE_REGISTRY["int32_byte_size_product"]
    fn, args, _ = builder()
    assert 4 * rec.bucket_slots * rec.value_words == 4096  # fn's factor
    rep = analyze_ranges(fn, args, {"rows": (0, rec.n_buckets_padded)},
                         allowlist=RANGE_ALLOWLIST)
    assert kind in _kinds(rep)
    # the lane this geometry found: the sticky counter with the old
    # 2^16 of headroom under a round that can drop 86,012 rows
    bounds = dict(round_step.RANGELINT_BOUNDS(ecfg))
    bounds["state.rec.overflow"] = (0, 2**32 - 2**16)
    state = jax.eval_shape(lambda: init_engine(ecfg, 0))
    rep = analyze_ranges(
        lambda st, ba: round_step.engine_round_step(ecfg, st, ba),
        {"state": state, "batch": gate._batch_spec(ecfg)},
        bounds=bounds, allowlist=RANGE_ALLOWLIST)
    assert [f.kind for f in rep.findings] == ["overflow"]


# ----------------------------------------------------------------------
# 5. allowlist contract
# ----------------------------------------------------------------------


def test_range_allowlist_entries_have_arguments():
    for e in RANGE_ALLOWLIST:
        assert e.reason and len(e.reason) > 20, e.key


def test_range_allowlist_reachability_accounting():
    import check_ranges as gate

    problems, hits = gate.run_audit(
        (gate.SMOKE_COMBO,), 5, with_subrounds=False
    )
    assert not problems, problems
    # the smoke slice alone reaches the cipher/carry entries; full
    # reachability (every entry) is enforced by the default sweep
    assert any(k.startswith("add@oblivious/bucket_cipher.py")
               for k in hits)


if __name__ == "__main__":
    sys.exit(os.system(f"{sys.executable} -m pytest {__file__} -q"))
