"""The round-cost observatory (PR 17): the two-derivation ledger
identity, its mutant teeth, the model-graded knob decisions, and the
grapevine_cost_* export surface.

Everything here is trace-only or pure arithmetic — zero engine round
compiles — so the whole file rides tier-1. The structure mirrors the
rangelint/oblint suites: the analyzer is proven against the shipped
matrix, then proven ALIVE against seeded defects, then the gate tool
itself is exercised in-process (tools/check_cost_model.py), then the
serving-side export is checked end-to-end down to the Prometheus text
a scrape of a running engine role would see.
"""

import dataclasses
import importlib.util
import os

import pytest

from grapevine_tpu.analysis import costmodel as cm
from grapevine_tpu.analysis.mutants import control_failures
from grapevine_tpu.config import GrapevineConfig
from grapevine_tpu.engine.state import EngineConfig
from grapevine_tpu.obs.costmon import (
    CostMonitor,
    resolve_bandwidth_gbps,
)
from grapevine_tpu.obs.exporter import render_prometheus
from grapevine_tpu.obs.registry import TelemetryRegistry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    path = os.path.join(REPO, "tools", f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- the two-derivation identity ---------------------------------------


@pytest.mark.parametrize(
    "name,cfg,b", cm.audit_oram_configs(),
    ids=[n for n, _, _ in cm.audit_oram_configs()],
)
def test_round_ledger_matches_traced_census(name, cfg, b):
    """Analytic row model == traced jaxpr census, bit-exact per operand
    shape class, for every shipped oram_round knob combination (cache-k
    x posmap, cipher on/off)."""
    cm.cross_validate_round(cfg, b)


@pytest.mark.parametrize(
    "name,ecfg", cm.audit_engine_configs(),
    ids=[n for n, _ in cm.audit_engine_configs()],
)
def test_engine_ledger_matches_traced_census(name, ecfg):
    """Same identity at the composed engine level: the recipient-tree
    round + the mailbox double-round, and the expiry sweep's chunked
    scan."""
    cm.cross_validate_engine_round(ecfg)
    cm.cross_validate_sweep(ecfg)
    # and as a TPU traces the sweep, the cipher kernel in the scan
    cm.cross_validate_sweep(ecfg, kernel=True)


def test_sharded_ledger_per_chip_bytes():
    """The per-chip ledger view: shards=1 reduces to the single-chip
    steady bytes exactly; at shards>1 only the owner-masked scatter
    half divides, and the aggregate across chips reconstructs the
    single-chip write bytes exactly (power-of-two binary division)."""
    ecfg = cm.sweep_engine_ecfg(64)
    led1 = cm.engine_cost_ledger(ecfg)
    assert led1.per_shard_steady_round_bytes == led1.steady_round_bytes
    led4 = cm.engine_cost_ledger(ecfg, shards=4)
    assert led4.per_shard_steady_round_bytes < led1.steady_round_bytes
    # reconstruct: per-chip = gathers + repl scatters + sharded/4
    wb1, wb4 = led1.phases["writeback"], led4.phases["writeback"]
    assert wb1.sharded_scatter_bytes == wb4.sharded_scatter_bytes > 0
    assert wb4.per_chip_bytes(4) * 4 == (
        4 * (wb4.gather_bytes
             + wb4.scatter_bytes - wb4.sharded_scatter_bytes)
        + wb4.sharded_scatter_bytes
    )
    with pytest.raises(ValueError, match="power of two"):
        cm.engine_cost_ledger(ecfg, shards=3)


def test_cost_mutants_all_caught():
    """Every seeded undercount mutant (dropped plane, halved fetch,
    forgotten nonce re-gather, missed mailbox double-round, ...) must
    trip CostModelMismatch with the declared kind — a cost checker
    that cannot catch a planted undercount is vacuous."""
    assert control_failures(
        cm.run_cost_mutants(), "cost-model mutant", log=lambda *_: None
    ) == []


def test_mismatch_reports_shape_and_kind():
    """A corrupted prediction surfaces as a typed, per-shape-class
    diff — the triage surface OPERATIONS.md §21 documents."""
    _, cfg, b = cm.audit_oram_configs()[0]
    with pytest.raises(cm.CostModelMismatch) as ei:
        cm.cross_validate_round(
            cfg, b,
            _corrupt=lambda rows: {
                n: (dataclasses.replace(r, gather_rows=r.gather_rows // 2)
                    if r.hbm else r)
                for n, r in rows.items()
            },
        )
    assert ei.value.kind == "gather-undercount"
    assert "disagree" in str(ei.value) and "shape (" in str(ei.value)


# -- the ledger's knob sensitivity (arithmetic, no tracing) ------------


def test_tree_cache_cuts_hbm_bytes_not_rows():
    """Cached levels move path rows from HBM planes to private planes:
    HBM bytes strictly fall with k while the row CENSUS (which counts
    private planes too) stays internally consistent."""
    cap_n, b = 1 << 12, 64
    b0 = cm.oram_steady_bytes(cm.machinery_oram_cfg(cap_n, b, k=0), b)
    b2 = cm.oram_steady_bytes(cm.machinery_oram_cfg(cap_n, b, k=2), b)
    b4 = cm.oram_steady_bytes(cm.machinery_oram_cfg(cap_n, b, k=4), b)
    assert b0 > b2 > b4


def test_ab_verdicts_shape():
    """Every A/B kind yields a winner + per-arm modeled bytes (or a
    structural basis) — the dict bench.py embeds per config group."""
    for scope in ("machinery", "sweep"):
        v = cm.ab_verdict("tree_cache", scope=scope, cap_n=1 << 12,
                          batch=64)
        assert v["winner"] in v["arms"]
        assert all(d["modeled_bytes"] > 0 for d in v["arms"].values())
    assert cm.ab_verdict("pipeline")["winner"] == "depth2"
    with pytest.raises(ValueError):
        cm.ab_verdict("nonsense")


# -- the gate tool, in-process (the leakcheck wrapper pattern) ---------


def test_check_cost_model_grade_banked_trajectory():
    """The gate's --grade replay covers the two banked A/B kinds
    whose programs still exist and the model's pick measures within
    the A/B's resolution (``MEASURED_TIE``) of every banked winner. The
    A/Bs whose arms ran the per-path round (PR 8) are marked
    ``superseded`` in the trajectory and are not graded: PR 26
    re-measured the kind on the level-dense round (CPU sandbox).
    Tolerated disagreements are pinned by name; anything else
    disagreeing is a regression in the model or an unexplained machine
    regime, and should fail loudly here."""
    tool = _load_tool("check_cost_model")
    results, problems = tool.grade_trajectory()
    assert problems == []
    assert {r["kind"] for r in results} == {"tree_cache", "pipeline"}
    assert not any(r["config"].startswith("PR8/")
                   for r in results)
    disagreements = {r["config"] for r in results if r["agree"] is False}
    # The byte model prefers k=8 (255 of 3,327 HBM rows a round less);
    # the CPU round, which is not HBM-bound, measured k=8 17.1 % behind
    # k=2 in the banked run and 4.5 % behind in the run before it: the
    # cut does not show on this backend. The other five tree_cache
    # lines are measured ties (the model's pick 0-16 % behind, winners
    # changing from run to run).
    assert disagreements <= {
        "PR26/machinery/round_cap1048576_b256",
    }, disagreements


def test_grade_reads_ties_and_skips_superseded_lines(tmp_path):
    """A model pick within ``MEASURED_TIE`` of the measured winner is not
    contradicted, one further behind is, and an A/B marked
    ``superseded`` is not graded at all (its kind then counts as
    missing unless a later line re-measured it)."""
    import json

    tool = _load_tool("check_cost_model")

    def arms(k2_ms):  # the model picks k0 here: a byte tie
        return {"k0": {"round_ms": 10.0}, "k2": {"round_ms": k2_ms}}

    lines = [
        {"pr": "PRx", "backend": "cpu", "configs": {"tree_cache_ab": {
            "superseded": "PRy", "machinery": {
                "round_cap65536_b256": arms(1.0)}}}},
        {"pr": "PRy", "backend": "cpu", "configs": {"tree_cache_ab": {
            "machinery": {"round_cap65536_b256": arms(9.0),
                          "round_cap65536_b1024": arms(5.0)}}}},
    ]
    path = tmp_path / "traj.jsonl"
    path.write_text("".join(json.dumps(ln) + "\n" for ln in lines))
    results, problems = tool.grade_trajectory(str(path))
    got = {r["config"]: (r["modeled"], r["measured"], r["agree"])
           for r in results}
    assert got == {
        "PRy/machinery/round_cap65536_b256": ("k0", "k2", True),
        "PRy/machinery/round_cap65536_b1024": ("k0", "k2", False),
    }
    assert results[0]["lead"] == pytest.approx(10.0 / 9.0 - 1.0)
    assert not any(" no tree_cache_ab " in p for p in problems)
    assert any(" no pipeline_ab " in p for p in problems)


def test_check_cost_model_smoke_gate():
    """tools/check_cost_model.py --smoke wired into tier-1 next to the
    telemetry/seal/oblint/rangelint gates: the full shipped identity
    matrix cross-validates and every mutant is caught. Budget: traces
    only, zero engine compiles."""
    tool = _load_tool("check_cost_model")
    assert tool.main(["--smoke"]) == 0


def test_telemetry_policy_cost_audit():
    """The telemetry gate's cost-namespace audit passes on the shipped
    CostMonitor: phase-only labels, fixed schedule values, teeth."""
    tool = _load_tool("check_telemetry_policy")
    report = tool.audit_cost_registry()
    assert report["cost_families"] >= 9


# -- the export surface ------------------------------------------------


def _small_ecfg():
    return EngineConfig.from_config(GrapevineConfig(
        max_messages=1 << 10, max_recipients=1 << 7, batch_size=8,
    ))


def test_costmon_gauges_and_residual():
    """CostMonitor exports the static ledger at attach and scores each
    resolved round's device span against the roofline floor."""
    reg = TelemetryRegistry()
    mon = CostMonitor(_small_ecfg(), reg, bandwidth_gbps=10.0)
    assert mon.bandwidth_gbps == 10.0
    steady = reg.get("grapevine_cost_steady_round_hbm_bytes").get()
    assert steady == float(mon.ledger.steady_round_bytes) > 0
    floor = reg.get("grapevine_cost_roofline_floor_ms").get()
    assert floor == pytest.approx(steady / (10.0 * 1e6))
    phase_bytes = reg.get("grapevine_cost_phase_hbm_bytes")
    total = sum(phase_bytes.get(phase=p) for p in cm.COST_PHASES)
    assert total > 0

    # a round whose device span is exactly 2x the floor -> residual 2
    mon.observe_round({"device": (0.0, 2.0 * floor / 1e3)})
    assert reg.get("grapevine_cost_roofline_residual").get() == (
        pytest.approx(2.0))
    mon.observe_round({"device": (0.0, 0.5 * floor / 1e3)})
    assert reg.get("grapevine_cost_roofline_residual").get() == (
        pytest.approx(0.5))
    assert reg.get("grapevine_cost_roofline_residual_max").get() == (
        pytest.approx(2.0))
    # rounds without a device span (tracer detached) are a no-op
    mon.observe_round({})


def test_costmon_bandwidth_resolution_order():
    """Override > GRAPEVINE_COST_GBPS env > per-backend placeholder."""
    assert resolve_bandwidth_gbps(42.0) == 42.0
    old = os.environ.get("GRAPEVINE_COST_GBPS")
    os.environ["GRAPEVINE_COST_GBPS"] = "123.5"
    try:
        assert resolve_bandwidth_gbps() == 123.5
        assert resolve_bandwidth_gbps(7.0) == 7.0
    finally:
        if old is None:
            del os.environ["GRAPEVINE_COST_GBPS"]
        else:
            os.environ["GRAPEVINE_COST_GBPS"] = old
    assert resolve_bandwidth_gbps() > 0


def test_cost_gauges_on_live_engine_metrics():
    """attach_round_observability (the one serving-layer policy point)
    wires the CostMonitor onto a real engine, and the gauges land in
    the same Prometheus exposition a scrape of /metrics serves."""
    from grapevine_tpu.engine.batcher import GrapevineEngine
    from grapevine_tpu.obs import attach_round_observability

    engine = GrapevineEngine(GrapevineConfig(
        max_messages=1 << 10, max_recipients=1 << 7, batch_size=8,
    ))
    try:
        attach_round_observability(engine, engine.metrics.registry)
        assert engine.costmon is not None
        text = render_prometheus(engine.metrics.registry)
        assert "grapevine_cost_steady_round_hbm_bytes" in text
        assert "grapevine_cost_roofline_floor_ms" in text
        assert "grapevine_cost_roofline_residual" in text
        assert 'grapevine_cost_phase_hbm_bytes{phase="fetch"}' in text
    finally:
        engine.close()
