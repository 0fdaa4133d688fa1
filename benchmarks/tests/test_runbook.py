"""The cell ``backlog-runbook-1chip-2p21`` (TTL, ``--state-dir`` and
``--leakmon`` on together) beyond its plain rehearsal, which
``test_rehearsal.py`` makes of every cell: the ``registry_ratio`` reader
and the four metrics that come with the cell, and one rehearsal for each
thing the cell's driver refuses, the timed path broken underneath: a
leak canary in the program's place, the journal cut back after the
crash, a round answered before its frame was fsynced, a monitor that was
handed nothing, a state directory left by another run. Each must come
out not ``correct``, through the same comparison code."""

import os
import time
import types

import pytest

from benchmarks.lib import harness
from benchmarks.lib.manifest import Benchmark
from benchmarks.readers import registry_ratio
from toy import toy_bench

pytestmark = pytest.mark.filterwarnings("ignore")

CELL = "backlog-runbook-1chip-2p21"
NEW = ("leakmon_ms", "leakmon_dropped_share", "replay_ms_per_frame",
       "sweep_journal_ms")


@pytest.fixture(scope="module", autouse=True)
def compile_cache():
    from grapevine_tpu.config import setup_compile_cache

    setup_compile_cache()


def _params(name):
    return Benchmark.load().layer_metric(name)["params"]


def test_the_new_metrics_are_declared_for_the_new_cell_alone():
    bench = Benchmark.load()
    for name in NEW:
        entry, = [m for m in bench.manifest["per_layer"]
                  if m["name"] == name]
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "ops_per_s" and entry["better"] == "lower"
        assert entry["layer"] == ("observability" if "leakmon" in name
                                  else "durability")
        assert bench.layer_metric(name)["reader"] == (
            "registry_gauge" if name == "leakmon_ms" else "registry_ratio")
    # the cell reports what its durable sibling reports, but for the
    # checkpoint it never takes
    sibling = {m["name"] for m in bench._metrics_for(
        "per_layer", "backlog-durable-1chip-2p21")}
    mine = {m["name"] for m in bench._metrics_for("per_layer", CELL)}
    assert mine == (sibling - {"checkpoint_stall_s", "checkpoint_gbps"}
                    | set(NEW))
    assert [m["name"] for m in bench.end_to_end(CELL)] == [
        "ops_per_s", "setup_s"]


def test_registry_ratio_reads_sums_labels_and_histograms():
    """Nothing from a program that keeps no such series (the reader's
    parent) or that has counted nothing; else the quotient of the sums."""
    from grapevine_tpu.obs.registry import TelemetryRegistry

    server = types.SimpleNamespace()
    obs = {"ctx": types.SimpleNamespace(server=server)}
    for name in NEW[1:]:
        assert registry_ratio.read(_params(name), obs) is None  # no registry
    reg = server.metrics_registry = TelemetryRegistry()
    for name in NEW[1:]:
        assert registry_ratio.read(_params(name), obs) is None  # no family
    dropped = reg.counter("grapevine_leakmon_rounds_dropped_total", "d")
    rounds = reg.counter("grapevine_leakmon_rounds_total", "r")
    assert registry_ratio.read(_params(NEW[1]), obs) is None  # none handed
    rounds.inc(396)
    assert registry_ratio.read(_params(NEW[1]), obs) == 0.0
    dropped.inc(4)
    assert registry_ratio.read(_params(NEW[1]), obs) == 1.0  # 4 of 400, %
    replayed = reg.counter("grapevine_recover_replayed_total", "n",
                           labels={"kind": ("round", "sweep")})
    seconds = reg.counter("grapevine_recover_replay_seconds", "s")
    assert registry_ratio.read(_params(NEW[2]), obs) is None  # none replayed
    replayed.inc(409, kind="round")
    replayed.inc(1, kind="sweep")
    seconds.inc(32.8)
    assert registry_ratio.read(_params(NEW[2]), obs) == pytest.approx(80.0)
    # a histogram's child, picked by its label: sum over count
    phases = reg.histogram("grapevine_phase_seconds", "p", (0.001, 0.01),
                           labels={"phase": ("sweep", "replay")})
    assert registry_ratio.read(_params(NEW[3]), obs) is None  # no such child
    reg2 = server.metrics_registry = TelemetryRegistry()
    phases = reg2.histogram("grapevine_phase_seconds", "p", (0.001, 0.01),
                            labels={"phase": ("sweep", "sweep_journal")})
    assert registry_ratio.read(_params(NEW[3]), obs) is None  # no sweep yet
    phases.observe(0.002, phase="sweep_journal")
    phases.observe(0.004, phase="sweep_journal")
    phases.observe(0.5, phase="sweep")
    assert registry_ratio.read(_params(NEW[3]), obs) == pytest.approx(3.0)


# -- what the driver refuses, rehearsed ---------------------------------


def _drive(tmp_path, tamper=None):
    """Two seconds of the cell through a ``Cell``; ``tamper`` gets the
    cell before the window. Returns (cell, observed, correct)."""
    cell = harness.Cell(toy_bench(tmp_path / "base"), CELL, 2**31 + 53,
                        str(tmp_path))
    try:
        if tamper is not None:
            tamper(cell)
        obs = cell.drive(2**31 + 53, 2.0, False, time.perf_counter())
    finally:
        cell.close()
    correct, _, _ = cell.judge(obs)
    return cell, obs, correct


def _refusals(obs) -> str:
    return " | ".join(obs["observed"]["summary"]["runbook_refusals"])


def test_the_cell_untampered_is_correct_and_refuses_nothing(tmp_path):
    cell, obs, correct = _drive(tmp_path)
    summary = obs["observed"]["summary"]
    assert correct and summary["runbook_refusals"] == []
    # every frame journaled since the server was built came back, the
    # sweep's among them, from no checkpoint
    replayed = summary["recover_replayed"]
    assert replayed["sweep"] == 1 and replayed["round"] >= 16
    assert sum(replayed.values()) == summary["frames_journaled"]
    assert summary["due_sweep"]["evicted"] > 0
    assert summary["leakaudit"]["verdict"] == "PASS"
    assert summary["leakaudit"]["rounds_observed"] >= replayed["round"]
    # the sweep stands among the tail's rounds in the log, and two whole
    # rounds of the script follow the restart
    kinds = [e["kind"] for e in cell.log.entries]
    at = kinds.index("sweep")
    assert kinds.count("sweep") == 1 and len(kinds) - at - 1 >= 8 + 2
    assert not os.path.exists(cell.engine.durability.dcfg.state_dir)


def test_a_leak_canary_in_the_programs_place_is_suspect_and_not_correct(
        tmp_path, monkeypatch):
    """``tests/test_leak_canary.py``'s no-remap canary as the served
    round: every block is remapped to the leaf it already has, so a key
    touched again repeats its path. Every answer is still right; the
    auditor's verdict is what refuses the run."""
    import jax

    from grapevine_tpu.engine import round_step

    honest = round_step.oram_round

    def no_remap(cfg, state, idxs, new_leaves, dummy_leaves, *a, **kw):
        return honest(cfg, state, idxs, state.posmap[idxs], dummy_leaves,
                      *a, **kw)

    def leaky_round_step(ecfg, state, batch):  # a new function: a new trace
        return round_step.engine_round_step(ecfg, state, batch)

    monkeypatch.setattr(round_step, "oram_round", no_remap)

    def canary(cell):
        cell.engine._step = jax.jit(
            leaky_round_step, static_argnums=(0,), donate_argnums=(1,))

    cell, obs, correct = _drive(tmp_path, canary)
    audit = obs["observed"]["summary"]["leakaudit"]
    assert audit["verdict"] == "SUSPECT" and not correct
    tripped = {d["name"] for d in audit["detectors"]
               if d["verdict"] == "SUSPECT"}
    assert "cross_round_repeat" in tripped
    assert "verdict is SUSPECT" in _refusals(obs)
    assert cell.compared["ops_unanswered"]["value"] >= 1
    assert cell.compared["ops_wrong"] == {"value": 0, "limit": 0}


def test_a_journal_cut_back_after_the_crash_is_not_correct(tmp_path):
    """PR 43's control on this cell: the last four frames gone from the
    disk between the crash and the restart. Those rounds were
    acknowledged; a state without them counts other records than the
    oracle and answers the rounds after wrongly, and the driver sees
    fewer frames replayed than journaled."""
    def cut_back(cell):
        engine = cell.engine
        abandon = engine.abandon

        def abandon_and_lose_the_tail():
            dm = engine.durability
            frame = dm.journal.last_append["bytes"]  # a round's
            (_, wal), = dm.journal._segments()
            abandon()
            os.truncate(wal, os.path.getsize(wal) - 4 * frame)

        engine.abandon = abandon_and_lose_the_tail

    cell, obs, correct = _drive(tmp_path, cut_back)
    assert not correct
    summary = obs["observed"]["summary"]
    assert sum(summary["recover_replayed"].values()) == (
        summary["frames_journaled"] - 4)
    assert "recovery replayed" in _refusals(obs)
    lost = sum(cell.compared[k]["value"] for k in (
        "ops_wrong", "message_count_gap", "recipient_count_gap"))
    assert lost > 0


def test_a_round_answered_before_its_frame_was_fsynced_is_not_correct(
        tmp_path):
    def lazy_fsync(cell):
        cell.engine.durability.journal.fsync_every = 1 << 30

    _, obs, correct = _drive(tmp_path, lazy_fsync)
    assert not correct
    assert "answered before their frame was fsynced" in _refusals(obs)


def test_a_monitor_that_was_handed_nothing_is_not_correct(tmp_path):
    def detached(cell):
        cell.engine.attach_leakmon(None)

    _, obs, correct = _drive(tmp_path, detached)
    assert not correct
    assert "audited no round at all" in _refusals(obs)


def test_a_state_directory_left_by_another_run_is_not_correct(tmp_path):
    """The engine recovered something when it was built: the directory
    holds another run's journal."""
    first, _, correct = _drive(tmp_path)
    assert correct
    state_dir = first.engine.durability.dcfg.state_dir
    from grapevine_tpu.engine.batcher import GrapevineEngine

    other = GrapevineEngine(first.cfg, seed=1, durability={
        "state_dir": state_dir, "checkpoint_every_rounds": 1 << 20})
    other.expire(1_700_000_000)  # one frame
    other.close()
    _, obs, correct = _drive(tmp_path / "again")
    assert not correct
    assert "left by another run" in _refusals(obs)
    assert not os.path.exists(state_dir)
