"""The harness itself, rehearsed on the CPU at a toy geometry (2^10
messages, B=16; the sharded configuration on four virtual devices), a
third configuration added as files alone, and the self-test of
``correct``: the timed path broken underneath must come
out as not correct, through the same comparison code. ``run.py`` as a
command still refuses anything but a TPU."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from benchmarks.lib import harness
from benchmarks.lib.manifest import ROOT, Benchmark, ManifestError
from toy import TOY_DATA, toy_bench

pytestmark = pytest.mark.filterwarnings("ignore")


@pytest.fixture(scope="module", autouse=True)
def compile_cache():
    from grapevine_tpu.config import setup_compile_cache

    setup_compile_cache()


def _run(cell, tmp_path, seconds=2.0, trace=False, seed=2**31 + 11):
    return harness.run_cell(toy_bench(tmp_path / "base"), cell, seed, seconds,
                            trace, time.perf_counter(), str(tmp_path))


def _cells():
    return [w["name"] for w in Benchmark.load().manifest["workloads"]]


@pytest.mark.parametrize("cell", _cells())
@pytest.mark.parametrize("trace", [False, True])
def test_every_cell_runs_and_is_correct(cell, trace, tmp_path):
    bench = toy_bench(tmp_path / "base")
    r = _run(cell, tmp_path, trace=trace)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    # each number compared beside its limit, as the result's last key
    assert list(r)[-1] == "compared" and len(r["compared"]) == 8
    assert all(x == {"value": 0, "limit": 0} for x in r["compared"].values())
    group = "per_layer" if trace else "end_to_end"
    declared = {m["name"] for m in bench._metrics_for(group, cell)}
    got = set(r["metrics"])
    assert got <= declared
    if trace:
        # the CPU has no device plane: the trace readers return nothing
        # and the harness leaves their metrics out of the line
        from_trace = {m["name"] for m in bench._metrics_for(group, cell)
                      if m["source"] == "device_trace"}
        assert got == declared - from_trace
    else:
        assert got == declared
        assert all(v["value"] > 0 for v in r["metrics"].values())
    assert r["device"]["platform"] == "cpu"  # named, never a device metric


def test_a_backlog_cell_accounts_for_its_loader_and_its_collections(
        tmp_path, capsys):
    """The loader's share of the interpreter lock and the collector's
    pauses are part of every backlog run's account."""
    r = _run("backlog-1chip", tmp_path, trace=True)
    assert r["correct"] is True
    submit = r["metrics"]["loadgen_submit_ms"]["value"]
    own = r["metrics"]["loadgen_own_ms"]["value"]
    assert submit > 0 and own > 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    samples = next(x for x in lines if x.get("phase") == "samples")
    assert samples["presigned_script_reused"] == 0
    waves = samples["loadgen_waves"]
    assert waves >= samples["rounds_in_window"] - 1 > 0
    # the metrics are the window's totals per wave
    assert submit == pytest.approx(
        1e3 * samples["loadgen_submit_cpu_s"] / waves)
    assert own == pytest.approx(1e3 * samples["loadgen_own_cpu_s"] / waves)
    # the log leaves the collector's sight wave by wave: one explicit
    # generation-1 collection per wave at least, and full collections
    # find nothing that has grown
    assert samples["gc_collections_in_window"][1] >= waves
    assert samples["gc_gen2_in_window"] <= 2
    assert 0 <= samples["gc_gen2_pause_ms"] <= samples["gc_pause_ms"]
    traffic = next(x for x in lines if x.get("phase") == "traffic")
    assert 0.5 < traffic["prebuilt_ops"] / traffic["presigned_ops"] < 0.7
    init = next(x for x in lines if x.get("phase") == "init")
    assert init["geometry"]["trees"]["mailbox"]["rows_per_pass"] == 16


SWEEP_CELL = "backlog-sweep-1chip-2p21"


def _drive_the_sweep_cell(tmp_path, tamper=None):
    """Three seconds of the sweep cell through a ``Cell``, as
    ``control.py`` drives one; ``tamper`` gets the cell before the
    window. Returns (cell, what was observed, the verdict)."""
    cell = harness.Cell(toy_bench(tmp_path / "base"), SWEEP_CELL,
                        2**31 + 41, str(tmp_path))
    try:
        if tamper is not None:
            tamper(cell)
        obs = cell.drive(2**31 + 41, 3.0, False, time.perf_counter())
    finally:
        cell.close()
    return cell, obs, cell.judge(obs)


def test_a_sweep_that_returns_its_state_unchanged_is_not_correct(tmp_path):
    """The engine's sweep runs and forgets what it did: no record
    leaves and no slot comes free, where the oracle's do."""
    def forgetful(cell):
        cell.engine._sweep = lambda ecfg, state, *clock: state

    cell, obs, (correct, _, _) = _drive_the_sweep_cell(tmp_path, forgetful)
    assert correct is False
    assert sum(s["evicted"] for s in obs["sweeps"]) == 0
    assert cell.compared["sweep_evicted_gap"]["value"] > 0
    assert cell.compared["recipient_count_gap"]["value"] > 0


def test_the_sweep_cell_expires_reclaims_and_catches_its_controls(tmp_path):
    """Three seconds of the toy TTL of one second: records come due on
    the server's clock, the engine's sweeps and the oracle's agree on
    every one, and the log's order and the ``expiry`` guarantee are
    each held by a number that fails when they are broken."""
    from benchmarks import control
    from benchmarks.lib import compare

    cell, obs, (correct, failed, rep) = _drive_the_sweep_cell(tmp_path)
    assert correct and failed == 0
    assert cell.compared["sweep_evicted_gap"] == {"value": 0, "limit": 0}
    assert cell.compared["recipient_count_gap"] == {"value": 0, "limit": 0}
    # the sweeps since the window opened: the window's, then the one
    # the driver makes due once the window's ops are answered
    *sweeps, due = obs["sweeps"]
    assert len(sweeps) >= 5 and rep["sweeps"] == len(sweeps) + 2  # set-up's
    assert sum(s["evicted"] for s in sweeps) > 0
    assert all(s["period"] == 1 for s in sweeps)
    summary = obs["observed"]["summary"]  # the driver's, for ``samples``
    assert summary["sweeps"] == len(sweeps)
    assert summary["records_evicted"] == sum(s["evicted"] for s in sweeps)
    # the due sweep: its clock is one TTL past the clock of the window's
    # middle round, it stands in the log behind every round of the
    # window, and whole rounds of the script follow it
    cut = summary["due_sweep"]["cut"]
    assert due["now"] == cut + 1 and due["t_start"] > obs["window"][1]
    assert summary["due_sweep"]["evicted"] == due["evicted"]
    rounds = [e["now"] for e in obs["all_rounds"]]
    assert min(rounds) <= cut <= max(rounds)
    after = cell.log.entries[cell.log.entries.index(due) + 1:]
    assert sum(len(e["reqs"]) for e in after) == 2 * cell.cfg.batch_size
    assert all(e["kind"] == "round" and all(e["ok"]) for e in after)
    stalls = cell.driver.stalls_ms(obs)
    assert len(stalls) >= len(sweeps) - 2 and min(stalls) > 0
    guarantees = cell.config["guarantees"]
    # the second control: an oracle that neither expires nor reclaims
    numbers = control.no_reclaim_numbers(cell.log.entries, guarantees)
    assert numbers["recipient_count_gap"] > 0
    assert not compare.verdict(numbers)[0]
    # the first, on a log with sweeps in it
    ctl = compare.replay(cell.log.entries, guarantees,
                         answered=control.control_in_place(guarantees))
    assert ctl["sweep_evicted_gap"] == 0 or ctl["ops_wrong"] > 0
    # a sweep one round later in the log than the engine ran it
    entries = cell.log.entries
    moved = caught = 0
    for i, e in enumerate(entries[:-1]):
        if e["kind"] == "sweep" and e["evicted"]:
            late = list(entries)
            late[i], late[i + 1] = late[i + 1], late[i]
            r = compare.replay(late, guarantees)
            moved += 1
            caught += r["ops_wrong"] + r["sweep_evicted_gap"] > 0
    assert moved >= 1 and caught >= 1


#: what the next configuration, ``chipshare-2p20-r65536``, brings as
#: its toy files: a mailbox tree taller than the batch covers (1,024
#: recipients: 9 levels; 32 accesses a pass cover 6 of them)
R65536 = "chipshare-2p20-r65536"
R65536_MIX = "backlog-mixed-r32768"


def _checkout_with_a_third_configuration(tmp_path) -> str:
    """A copy of the benchmark's data files with one configuration, one
    mix and one cell more, each a new file or a new entry."""
    root = tmp_path / "checkout"
    for sub in ("configs", "traffic", "layer_metrics",
                os.path.join("tests", "data", "configs"),
                os.path.join("tests", "data", "traffic")):
        shutil.copytree(os.path.join(ROOT, "benchmarks", sub),
                        root / "benchmarks" / sub)
    before = {str(p): p.read_bytes() for p in root.rglob("*.json")}
    bench = Benchmark.load()
    manifest = json.loads(json.dumps(bench.manifest))
    real = bench.config("chipshare-2p20")
    real["name"] = R65536
    real["grapevine_config"]["max_recipients"] = 1 << 16
    real["guarantees"]["max_recipients"] = 1 << 16
    (root / "benchmarks" / "configs" / f"{R65536}.json").write_text(
        json.dumps(real))
    mix = dict(bench.traffic("backlog-mixed"), name=R65536_MIX,
               identities=1 << 15)
    (root / "benchmarks" / "traffic" / f"{R65536_MIX}.json").write_text(
        json.dumps(mix))
    toy = json.load(open(os.path.join(ROOT, TOY_DATA, "configs",
                                      "chipshare-2p20.json")))
    toy["name"] = R65536
    toy["grapevine_config"]["max_recipients"] = 1024
    toy["guarantees"]["max_recipients"] = 1024
    (root / TOY_DATA / "configs" / f"{R65536}.json").write_text(
        json.dumps(toy))
    toy_mix = dict(json.load(open(os.path.join(
        ROOT, TOY_DATA, "traffic", "backlog-mixed.json"))),
        name=R65536_MIX, identities=512)
    (root / TOY_DATA / "traffic" / f"{R65536_MIX}.json").write_text(
        json.dumps(toy_mix))
    manifest["configs"].append(
        {**manifest["configs"][0], "name": R65536,
         "file": f"benchmarks/configs/{R65536}.json"})
    manifest["workloads"].append(
        {**bench.cell("backlog-1chip"), "name": "backlog-1chip-r65536",
         "config": R65536, "traffic": R65536_MIX})
    for group in ("end_to_end", "per_layer"):
        for m in manifest[group]:
            if "backlog-1chip" in m.get("workloads", []):
                m["workloads"].append("backlog-1chip-r65536")
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    after = {str(p): p.read_bytes() for p in root.rglob("*.json")}
    assert all(after[p] == data for p, data in before.items()), \
        "no file that was there is edited"
    assert len(after) == len(before) + 5
    return str(root)


def test_a_third_configuration_comes_from_new_files_alone(tmp_path, capsys):
    root = _checkout_with_a_third_configuration(tmp_path)
    bench = toy_bench(tmp_path / "base", root)
    bench.check_files()
    r = harness.run_cell(bench, "backlog-1chip-r65536", 2**31 + 27, 2.0,
                         True, time.perf_counter(), str(tmp_path))
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert {"round_ms", "loadgen_own_ms", "host_floor_ms"} <= set(r["metrics"])
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    mailbox = next(x for x in lines if x.get("phase") == "init")[
        "geometry"]["trees"]["mailbox"]
    # dense levels 4 and 5 whole, levels 6 to 8 a row per access
    assert (mailbox["path_len"], mailbox["accesses"]) == (9, 32)
    assert mailbox["rows_per_pass"] == 16 + 32 + 3 * 32
    # the cells that were there rehearse from the same checkout
    assert toy_bench(tmp_path / "base", root).cell("backlog-1chip")


def test_a_configuration_with_no_toy_geometry_is_refused_by_name(tmp_path):
    root = _checkout_with_a_third_configuration(tmp_path)
    os.remove(os.path.join(root, TOY_DATA, "configs", f"{R65536}.json"))
    with pytest.raises(ManifestError, match=R65536):
        toy_bench(tmp_path / "base", root)


def _break_resolve(monkeypatch, tamper):
    """Alter what the engine's round hands back, where it is produced."""
    from grapevine_tpu.engine.batcher import PendingRound

    real = PendingRound.resolve
    calls = {"n": 0, "done": False}

    def broken(self):
        out = real(self)
        calls["n"] += 1
        if calls["n"] < 5 or calls["done"]:
            return out  # once, after the window has opened
        out, calls["done"] = tamper(out)
        return out

    monkeypatch.setattr(PendingRound, "resolve", broken)


def _flip_payload_byte(resps):
    for r in resps:
        if r.status_code == 1:
            p = bytearray(r.record.payload)
            p[17] ^= 0x40
            r.record.payload = bytes(p)
            return resps, True
    return resps, False


def _drop_an_answer(resps):
    return resps[:-1], True


@pytest.mark.parametrize("cell", _cells()[:2])
@pytest.mark.parametrize("tamper", [_flip_payload_byte, _drop_an_answer])
def test_a_broken_answer_is_not_correct(cell, tamper, tmp_path, monkeypatch):
    from benchmarks.drivers import grpc_openloop, scheduler_backlog

    monkeypatch.setattr(scheduler_backlog, "STALL_S", 3.0)
    monkeypatch.setattr(grpc_openloop, "DRAIN_S", 3.0)
    _break_resolve(monkeypatch, tamper)
    r = _run(cell, tmp_path)
    assert r["correct"] is False and r["failed"] >= 1
    assert any(x["value"] > x["limit"] for x in r["compared"].values())


def test_a_stash_overflow_is_not_correct(tmp_path, monkeypatch):
    from grapevine_tpu.engine.batcher import GrapevineEngine

    real = GrapevineEngine.health
    monkeypatch.setattr(GrapevineEngine, "health",
                        lambda self: dict(real(self), stash_overflow=3))
    r = _run(_cells()[0], tmp_path)
    assert r["correct"] is False
    assert r["failed"] == 0  # every answer was right; the guarantee was not


def test_a_round_that_returns_its_state_unchanged_is_not_correct(
        tmp_path, monkeypatch):
    """The engine answers, but its step forgets what it wrote."""
    from grapevine_tpu.engine.batcher import GrapevineEngine

    real = GrapevineEngine._dispatch_round

    def forgetful(self, batch):
        before = self.state
        if getattr(self, "_rounds_seen", 0) >= 4:
            import jax

            before = jax.tree.map(lambda x: x.copy(), self.state)
        out = real(self, batch)
        self._rounds_seen = getattr(self, "_rounds_seen", 0) + 1
        if self._rounds_seen == 5:
            self.state = before
        return out

    monkeypatch.setattr(GrapevineEngine, "_dispatch_round", forgetful)
    r = _run(_cells()[0], tmp_path)
    assert r["correct"] is False and r["failed"] >= 1


def _command(cwd, env_extra):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", _cells()[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_py_refuses_anything_but_a_tpu():
    p = _command(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode == 2
    assert "needs 1 TPU chip" in p.stderr
    assert not any(line.startswith('{"correct"')
                   for line in p.stdout.splitlines())


def test_run_py_fails_without_the_program(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns(".scratch", "__pycache__"))
    p = _command(str(tmp_path), {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert not any(line.startswith('{"correct"')
                   for line in p.stdout.splitlines())
