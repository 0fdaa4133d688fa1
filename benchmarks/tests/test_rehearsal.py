"""The harness itself, rehearsed on the CPU at a toy geometry (2^10
messages, B=16; the sharded configuration on four virtual devices), and
the self-test of ``correct``: the timed path broken underneath must come
out as not correct, through the same comparison code. ``run.py`` as a
command still refuses anything but a TPU."""

import os
import subprocess
import sys
import time

import pytest

from benchmarks.lib import harness
from benchmarks.lib.manifest import ROOT, Benchmark
from toy import toy_bench

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
