"""What PR 22 changed about how the program meets a chip, checked on the
CPU and without compiling an engine round: where the compile cache
goes, bench.py's refusals and exit codes, chip_smoke.py's refusal, the
"no silent fallback" rules (platform, device kind, native library), and
direct sharded placement of a restored state."""

from __future__ import annotations

import logging
import os
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


# -- the compile cache is placed from outside ---------------------------


@pytest.fixture
def config_updates(monkeypatch):
    """jax.config.update calls, recorded instead of applied (a test must
    not switch the persistent cache on for the rest of its worker)."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    return calls


def test_compile_cache_env_var_wins_and_nothing_else_is_set(
    monkeypatch, config_updates
):
    from grapevine_tpu.config import setup_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert setup_compile_cache() == "/some/dir"
    assert config_updates == []


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(
    monkeypatch, config_updates
):
    from grapevine_tpu.config import setup_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert setup_compile_cache() == want
    assert setup_compile_cache() == want  # it does not move
    assert set(config_updates) == {("jax_compilation_cache_dir", want)}
    ignored = open(os.path.join(REPO, ".gitignore")).read().split()
    assert ".jax_cache/" in ignored


# -- bench.py: no fallback, and an exit code that means something -------


@pytest.fixture
def bench(monkeypatch):
    import bench as mod

    import grapevine_tpu.config as gcfg

    # no trajectory line, no cache switched on, from a test
    monkeypatch.setattr(mod, "_append_trajectory", lambda line, tag: None)
    monkeypatch.setattr(gcfg, "setup_compile_cache", lambda: "unused")
    return mod


def test_bench_without_smoke_refuses_a_machine_without_a_tpu(
    bench, monkeypatch, capsys
):
    ran = []
    monkeypatch.setattr(bench, "CONFIGS",
                        [("never", lambda smoke: ran.append(1) or {})])
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    assert bench.main() != 0
    assert ran == []
    out = capsys.readouterr()
    assert "no TPU" in out.err
    assert out.out == ""  # no result line, not even an empty snapshot


_TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def test_bench_exits_nonzero_when_a_config_raised(bench, monkeypatch, capsys):
    def boom(smoke):
        raise RuntimeError("kernel refused")

    monkeypatch.setattr(bench, "_device", lambda: dict(_TPU))
    monkeypatch.setattr(bench, "CONFIGS",
                        [("boom", boom), ("fine", lambda smoke: {"n": 1})])
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    assert bench.main() == 1
    import json

    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "kernel refused" in last["configs"]["boom"]["error"]
    assert last["configs"]["fine"] == {"n": 1}  # the others still ran
    assert last["device"] == _TPU and last["sizes"] == "full"


def test_bench_exits_zero_when_every_config_passed(bench, monkeypatch, capsys):
    monkeypatch.setattr(bench, "_device", lambda: dict(_TPU))
    monkeypatch.setattr(bench, "CONFIGS", [("fine", lambda smoke: {"n": 1})])
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    assert bench.main() == 0


def test_bench_leaves_mesh_configs_out_on_one_device(
    bench, monkeypatch, capsys
):
    ran = []
    monkeypatch.setattr(bench, "_device", lambda: dict(_TPU))
    monkeypatch.setattr(bench, "CONFIGS", [
        ("sharded", lambda smoke: ran.append("sharded") or {}),
        ("fine", lambda smoke: ran.append("fine") or {}),
    ])
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    assert bench.main() == 0
    assert ran == ["fine"]
    assert "['sharded']" in capsys.readouterr().err


def test_bench_starts_no_child_process():
    """One process for each chip: a bench that has touched JAX holds
    the chip, so nothing it starts may need it."""
    src = open(os.path.join(REPO, "bench.py")).read()
    assert "subprocess" not in src and "Popen" not in src


# -- chip_smoke.py -------------------------------------------------------


def test_chip_smoke_refuses_without_a_tpu_before_building_anything(
    monkeypatch, capsys
):
    import chip_smoke

    built = []
    monkeypatch.setattr(chip_smoke, "build_native", lambda: built.append(1))
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    assert chip_smoke.main() == 2
    out = capsys.readouterr()
    assert built == [] and out.out == ""
    assert "no TPU" in out.err


# -- no silent fallback --------------------------------------------------


def test_on_tpu_knows_two_platforms_and_refuses_the_rest(monkeypatch):
    from grapevine_tpu.config import on_tpu

    assert on_tpu() is False  # the tests' CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert on_tpu() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="unsupported JAX platform 'gpu'"):
        on_tpu()


def test_costmon_peak_is_keyed_by_device_kind_and_unknown_is_an_error(
    monkeypatch,
):
    from grapevine_tpu.obs import costmon

    class Dev:
        device_kind = "TPU v5 lite"

    monkeypatch.delenv("GRAPEVINE_COST_GBPS", raising=False)
    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    assert costmon.resolve_bandwidth_gbps() == 819.0
    Dev.device_kind = "TPU v9 imaginary"
    with pytest.raises(ValueError, match="TPU v9 imaginary"):
        costmon.resolve_bandwidth_gbps()
    assert costmon.resolve_bandwidth_gbps(5.0) == 5.0  # override still wins


def test_native_library_state_is_logged_with_its_reason(monkeypatch, caplog):
    from grapevine_tpu import native

    log = logging.getLogger("test.native")
    with caplog.at_level(logging.INFO, logger="test.native"):
        native.log_state(log)
    if native.lib is not None:
        assert caplog.records[-1].levelno == logging.INFO
    monkeypatch.setattr(native, "lib", None)
    monkeypatch.setattr(native, "load_error", "building r255.c failed: no cc")
    with caplog.at_level(logging.INFO, logger="test.native"):
        native.log_state(log)
    rec = caplog.records[-1]
    assert rec.levelno == logging.WARNING
    assert "no cc" in rec.getMessage() and "pure-Python" in rec.getMessage()


# -- four chips: nothing is staged on the first device -------------------


def _tiny_cfg(**kw):
    from grapevine_tpu.config import GrapevineConfig

    return GrapevineConfig(
        max_messages=64, max_recipients=8, mailbox_cap=4, batch_size=4,
        stash_size=64, **kw,
    )


def test_sharded_engine_state_is_created_sharded():
    from grapevine_tpu.engine.batcher import GrapevineEngine

    eng = GrapevineEngine(
        _tiny_cfg(shards=2, bucket_cipher_impl="pallas"), seed=1
    )
    for tree in (eng.state.rec, eng.state.mb):
        shards = tree.tree_val.addressable_shards
        assert len({s.device for s in shards}) == 2
        assert all(s.data.shape[0] * 2 == tree.tree_val.shape[0]
                   for s in shards)
    assert len(eng.state.freelist.sharding.device_set) == 2  # replicated


def test_restored_checkpoint_lands_on_its_shardings_not_on_device_zero():
    from grapevine_tpu.engine.checkpoint import bytes_to_state, state_to_bytes
    from grapevine_tpu.engine.state import EngineConfig
    from grapevine_tpu.parallel import init_sharded_engine, make_mesh

    ecfg = EngineConfig.from_config(_tiny_cfg(shards=2))
    mesh = make_mesh(jax.devices()[:2])
    state = init_sharded_engine(ecfg, mesh, seed=5)
    shardings = jax.tree.map(lambda x: x.sharding, state)
    back = bytes_to_state(ecfg, state_to_bytes(ecfg, state), shardings)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(back)):
        assert b.sharding == a.sharding
        assert np.array_equal(np.asarray(a), np.asarray(b))
    plain = bytes_to_state(ecfg, state_to_bytes(ecfg, state))
    assert len(plain.rec.tree_val.sharding.device_set) == 1
