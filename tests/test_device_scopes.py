"""The device scopes (obs/phases.py DEVICE_SCOPES): a fixed list that
``device_phase`` enforces, every equation of the traced round under one
of them, and no effect on what the compiler builds."""

import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from grapevine_tpu.analysis.jaxpr_walk import _sub_jaxprs
from grapevine_tpu.config import GrapevineConfig
from grapevine_tpu.engine.round_step import engine_round_step
from grapevine_tpu.engine.state import EngineConfig, init_engine
from grapevine_tpu.obs import phases
from grapevine_tpu.obs.phases import DEVICE_SCOPES, device_phase

SCOPE = re.compile(r"grapevine/([A-Za-z0-9_]+)")


def _toy(**kw):
    cfg = GrapevineConfig(max_messages=64, max_recipients=16, batch_size=4,
                          **kw)
    ecfg = EngineConfig.from_config(cfg)
    state = jax.eval_shape(lambda: init_engine(ecfg, 0))
    u32 = jnp.uint32
    b = ecfg.batch_size
    from grapevine_tpu.engine.state import ID_WORDS, KEY_WORDS, PAYLOAD_WORDS

    batch = {
        "req_type": jax.ShapeDtypeStruct((b,), u32),
        "auth": jax.ShapeDtypeStruct((b, KEY_WORDS), u32),
        "msg_id": jax.ShapeDtypeStruct((b, ID_WORDS), u32),
        "recipient": jax.ShapeDtypeStruct((b, KEY_WORDS), u32),
        "payload": jax.ShapeDtypeStruct((b, PAYLOAD_WORDS), u32),
        "now": jax.ShapeDtypeStruct((), u32),
        "now_hi": jax.ShapeDtypeStruct((), u32),
    }
    return ecfg, state, batch


def _walk(jaxpr, outer: str = ""):
    """(primitive, name stack with every enclosing equation's in front,
    whether the equation holds no jaxpr of its own) for every equation."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in inner.eqns:
        stack = f"{outer}/{eqn.source_info.name_stack}"
        subs = list(_sub_jaxprs(eqn))
        yield eqn.primitive.name, stack, not subs
        for sub in subs:
            yield from _walk(sub, stack)


def _unscoped(jaxpr):
    """Leaf equations whose own name stack and every enclosing
    equation's carry no ``grapevine/`` scope."""
    return [(prim, stack) for prim, stack, leaf in _walk(jaxpr)
            if leaf and not SCOPE.search(stack)]


def _scopes_used(jaxpr):
    return {name for _, stack, _ in _walk(jaxpr)
            for name in SCOPE.findall(stack)}


def test_device_phase_refuses_a_name_outside_the_list():
    with pytest.raises(ValueError, match="not a device scope"):
        device_phase("op_read_client_7")
    with pytest.raises(ValueError, match="not a device scope"):
        device_phase("oram_fetch ")  # a typo mints no new path
    assert len(set(DEVICE_SCOPES)) == len(DEVICE_SCOPES)
    for name in DEVICE_SCOPES:
        assert re.fullmatch(r"[a-z0-9_]+", name)
        with device_phase(name):
            pass


@pytest.mark.parametrize("knobs", [
    {},
    {"posmap_impl": "recursive"},
], ids=["default", "recursive-posmap"])
def test_every_equation_of_the_round_sits_under_a_device_scope(knobs):
    """Trace only, no compile: each leaf equation of the round program
    carries (itself or through the call it sits in) a ``grapevine/``
    scope, and every scope met is one of DEVICE_SCOPES."""
    ecfg, state, batch = _toy(**knobs)
    jaxpr = jax.make_jaxpr(
        lambda s, b: engine_round_step(ecfg, s, b))(state, batch)
    bare = _unscoped(jaxpr)
    assert not bare, f"{len(bare)} equations under no scope: {bare[:8]}"
    used = _scopes_used(jaxpr)
    assert used <= set(DEVICE_SCOPES), used - set(DEVICE_SCOPES)
    # the three tree rounds and their four stages are all there
    assert {"round_a_mailbox", "round_b_records", "round_c_mailbox",
            "oram_fetch", "oram_apply", "oram_evict", "request_unpack",
            "freelist_counters", "respond", "path_gather",
            "cipher_decrypt", "posmap", "dedup",
            "oram_writeback", "cipher_encrypt", "path_scatter",
            "oram_evict_sort", "stash_compact"} <= used


def test_the_mesh_round_names_its_psum_assembly():
    from grapevine_tpu.parallel.mesh import TREE_AXIS

    ecfg, state, batch = _toy()
    jaxpr = jax.make_jaxpr(
        lambda s, b: engine_round_step(ecfg, s, b, axis_name=TREE_AXIS),
        axis_env=[(TREE_AXIS, 2)])(state, batch)
    psums = [stack for prim, stack, _ in _walk(jaxpr) if prim == "psum"]
    assert psums and all("grapevine/psum_assembly" in s for s in psums)
    assert not _unscoped(jaxpr)


def _hlo_ops(ecfg, state, batch) -> list[str]:
    """The compiled round's instructions as ``opcode shape`` lines,
    names and metadata left out."""
    compiled = jax.jit(
        lambda s, b: engine_round_step(ecfg, s, b)).lower(state, batch).compile()
    ops = []
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\S+) ([a-z\-]+)\(", line)
        if m:
            ops.append(f"{m.group(2)} {m.group(1)}")
    return ops


def test_scopes_change_nothing_the_compiler_builds(monkeypatch):
    """``named_scope`` is metadata: the compiled toy round has the same
    instructions, one for one, with the scopes and without them."""
    ecfg, state, batch = _toy()
    with_scopes = _hlo_ops(ecfg, state, batch)
    for mod in ("grapevine_tpu.engine.round_step", "grapevine_tpu.oram.round",
                "grapevine_tpu.oram.path_oram", "grapevine_tpu.oram.posmap",
                "grapevine_tpu.engine.responses"):
        monkeypatch.setattr(f"{mod}.device_phase",
                            lambda name: contextlib.nullcontext())
    without = _hlo_ops(ecfg, state, batch)
    assert phases.device_phase is device_phase  # the patch was local
    assert len(with_scopes) > 100
    assert with_scopes == without
