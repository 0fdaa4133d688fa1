"""xla-vs-radix sort engine equivalence: bit-identical rounds, no sort HLO.

The tentpole contract of the radix sort engine (oblivious/radix.py,
``GrapevineConfig.sort_impl="radix"``), mirroring PR 3's vphases
playbook (tests/test_vphases_scan.py):

1. responses AND final engine state bit-identical to the xla sorts —
   randomized oracle campaigns over same-key-chain-heavy mixes,
   saturation-fallback rounds, and single-op batches, reusing the
   vphases campaign harness with the sort knob as the only difference;
2. the radix ORAM round traces **zero** ``sort`` HLO ops (the xla impl
   as the positive control proving the counter sees them), and the
   radix engine round sheds every bounded-key sort — only the
   explicitly-gated wide-key sorts remain (the 256-bit recipient
   grouping and the u64 per-mailbox seq order);
3. the ``sort`` phase calibration registers under the telemetry
   registry without violating the leak policy.

The fast campaign set keeps tier-1 in budget; the full ≥200-campaign
sweep runs under ``-m slow`` (run at PR time — PERF.md Round 7). Set
$GRAPEVINE_SORT_CAMPAIGNS to override the fast count.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest

from test_vphases_scan import (
    BASE,
    SAT_BUS,
    _campaign_plan,
    _run_campaign,
)

from grapevine_tpu.config import GrapevineConfig
from grapevine_tpu.engine.batcher import GrapevineEngine
from grapevine_tpu.engine.state import (
    EngineConfig,
    ID_WORDS,
    KEY_WORDS,
    PAYLOAD_WORDS,
    init_engine,
)
from grapevine_tpu.oram.path_oram import OramConfig, init_oram
from grapevine_tpu.oram.round import oram_round

U32 = jnp.uint32


def _mk_sort_pair(vphases):
    def mk_pair(cfg_kwargs, seed):
        kw = dict(cfg_kwargs, vphases_impl=vphases)
        xla = GrapevineEngine(
            GrapevineConfig(sort_impl="xla", **kw), seed=seed
        )
        radix = GrapevineEngine(
            GrapevineConfig(sort_impl="radix", **kw), seed=seed
        )
        return xla, radix

    return mk_pair


_FAST_N = int(os.environ.get("GRAPEVINE_SORT_CAMPAIGNS", "6"))


@pytest.mark.slow  # ~29 s of jit compiles — moved off tier-1 in the
# ISSUE-19 budget audit to offset the always-on replication tests. The
# dense-vphases campaign below and the zero-sort-HLO trace audits keep
# the sort knob covered every run; this set and the 220-campaign
# acceptance sweep both ride -m slow.
def test_randomized_sort_ab_campaigns():
    """Budget-shaped fast set under vphases "scan" (the impl whose
    group sorts the knob actually swaps): steady-state, bus-saturation
    (the _admission_slow fallback — identical under both sort impls),
    and single-op batches. Cost is ~all jit compiles, so the plan spans
    two geometries like the vphases fast set."""
    mk = _mk_sort_pair("scan")
    for i, (cfg, fill) in enumerate(_campaign_plan(_FAST_N)):
        if cfg is not BASE:
            cfg = SAT_BUS  # both saturation regimes share _admission_slow
        _run_campaign(cfg, seed=7000 + i, batch_fill=fill, mk_pair=mk)


def test_sort_ab_campaign_dense_vphases():
    """One dense-vphases campaign: dense has no group sorts, but the
    admission walk's slot grouping and the ORAM eviction/dedup sorts
    still follow the knob — the pair must stay bit-identical there too."""
    _run_campaign(BASE, seed=7900, mk_pair=_mk_sort_pair("dense"))


@pytest.mark.slow
def test_randomized_sort_ab_campaigns_full():
    """The full ≥200-campaign acceptance sweep (run at PR time; kept
    under -m slow so tier-1 stays within its budget)."""
    mk = _mk_sort_pair("scan")
    mkd = _mk_sort_pair("dense")
    for i, (cfg, fill) in enumerate(_campaign_plan(220)):
        m = mkd if i % 5 == 4 else mk  # dense pairs ride the sweep too
        _run_campaign(cfg, seed=9000 + i, batch_fill=fill, mk_pair=m)


# ----------------------------------------------------------------------
# jaxpr sort audit: the radix round traces ZERO sort HLO ops
# ----------------------------------------------------------------------


def _count_sorts(jaxpr):
    n, stack, seen = 0, [jaxpr], set()
    while stack:
        jx = stack.pop()
        if id(jx) in seen:
            continue
        seen.add(id(jx))
        for eqn in jx.eqns:
            if eqn.primitive.name == "sort":
                n += 1
            for v in eqn.params.values():
                vs = v if isinstance(v, (list, tuple)) else (v,)
                for x in vs:
                    inner = getattr(x, "jaxpr", None)
                    if inner is not None and hasattr(inner, "eqns"):
                        stack.append(inner)
                    elif hasattr(x, "eqns"):
                        stack.append(x)
    return n


def _trace_oram_round(sort_impl, b=64):
    """The batched ORAM round standalone (scan dedup + eviction under
    the knob), with a pass-through apply callback."""
    cfg = OramConfig(height=6, value_words=4, n_blocks=128)
    state = jax.eval_shape(lambda: init_oram(cfg, jax.random.PRNGKey(0)))
    u = lambda *s: jax.ShapeDtypeStruct(s, jnp.uint32)  # noqa: E731

    def run(state, idxs, nl, dl):
        return oram_round(
            cfg, state, idxs, nl, dl,
            lambda vals0, present0: ({}, vals0, present0),
            occ_impl="scan", sort_impl=sort_impl,
        )

    return jax.make_jaxpr(run)(state, u(b), u(b), u(b)).jaxpr


def test_radix_oram_round_traces_zero_sort_hlo():
    assert _count_sorts(_trace_oram_round("radix")) == 0


def test_xla_oram_round_audit_positive_control():
    """The xla round DOES trace sorts — proving the counter sees the
    ops the radix test asserts away."""
    assert _count_sorts(_trace_oram_round("xla")) > 0


def _trace_engine_jaxpr(sort_impl, b=32):
    from grapevine_tpu.engine.round_step import engine_round_step

    cfg = GrapevineConfig(
        max_messages=1 << 10,
        max_recipients=1 << 6,
        mailbox_cap=4,
        batch_size=b,
        bucket_cipher_rounds=0,
        stash_size=128,
        vphases_impl="scan",
        sort_impl=sort_impl,
    )
    ecfg = EngineConfig.from_config(cfg)
    state = jax.eval_shape(lambda: init_engine(ecfg, 0))
    u32 = jnp.uint32
    batch = {
        "req_type": jax.ShapeDtypeStruct((b,), u32),
        "auth": jax.ShapeDtypeStruct((b, KEY_WORDS), u32),
        "msg_id": jax.ShapeDtypeStruct((b, ID_WORDS), u32),
        "recipient": jax.ShapeDtypeStruct((b, KEY_WORDS), u32),
        "payload": jax.ShapeDtypeStruct((b, PAYLOAD_WORDS), u32),
        "now": jax.ShapeDtypeStruct((), u32),
        "now_hi": jax.ShapeDtypeStruct((), u32),
    }
    return jax.make_jaxpr(functools.partial(engine_round_step, ecfg))(
        state, batch
    ).jaxpr


def test_radix_engine_round_sheds_bounded_sorts():
    """Whole engine round: radix removes every bounded-key sort; the
    residue is exactly the explicitly-gated wide-key sites (256-bit
    recipient grouping, u64 seq entry ordering) — strictly fewer sorts
    than xla and a fixed small count, so a new unbounded sort sneaking
    into the round fails CI here."""
    n_xla = _count_sorts(_trace_engine_jaxpr("xla"))
    n_radix = _count_sorts(_trace_engine_jaxpr("radix"))
    assert n_radix < n_xla, (n_radix, n_xla)
    assert n_radix <= 5, (
        f"radix engine round traces {n_radix} sort ops — more than the "
        f"gated wide-key residue; a bounded-key sort escaped the knob"
    )
