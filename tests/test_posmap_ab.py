"""Flat↔recursive↔oracle position-map equivalence (PR 7 tentpole).

The contract of ``GrapevineConfig.posmap_impl="recursive"``
(oram/posmap.py), following the PR-3/PR-5 selectable-impl playbook:

1. responses AND the final payload-facing engine state bit-identical to
   the flat map — randomized oracle campaigns over same-key-chain-heavy
   mixes, saturation fallback, single-op batches (and batch_size=1
   geometry under ``-m slow``), with the logical position table proven
   equal through every round via the test-only ``read_table`` view;
2. the leak monitor stays PASS with the recursive map's internal
   accesses included in the transcript (the appended ``*_pm`` columns /
   streams);
3. a flat checkpoint can never silently restore into a recursive
   engine, nor the reverse — the geometry fingerprint covers the
   posmap spec (the ISSUE-7 small-fix satellite);
4. crash recovery stays bit-identical with ``posmap_impl="recursive"``
   (chaos kill trials under ``-m slow``).

Always-on cost is one flat + one recursive engine compile (plaintext,
reused across every always-on assertion below, per the ROADMAP 5-8 s
rule); cipher pairs, regime breadth, and chaos ride ``-m slow``.
"""

from __future__ import annotations

import os
import random
import sys

import numpy as np
import pytest

from test_vphases import (
    BASE,
    NOW,
    SAT_BUS,
    SAT_RECIP,
    _assert_responses_bitequal,
    _campaign_plan,
    _gen_batch,
    key,
    req,
)

from grapevine_tpu.config import GrapevineConfig
from grapevine_tpu.engine.batcher import GrapevineEngine
from grapevine_tpu.oram.posmap import read_table
from grapevine_tpu.testing.reference import ReferenceEngine
from grapevine_tpu.wire import constants as C

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: payload-facing OramState fields — everything except the posmap pytree
#: and the (recursive-only) leaf-metadata planes, whose *logical* content
#: is compared via read_table instead
_TREE_FIELDS = ("tree_idx", "tree_val", "stash_idx", "stash_val",
                "overflow", "nonces", "cipher_key", "epoch")
_SCALAR_FIELDS = ("freelist", "free_top", "recipients", "seq",
                  "hash_key", "id_key", "rng")


def _mk_posmap_pair(cfg_kwargs, seed):
    flat = GrapevineEngine(
        GrapevineConfig(posmap_impl="flat", **cfg_kwargs), seed=seed
    )
    rec = GrapevineEngine(
        GrapevineConfig(posmap_impl="recursive", **cfg_kwargs), seed=seed
    )
    return flat, rec


def _assert_payload_state_bitequal(ef, er, ctx=""):
    """Final-state contract: every payload-facing leaf equal bitwise;
    the position maps equal as logical tables."""
    for tree in ("rec", "mb"):
        of, orc = getattr(ef.state, tree), getattr(er.state, tree)
        for f in _TREE_FIELDS:
            assert np.array_equal(
                np.asarray(getattr(of, f)), np.asarray(getattr(orc, f))
            ), f"{ctx}: {tree}.{f} diverges flat vs recursive"
        cfg = getattr(ef.ecfg, tree)
        rcfg = getattr(er.ecfg, tree)
        assert np.array_equal(
            np.asarray(of.posmap)[: cfg.blocks], read_table(rcfg, orc.posmap)
        ), f"{ctx}: {tree} logical position table diverges"
        assert int(orc.posmap.inner.overflow) == 0, (
            f"{ctx}: internal posmap ORAM overflowed"
        )
    for f in _SCALAR_FIELDS:
        assert np.array_equal(
            np.asarray(getattr(ef.state, f)), np.asarray(getattr(er.state, f))
        ), f"{ctx}: {f} diverges"


def _run_pm_campaign(cfg_kwargs, seed, n_batches=3, batch_fill=None,
                     pair=None, sweep=False):
    """One campaign: flat/recursive pair + oracle over mixed batches.

    ``pair`` reuses already-compiled engines (fresh engines otherwise);
    reusing keeps the always-on cost at one compile per impl."""
    rng = np.random.default_rng(seed)
    ef, er = pair or _mk_posmap_pair(
        cfg_kwargs, seed=int(rng.integers(1 << 30))
    )
    oracle = None
    if pair is None:
        oracle = ReferenceEngine(
            config=GrapevineConfig(**cfg_kwargs), rng=random.Random(seed)
        )
    idents = [key(i) for i in range(1, 1 + int(rng.integers(2, 6)))]
    live_ids: list[tuple[bytes, bytes]] = []
    bs = cfg_kwargs["batch_size"]
    for bi in range(n_batches):
        n = batch_fill or int(rng.integers(1, bs + 1))
        reqs = _gen_batch(rng, idents, live_ids, n)
        t = NOW + bi
        rf = ef.handle_queries(reqs, t)
        rr = er.handle_queries(reqs, t)
        _assert_responses_bitequal(rf, rr, f"posmap seed {seed} batch {bi}")
        if oracle is not None:
            forced = [
                d.record.msg_id
                if r.request_type == C.REQUEST_TYPE_CREATE
                and d.status_code == C.STATUS_CODE_SUCCESS
                else None
                for r, d in zip(reqs, rf)
            ]
            ro = oracle.handle_batch(reqs, t, forced)
            for j, (d, o) in enumerate(zip(rf, ro)):
                assert d.status_code == o.status_code, (
                    f"posmap seed {seed} batch {bi} slot {j}: engine "
                    f"{d.status_code} != oracle {o.status_code}"
                )
                assert d.record.msg_id == o.record.msg_id
                assert d.record.payload == o.record.payload
            assert ef.message_count() == oracle.message_count()
            assert ef.recipient_count() == oracle.recipient_count()
        for r, d in zip(reqs, rf):
            if (r.request_type == C.REQUEST_TYPE_CREATE
                    and d.status_code == C.STATUS_CODE_SUCCESS):
                live_ids.append((d.record.msg_id, r.record.recipient))
            elif (r.request_type == C.REQUEST_TYPE_DELETE
                    and d.status_code == C.STATUS_CODE_SUCCESS):
                live_ids = [
                    (m, o_) for m, o_ in live_ids if m != d.record.msg_id
                ]
    if sweep:
        ef.expire(NOW + 10_000, 5_000)
        er.expire(NOW + 10_000, 5_000)
    _assert_payload_state_bitequal(ef, er, f"posmap seed {seed}")
    return ef, er


# -- always-on: one compiled pair carries every fast assertion ----------


def test_posmap_ab_campaign_with_sweep_leakmon_and_single_op():
    """The budget-shaped always-on path: ONE flat + ONE recursive engine
    (plaintext BASE geometry) run a randomized oracle campaign, then an
    expiry sweep, then single-op (dummy-padded) batches, then a leakmon
    soak — every stage asserting bit-identity, with zero additional
    compiles after the first round."""
    ef, er = _run_pm_campaign(BASE, seed=4100, n_batches=4, sweep=True)

    # single-op batches on the same compiled pair (fill=1 → 7 dummies)
    _run_pm_campaign(BASE, seed=4101, n_batches=2, batch_fill=1,
                     pair=(ef, er))

    # leak monitor with the internal accesses in the transcript: the
    # recursive engine's verdict must be PASS and the pm streams must
    # actually be observing (window fills)
    from grapevine_tpu.obs.leakmon import EngineLeakMonitor, LeakMonitorConfig

    mon = EngineLeakMonitor.for_engine(
        er, LeakMonitorConfig(window_rounds=64)
    )
    assert set(mon.monitor.streams) == {"rec", "mb", "rec_pm", "mb_pm"}
    er.attach_leakmon(mon)
    rng = np.random.default_rng(77)
    idents = [key(i) for i in range(1, 5)]
    live: list[tuple[bytes, bytes]] = []
    for bi in range(12):
        reqs = _gen_batch(rng, idents, live, 8)
        er.handle_queries(reqs, NOW + 100 + bi)
    assert mon.flush(), "leak monitor did not drain"
    v = mon.verdict()
    assert v["verdict"] == "PASS", v
    pm_stats = mon.monitor.stats("rec_pm")
    assert pm_stats["pooled_leaves"] > 0, "rec_pm stream saw no leaves"
    mon.close()


def test_posmap_checkpoint_fingerprint_rejects_cross_impl(tmp_path):
    """ISSUE-7 small fix: a flat checkpoint must fail loudly against a
    recursive engine (and vice versa) — the geometry fingerprint covers
    ``posmap_impl`` and the recursion geometry via the embedded
    PosMapSpec, so the mismatch is a CheckpointError, never a silent
    misload. Pure serialization — no engine compile."""
    from grapevine_tpu.engine.checkpoint import (
        CheckpointError,
        bytes_to_state,
        engine_fingerprint,
        state_to_bytes,
    )
    from grapevine_tpu.engine.state import EngineConfig, init_engine

    kw = dict(BASE, max_messages=32, batch_size=4)
    ecf = EngineConfig.from_config(GrapevineConfig(posmap_impl="flat", **kw))
    ecr = EngineConfig.from_config(
        GrapevineConfig(posmap_impl="recursive", **kw)
    )
    assert engine_fingerprint(ecf) != engine_fingerprint(ecr)
    blob_f = state_to_bytes(ecf, init_engine(ecf, seed=1))
    blob_r = state_to_bytes(ecr, init_engine(ecr, seed=1))
    assert bytes_to_state(ecf, blob_f) is not None  # control: self-loads
    with pytest.raises(CheckpointError, match="fingerprint"):
        bytes_to_state(ecr, blob_f)  # flat ckpt → recursive engine
    with pytest.raises(CheckpointError, match="fingerprint"):
        bytes_to_state(ecf, blob_r)  # recursive ckpt → flat engine

    # recursion geometry is fingerprinted too, not just the impl name:
    # same impl, different k must also refuse
    from dataclasses import replace

    from grapevine_tpu.oram.posmap import derive_posmap_spec

    spec2 = derive_posmap_spec(32, entries_per_block=2)
    ecr2 = replace(ecr, rec=replace(ecr.rec, posmap=spec2))
    assert engine_fingerprint(ecr2) != engine_fingerprint(ecr)
    with pytest.raises(CheckpointError, match="fingerprint"):
        bytes_to_state(ecr2, blob_r)


def test_posmap_impl_validation():
    with pytest.raises(ValueError, match="posmap_impl"):
        GrapevineConfig(posmap_impl="pyramid")
    with pytest.raises(ValueError, match="posmap_impl"):
        GrapevineConfig(commit="op", posmap_impl="recursive")
    # auto resolves to flat (until a measured win flips it — PERF.md R9)
    from grapevine_tpu.engine.state import EngineConfig

    ecfg = EngineConfig.from_config(GrapevineConfig(**BASE))
    assert ecfg.posmap_impl == "flat"
    assert ecfg.rec.posmap is None and ecfg.mb.posmap is None


# -- slow: breadth, cipher, regimes, batch_size=1 geometry, chaos -------


@pytest.mark.slow
def test_randomized_posmap_ab_campaigns_full():
    """Regime breadth: steady-state, bus/recipient saturation fallback,
    single-op batches — fresh pairs + oracle per campaign."""
    n = int(os.environ.get("GRAPEVINE_POSMAP_CAMPAIGNS", "20"))
    for i, (cfg, fill) in enumerate(_campaign_plan(n)):
        _run_pm_campaign(cfg, seed=4200 + i, batch_fill=fill)


@pytest.mark.slow
def test_posmap_ab_campaign_cipher_on():
    """The at-rest cipher pair: the leaf-metadata plane's ride on the
    bucket cipher (decrypt/re-encrypt per fetch, epoch re-key in the
    expiry sweep) must preserve bit-identity end to end."""
    cfg = dict(BASE, bucket_cipher_rounds=8)
    _run_pm_campaign(cfg, seed=4300, n_batches=4, sweep=True)


@pytest.mark.slow
def test_posmap_single_op_batch_geometry():
    """batch_size=1 end to end: the recursive lookup round at B=1
    (degenerate dedup segments) stays bit-identical and oracle-true."""
    cfg = dict(BASE, batch_size=1)
    for i in range(3):
        _run_pm_campaign(cfg, seed=4500 + i, n_batches=6, batch_fill=1)


@pytest.mark.slow
def test_posmap_saturation_fallback_bitequal():
    """Bus saturation: rounds resolve through _admission_slow with the
    recursive map in the loop and must stay bit-identical, including
    TOO_MANY_MESSAGES admission order."""
    ef, er = _mk_posmap_pair(SAT_BUS, seed=9)
    a, x = key(1), key(2)
    for bi in range(3):
        reqs = [
            req(C.REQUEST_TYPE_CREATE, a, recipient=x, tag=bi * 8 + j)
            for j in range(8)
        ]
        rf = ef.handle_queries(reqs, NOW + bi)
        rr = er.handle_queries(reqs, NOW + bi)
        _assert_responses_bitequal(rf, rr, f"sat batch {bi}")
    codes = {r.status_code for r in rf}
    assert C.STATUS_CODE_TOO_MANY_MESSAGES in codes
    _assert_payload_state_bitequal(ef, er, "saturation")
    # recipient-table saturation regime as well
    _run_pm_campaign(SAT_RECIP, seed=4600, n_batches=3)


@pytest.mark.slow
def test_chaos_recovery_with_recursive_posmap():
    """SIGKILL trials with posmap_impl='recursive': recovered state and
    every response hash bit-identical to the uninterrupted oracle, leak
    monitor PASS across recovery (tools/chaos_run.py --posmap-impl)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import chaos_run

    args = chaos_run.parse_args(
        ["--events", "14", "--posmap-impl", "recursive", "--seed", "41"]
    )
    failures = chaos_run.run_trials(3, args)
    assert not failures, "\n".join(failures)
