"""Delayed batched eviction — equivalence, cadence, canary, audit
(ISSUE 15 tentpole).

The contract of ``GrapevineConfig.evict_every = E`` (oram/round.py,
ROADMAP item 1 — the scatter+encrypt half of the round amortized 1/E):

1. responses bit-identical E=1 ↔ E>1 ↔ oracle at EVERY round, and the
   final LOGICAL state — live blocks, values, positions, freelist,
   scalars — bit-identical too (physical placement legitimately
   differs: E=1 evicts per round, E>1 evicts each window's
   deduplicated union of paths; testing/compare.py
   ``assert_logical_content_equal`` is the canonical form);
2. the fetch-only round is index-blind and performs ZERO HBM tree
   scatters; one flush scatters exactly ``flush_target_slots =
   min(E·F·path_len, n_buckets_padded)`` rows per plane
   (tools/check_tree_cache_oblivious.py:check_evict_round_accounting);
3. the buffer is bounded private state with the stash's standing:
   overflow rides the same sticky counter, ``health()`` exposes
   occupancy/capacity, and the ``grapevine_evict_buffer_*`` gauges
   track the near-overflow canary;
4. a buffer-bearing checkpoint can never silently restore into a
   differently-cadenced engine (fingerprint covers E via the per-tree
   window fields), and journal replay — KIND_FLUSH included —
   reproduces crashed runs bit-identically (chaos kill-at-flush);
5. the leak monitor stays PASS on a live E=4 soak (the flush cadence
   is not a timing channel), and the probe-campaign injector still
   flips SUSPECT (tests/test_load_harness.py breadth rides -m slow
   here).

Always-on cost: ONE E=1 + ONE E=4 engine compile (plaintext BASE
geometry, reused across the fast assertions incl. the leakmon soak) +
one tiny near-overflow engine + trace-only audits. Cipher/recursive/
scan-radix pairs, E breadth, chaos, and the scenario-runner soaks ride
``-m slow`` (the PR-5/9/10 tier-1 budget playbook).
"""

from __future__ import annotations

import os
import random
import sys

import numpy as np
import pytest

from test_vphases_scan import (
    BASE,
    NOW,
    _assert_responses_bitequal,
    _gen_batch,
    key,
)

from grapevine_tpu.config import GrapevineConfig
from grapevine_tpu.engine.batcher import GrapevineEngine
from grapevine_tpu.testing.compare import (
    assert_logical_content_equal,
    logical_block_map,
)
from grapevine_tpu.testing.reference import ReferenceEngine
from grapevine_tpu.wire import constants as C

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


def _mk_evict_pair(cfg_kwargs, seed, e=4):
    e1 = GrapevineEngine(
        GrapevineConfig(evict_every=1, **cfg_kwargs), seed=seed
    )
    ee = GrapevineEngine(
        GrapevineConfig(evict_every=e, **cfg_kwargs), seed=seed
    )
    return e1, ee


def _run_evict_campaign(cfg_kwargs, seed, n_batches=6, batch_fill=None,
                        pair=None, sweep=False, e=4):
    """One campaign: E=1/E pair + oracle over mixed batches, responses
    bit-equal per round, zero overflow, logical content equal at the
    end (typically MID-window for the E arm — the content contract
    must hold with live buffer state, not only at flush barriers)."""
    rng = np.random.default_rng(seed)
    e1, ee = pair or _mk_evict_pair(
        cfg_kwargs, seed=int(rng.integers(1 << 30)), e=e
    )
    oracle = None
    if pair is None:
        oracle = ReferenceEngine(
            config=GrapevineConfig(**cfg_kwargs), rng=random.Random(seed)
        )
    idents = [key(i) for i in range(1, 1 + int(rng.integers(2, 6)))]
    live_ids: list[tuple[bytes, bytes]] = []
    bs = cfg_kwargs["batch_size"]
    rounds0 = ee._rounds_since_flush  # reused pairs carry a live window
    for bi in range(n_batches):
        n = batch_fill or int(rng.integers(1, bs + 1))
        reqs = _gen_batch(rng, idents, live_ids, n)
        t = NOW + bi
        r1 = e1.handle_queries(reqs, t)
        re_ = ee.handle_queries(reqs, t)
        _assert_responses_bitequal(r1, re_, f"evict seed {seed} b {bi}")
        h1, he = e1.health(), ee.health()
        assert h1["stash_overflow"] == he["stash_overflow"] == 0
        # window invariant: the host cadence counter tracks the
        # state-side one (the recovery anchor)
        assert he["evict_rounds_since_flush"] == (rounds0 + bi + 1) % e
        occ = he["evict_buffer_occupancy"]
        caps = he["evict_buffer_slots"]
        assert set(occ) >= {"rec", "mb"}
        assert all(occ[k2] <= caps[k2] for k2 in ("rec", "mb"))
        if oracle is not None:
            forced = [
                d.record.msg_id
                if r.request_type == C.REQUEST_TYPE_CREATE
                and d.status_code == C.STATUS_CODE_SUCCESS
                else None
                for r, d in zip(reqs, r1)
            ]
            ro = oracle.handle_batch(reqs, t, forced)
            for j, (d, o) in enumerate(zip(r1, ro)):
                assert d.status_code == o.status_code, (
                    f"evict seed {seed} batch {bi} slot {j}: engine "
                    f"{d.status_code} != oracle {o.status_code}"
                )
                assert d.record.msg_id == o.record.msg_id
                assert d.record.payload == o.record.payload
            assert e1.message_count() == oracle.message_count()
            assert e1.recipient_count() == oracle.recipient_count()
        for r, d in zip(reqs, r1):
            if (r.request_type == C.REQUEST_TYPE_CREATE
                    and d.status_code == C.STATUS_CODE_SUCCESS):
                live_ids.append((d.record.msg_id, r.record.recipient))
            elif (r.request_type == C.REQUEST_TYPE_DELETE
                    and d.status_code == C.STATUS_CODE_SUCCESS):
                live_ids = [
                    (m, o_) for m, o_ in live_ids if m != d.record.msg_id
                ]
    if sweep:
        # mid-window sweep: stale-bucket masking + buffer sweep must
        # keep the two engines' logical content identical
        e1.expire(NOW + 10_000, 5_000)
        ee.expire(NOW + 10_000, 5_000)
    assert_logical_content_equal(
        e1.ecfg, e1.state, ee.ecfg, ee.state, f"evict seed {seed}"
    )
    return e1, ee


# -- always-on: one compiled pair carries every fast assertion ----------


def test_evict_campaign_with_sweep_and_leakmon():
    """The budget-shaped always-on path: ONE E=1 + ONE E=4 engine
    (plaintext BASE geometry) run a randomized oracle campaign crossing
    several flush boundaries, an expiry sweep mid-window, single-op
    batches, and a leakmon soak at E=4 — zero additional compiles
    after the first window."""
    e1, e4 = _run_evict_campaign(BASE, seed=7100, n_batches=9, sweep=True)
    assert e4.evict_every == 4
    assert e4.ecfg.rec.evict_window == 4
    assert e4.ecfg.mb.evict_window == 8  # two mailbox rounds per round

    # single-op batches on the same compiled pair
    _run_evict_campaign(BASE, seed=7101, n_batches=4, batch_fill=1,
                        pair=(e1, e4))

    # the flush really moves content back: after an exact window
    # boundary the buffer is empty and the tree holds the blocks
    # (pad with single READ rounds — an empty request list dispatches
    # no round, so it cannot advance the window)
    from test_vphases_scan import req

    while int(e4.state.rec.ebuf_rounds) % 4:
        e4.handle_queries([req(C.REQUEST_TYPE_READ, key(1))], NOW + 500)
    from grapevine_tpu.oram.path_oram import evict_buffer_occupancy

    assert int(evict_buffer_occupancy(e4.state.rec)) == 0
    assert int(e4.state.rec.ebuf_rounds) == 0

    # the near-overflow canary gauges exist and sampled something
    snap = e4.metrics.registry.snapshot()
    assert "grapevine_evict_buffer_occupancy" in snap
    assert snap["grapevine_evict_buffer_high_water"] > 0
    e4.metrics.registry.audit()  # the new gauges stay batch-level

    # acceptance: leak monitor PASS on a live soak at E=4 — the flush
    # cadence must not become a timing channel
    from grapevine_tpu.obs.leakmon import EngineLeakMonitor, LeakMonitorConfig

    mon = EngineLeakMonitor.for_engine(e4, LeakMonitorConfig(window_rounds=64))
    e4.attach_leakmon(mon)
    rng = np.random.default_rng(79)
    idents = [key(i) for i in range(1, 5)]
    live: list[tuple[bytes, bytes]] = []
    for bi in range(12):
        reqs = _gen_batch(rng, idents, live, 8)
        e4.handle_queries(reqs, NOW + 100 + bi)
    assert mon.flush(), "leak monitor did not drain"
    v = mon.verdict()
    assert v["verdict"] == "PASS", v
    mon.close()


def test_evict_config_validation():
    with pytest.raises(ValueError, match="evict_every"):
        GrapevineConfig(evict_every=0)
    with pytest.raises(ValueError, match="evict_every"):
        GrapevineConfig(commit="op", evict_every=2)
    with pytest.raises(ValueError, match="evict_buffer_slots"):
        GrapevineConfig(evict_buffer_slots=0)
    from grapevine_tpu.engine.state import EngineConfig

    # auto resolves to 1 (per-round eviction) on every backend: the
    # flush overlap is not measured on the chip
    auto = EngineConfig.from_config(GrapevineConfig(**BASE))
    assert auto.evict_every == 1
    assert auto.rec.evict_window == 1
    assert auto.rec.evict_buffer_slots == 0
    # E > 1: per-tree windows (rec E, mb 2E — rounds A and C), fetch
    # counts (B, B·D), and clamped auto buffer sizing
    e4 = EngineConfig.from_config(GrapevineConfig(evict_every=4, **BASE))
    assert (e4.rec.evict_window, e4.mb.evict_window) == (4, 8)
    b, d = e4.batch_size, e4.mb_choices
    assert e4.rec.evict_fetch_count == b
    assert e4.mb.evict_fetch_count == b * d
    from grapevine_tpu.oram.path_oram import derive_evict_buffer_slots

    # the clamp: a buffer that can hold every live block never overflows
    assert derive_evict_buffer_slots(64, 4, 8, 4) == 64
    assert e4.rec.evict_buffer_slots == min(
        e4.rec.blocks, 2 * 4 * 4 * b + 4 * b
    )
    # the OramConfig itself refuses inconsistent delayed geometry
    from grapevine_tpu.oram.path_oram import OramConfig

    with pytest.raises(ValueError, match="evict_window"):
        OramConfig(height=3, value_words=4, evict_window=0)
    with pytest.raises(ValueError, match="evict_window > 1"):
        OramConfig(height=3, value_words=4, evict_window=2)
    # flush target arithmetic: the dedup cap IS the amortization
    from grapevine_tpu.oram.round import flush_target_slots

    c = OramConfig(height=3, value_words=4, evict_window=8,
                   evict_fetch_count=16, evict_buffer_slots=64)
    assert flush_target_slots(c) == c.n_buckets_padded  # saturated
    c2 = OramConfig(height=9, value_words=4, evict_window=2,
                    evict_fetch_count=4, evict_buffer_slots=64)
    assert flush_target_slots(c2) == 2 * 4 * c2.path_len  # unsaturated


def test_evict_checkpoint_fingerprint_rejects_cross_e(tmp_path):
    """A buffer-bearing checkpoint must fail loudly against a
    differently-cadenced engine — the plane shapes differ AND the
    fingerprint covers the per-tree windows. Pure serialization."""
    from grapevine_tpu.engine.checkpoint import (
        CheckpointError,
        bytes_to_state,
        engine_fingerprint,
        state_to_bytes,
    )
    from grapevine_tpu.engine.state import EngineConfig, init_engine

    kw = dict(BASE, max_messages=32, batch_size=4)
    ec1 = EngineConfig.from_config(GrapevineConfig(evict_every=1, **kw))
    ec4 = EngineConfig.from_config(GrapevineConfig(evict_every=4, **kw))
    assert engine_fingerprint(ec1) != engine_fingerprint(ec4)
    blob4 = state_to_bytes(ec4, init_engine(ec4, seed=1))
    assert bytes_to_state(ec4, blob4) is not None  # control: self-loads
    with pytest.raises(CheckpointError, match="fingerprint"):
        bytes_to_state(ec1, blob4)


def test_evict_access_schedule_audit():
    """CI gate (trace-only, flat map): the fetch round is index-blind
    and HBM-read-only; one flush scatters exactly the deduplicated
    window — ISSUE-15's acceptance audit, wired into tier-1 next to
    the tree-cache/posmap/telemetry gates."""
    from check_tree_cache_oblivious import check_evict_round_accounting

    out = check_evict_round_accounting(b=8, height=7, k=2, window=2)
    assert out["fetch"]["tree_val"] == [8 * 6]  # B·(plen−k), gathers
    assert out["flush"]["tree_val"] == [2 * 8 * 8]  # t rows, scatters


def test_sharded_evict_access_schedule_audit():
    """ISSUE-18 trace gate (compile-free, always-on): per shard, the
    sharded fetch round is index-blind and HBM-read-only at the uniform
    B·(path_len−k) working-set shape, and the sharded flush's scatter
    ops carry all t rows on every chip (owner-masked lanes drop via
    out-of-range targets — the static shape never shrinks). The runtime
    owner-partition claim and its seeded mutant ride -m slow."""
    from check_tree_cache_oblivious import check_sharded_evict_accounting

    out = check_sharded_evict_accounting(runtime=False)
    assert out["shards"] == 2
    assert out["fetch"]["tree_val"] == [6 * 6]  # B·(plen−k) per shard
    assert out["flush"]["tree_val"] == [2 * 6 * 8]  # all t rows per shard


@pytest.mark.slow
def test_sharded_evict_owner_partition_and_mutant():
    """Runtime halves of the ISSUE-18 audit, both directions: (a) every
    bucket the single-chip flush writes is written by exactly its
    heap-range owner shard and per-shard counts sum to the single-chip
    count; (b) the seeded unmasked-scatter mutant (shard mask dropped,
    wrapped local targets) must FAIL the partition check."""
    from check_tree_cache_oblivious import check_sharded_evict_accounting

    out = check_sharded_evict_accounting()
    assert sum(out["per_shard_written"]) == out["oracle_written"]
    with pytest.raises(AssertionError, match="owner partition|diverges"):
        check_sharded_evict_accounting(_unmasked_scatter=True)


def test_evict_buffer_overflow_canary():
    """Directed near-overflow: an explicitly undersized buffer + stash
    must trip the shared sticky overflow counter and surface through
    health() — silent block loss is the one failure mode the canary
    exists to catch. (Responses after overflow are undefined; this
    test only asserts the alarm fires.)"""
    from test_vphases_scan import req

    cfg = GrapevineConfig(
        **dict(BASE, stash_size=8), evict_every=8, evict_buffer_slots=2,
    )
    eng = GrapevineEngine(cfg, seed=3)
    assert eng.ecfg.rec.evict_buffer_slots == 2
    idents = [key(i) for i in range(1, 6)]
    h = eng.health()
    for bi in range(6):  # pure creates: live blocks pile into a
        reqs = [         # 2-row buffer + 8-row stash, no flush due
            req(C.REQUEST_TYPE_CREATE, idents[j % 5],
                recipient=idents[(j + 1) % 5], tag=bi * 8 + j)
            for j in range(8)
        ]
        eng.handle_queries(reqs, NOW + bi)
        h = eng.health()
        if h["stash_overflow"] > 0:
            break
    assert h["stash_overflow"] > 0, (
        "2-slot buffer + 8-slot stash under create-heavy traffic never "
        "overflowed — the canary cannot fire"
    )
    occ = h["evict_buffer_occupancy"]
    assert occ["rec"] <= 2 and occ["mb"] <= 2
    # the gauge sums the trees (batch-level): capped by rec C + mb C
    assert 0 < eng.metrics.registry.snapshot()[
        "grapevine_evict_buffer_high_water"
    ] <= 4


def test_evict_recovery_mid_window(tmp_path):
    """Durability at E=4: close mid-window, reopen (journal replay
    re-executes rounds AND KIND_FLUSH records through the jitted
    programs), continue, and land bit-identical to an uninterrupted
    engine — buffer planes, window counter, and placement included."""
    import hashlib

    from grapevine_tpu.config import DurabilityConfig
    from grapevine_tpu.engine.checkpoint import state_to_bytes

    kw = dict(BASE, max_messages=32, batch_size=4)
    idents = [key(i) for i in range(1, 4)]

    def batches(n):
        r = np.random.default_rng(31)
        live: list = []
        return [_gen_batch(r, idents, live, 4) for _ in range(n)]

    evs = batches(6)  # 6 rounds at E=4: one flush + a 2-round tail
    d = str(tmp_path / "state")
    dc = DurabilityConfig(state_dir=d, checkpoint_every_rounds=3)
    eng = GrapevineEngine(
        GrapevineConfig(evict_every=4, **kw), seed=2, durability=dc
    )
    for i, reqs in enumerate(evs[:4]):
        eng.handle_queries(reqs, NOW + i)
    eng.close()  # dies mid-window (2 rounds buffered)

    eng2 = GrapevineEngine(
        GrapevineConfig(evict_every=4, **kw), seed=2,
        durability=DurabilityConfig(state_dir=d, checkpoint_every_rounds=3),
    )
    assert eng2._rounds_since_flush == int(eng2.state.rec.ebuf_rounds)
    for i, reqs in enumerate(evs[4:]):
        eng2.handle_queries(reqs, NOW + 4 + i)
    h_rec = hashlib.sha256(
        state_to_bytes(eng2.ecfg, eng2.state)
    ).hexdigest()
    eng2.close()

    ref = GrapevineEngine(GrapevineConfig(evict_every=4, **kw), seed=2)
    for i, reqs in enumerate(evs):
        ref.handle_queries(reqs, NOW + i)
    h_ref = hashlib.sha256(
        state_to_bytes(ref.ecfg, ref.state)
    ).hexdigest()
    assert h_rec == h_ref, (
        "recovered + continued state diverges from the uninterrupted "
        "run — journal replay did not reproduce the flush cadence"
    )


def test_evict_replay_refuses_cross_e_journal(tmp_path):
    """Journal-only recovery (no checkpoint) must refuse a journal
    written under a different cadence: a KIND_FLUSH frame replayed on
    an evict_every=1 engine raises JournalError instead of crashing
    (or silently corrupting the window ledger)."""
    from grapevine_tpu.config import DurabilityConfig
    from grapevine_tpu.engine.journal import JournalError

    kw = dict(BASE, max_messages=32, batch_size=4)
    d = str(tmp_path / "xe")
    eng = GrapevineEngine(
        GrapevineConfig(evict_every=2, **kw), seed=2,
        durability=DurabilityConfig(state_dir=d,
                                    checkpoint_every_rounds=1 << 20),
    )
    rng = np.random.default_rng(41)
    idents = [key(1), key(2)]
    for bi in range(2):  # 2 rounds at E=2 -> one flush frame journaled
        eng.handle_queries(_gen_batch(rng, idents, [], 4), NOW + bi)
    eng.close()
    with pytest.raises(JournalError, match="evict_every"):
        GrapevineEngine(
            GrapevineConfig(evict_every=1, **kw), seed=2,
            durability=DurabilityConfig(state_dir=d,
                                        checkpoint_every_rounds=1 << 20),
        )


# -- slow: breadth, cipher, recursive posmap, chaos, scenario soaks -----


@pytest.mark.slow
def test_evict_replay_refuses_missing_flush_frames(tmp_path):
    """The converse cadence guard: an evict_every=1 journal (no flush
    frames) replayed by an E>1 engine raises once more rounds than one
    window replay without a flush — instead of silently clamping the
    window ledger and overflowing the buffer."""
    from grapevine_tpu.config import DurabilityConfig
    from grapevine_tpu.engine.journal import JournalError

    kw = dict(BASE, max_messages=32, batch_size=4)
    d = str(tmp_path / "xe1")
    eng = GrapevineEngine(
        GrapevineConfig(evict_every=1, **kw), seed=2,
        durability=DurabilityConfig(state_dir=d,
                                    checkpoint_every_rounds=1 << 20),
    )
    rng = np.random.default_rng(43)
    idents = [key(1), key(2)]
    for bi in range(4):  # > one E=2 window of rounds, zero flush frames
        eng.handle_queries(_gen_batch(rng, idents, [], 4), NOW + bi)
    eng.close()
    with pytest.raises(JournalError, match="different evict_every"):
        GrapevineEngine(
            GrapevineConfig(evict_every=2, **kw), seed=2,
            durability=DurabilityConfig(state_dir=d,
                                        checkpoint_every_rounds=1 << 20),
        )


@pytest.mark.slow
def test_evict_campaign_cipher_on():
    """The at-rest cipher pair at E=2: fetch rounds decrypt-only, the
    flush re-keys the deduplicated window — logical content identity
    must hold end to end, sweep re-key included."""
    cfg = dict(BASE, bucket_cipher_rounds=8)
    _run_evict_campaign(cfg, seed=7300, n_batches=5, sweep=True, e=2)


@pytest.mark.slow
def test_evict_campaign_recursive_posmap():
    """ROADMAP item 1 ∘ item 5: delayed eviction applied to the payload
    trees AND the recursive posmap's internal trees (their buffers
    flush inside the same oram_flush pass) stays content-identical,
    leaf-metadata planes included."""
    cfg = dict(BASE, posmap_impl="recursive", bucket_cipher_rounds=8)
    _run_evict_campaign(cfg, seed=7400, n_batches=4, sweep=True, e=4)


@pytest.mark.slow
def test_evict_campaign_scan_radix_e8():
    """The delayed round composes with the scan/radix machinery, at the
    widest shipped window (E=8 — two full windows crossed)."""
    cfg = dict(BASE, vphases_impl="scan", sort_impl="radix")
    _run_evict_campaign(cfg, seed=7500, n_batches=17, e=8)


@pytest.mark.slow
def test_evict_campaign_with_tree_cache_interaction():
    """Tree-top cache × delayed eviction: cached top buckets go stale
    within a window (their rows migrate to the buffer) and get
    rewritten at flush via the heap-prefix peel — content identity
    and zero overflow across both knobs."""
    cfg = dict(BASE, tree_top_cache_levels=2, bucket_cipher_rounds=8)
    _run_evict_campaign(cfg, seed=7600, n_batches=6, sweep=True, e=4)


@pytest.mark.slow
def test_chaos_kill_at_flush():
    """SIGKILL trials aimed at the flush crash windows, at pipeline
    depth 2 (the ISSUE-15 acceptance): recovery replays journal order
    — KIND_FLUSH included — and every response hash + the final state
    stay bit-identical to the uninterrupted E=4 oracle, leakmon
    PASS."""
    import chaos_run

    args = chaos_run.parse_args(
        ["--events", "14", "--evict-every", "4", "--pipeline-depth", "2",
         "--seed", "47", "--checkpoint-every", "5"]
    )
    modes = ["flush.pre_dispatch", "flush.post_dispatch", "timer"]
    failures = chaos_run.run_trials(0, args, modes=modes)
    assert not failures, "\n".join(failures)


@pytest.mark.slow
def test_evict_leakmon_pop_heavy_and_probe():
    """The ISSUE-15 leakmon soak: the PR-9 pop-heavy mailbox-drain
    scenario runs PASS at E=4 (the op-independent flush cadence adds
    no timing channel even under drain-shaped traffic), and the
    probe-campaign injector still flips SUSPECT — detection power is
    not degraded by the extra flush dispatches."""
    from grapevine_tpu.load import (
        ProbeCampaignInjector,
        ScenarioRunner,
        adversarial_probe,
        pop_heavy_drain,
    )
    from grapevine_tpu.obs.leakmon import (
        PASS,
        SUSPECT,
        EngineLeakMonitor,
        LeakMonitorConfig,
    )
    from grapevine_tpu.server.scheduler import BatchScheduler

    engine = GrapevineEngine(
        GrapevineConfig(
            evict_every=4,
            **dict(BASE, max_messages=256, max_recipients=32,
                   batch_size=8, mailbox_cap=8),
        ),
        seed=9,
    )

    def soak(schedule, sink):
        engine.attach_leakmon(sink)
        sched = BatchScheduler(engine, clock=lambda: NOW)
        try:
            runner = ScenarioRunner(sched, n_idents=16,
                                    settle_timeout_s=60.0)
            return runner.run(schedule)
        finally:
            sched.close()
            sink.flush(30)
            engine.attach_leakmon(None)

    def fresh_monitor():
        # registry-free monitors: two soaks on one engine must not
        # double-register the leakmon gauges (the load-harness pattern)
        return EngineLeakMonitor(
            mb_leaves=engine.ecfg.mb.leaves,
            rec_leaves=engine.ecfg.rec.leaves,
            mb_choices=engine.ecfg.mb_choices,
            cfg=LeakMonitorConfig(window_rounds=64),
        )

    mon = fresh_monitor()
    soak(pop_heavy_drain(120.0, 1.5, 37, n_idents=16), mon)
    v = mon.verdict()
    assert v["verdict"] == PASS, v
    assert engine.health()["stash_overflow"] == 0
    mon.close()

    mon2 = fresh_monitor()
    inj = ProbeCampaignInjector(mon2, engine.ecfg)
    soak(
        adversarial_probe(0.03, 1.5, 38, n_probe_keys=4,
                          probes_per_pulse=2),
        inj,
    )
    v2 = mon2.verdict()
    assert v2["verdict"] == SUSPECT, v2
    mon2.close()


@pytest.mark.slow
def test_evict_recursive_schedule_audit():
    """The trace audit over a recursive-posmap delayed geometry (inner
    buffers + inner flush accounting included) — the heavier trace
    rides -m slow."""
    from check_tree_cache_oblivious import check_evict_round_accounting

    check_evict_round_accounting(recursive=True)


@pytest.mark.slow
def test_sharded_evict_recursive_schedule_audit():
    """The sharded trace+runtime audit over the recursive-posmap
    geometry: the replicated inner trees flush axis-free inside every
    chip's pass while the outer planes owner-partition."""
    from check_tree_cache_oblivious import check_sharded_evict_accounting

    check_sharded_evict_accounting(recursive=True)


@pytest.mark.slow
def test_evict_content_map_partition_invariant():
    """logical_block_map's partition assertion has teeth across many
    windows: no block is ever duplicated between tree, buffer, and
    stash at any round boundary of a long mixed campaign."""
    cfg = GrapevineConfig(evict_every=4, **BASE)
    eng = GrapevineEngine(cfg, seed=13)
    rng = np.random.default_rng(99)
    idents = [key(i) for i in range(1, 6)]
    live: list[tuple[bytes, bytes]] = []
    for bi in range(10):
        reqs = _gen_batch(rng, idents, live, 8)
        r = eng.handle_queries(reqs, NOW + bi)
        for q, d in zip(reqs, r):
            if (q.request_type == C.REQUEST_TYPE_CREATE
                    and d.status_code == C.STATUS_CODE_SUCCESS):
                live.append((d.record.msg_id, q.record.recipient))
            elif (q.request_type == C.REQUEST_TYPE_DELETE
                    and d.status_code == C.STATUS_CODE_SUCCESS):
                live = [x for x in live if x[0] != d.record.msg_id]
        # raises internally on any duplicate placement
        m = logical_block_map(eng.ecfg.rec, eng.state.rec)
        assert len(m) == eng.message_count()
