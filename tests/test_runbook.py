"""The bus as OPERATIONS.md's runbook serves it: its TTL
(``--expiry-period``), crash safety (``--state-dir``) and the transcript
auditor (``--leakmon``) on together. The three meet in
``engine/batcher.py`` ``expire`` (the sweep's journal frame before the
sweep, under the engine's lock), in the recovery that replays a sweep
among rounds from no checkpoint, and in the monitor's own thread beside
both. Here at a toy size on the CPU against ``testing/reference.py``,
op for op; at the size it is for, on the chip, in the benchmark's cell
``backlog-runbook-1chip-2p21``.
"""

import dataclasses
import random

import jax
import pytest

from grapevine_tpu.config import GrapevineConfig
from grapevine_tpu.engine.batcher import GrapevineEngine
from grapevine_tpu.obs.leakmon import (
    PASS, EngineLeakMonitor, LeakMonitorConfig,
)
from grapevine_tpu.testing.reference import ReferenceEngine
from grapevine_tpu.wire import constants as C

from test_round import SMALL, assert_responses_equal, key, req

NOW = 1_700_000_000
TTL = 50


class _Session:
    """Seeded CRUD rounds through an engine and the reference side by
    side, every answer compared."""

    def __init__(self, engine, oracle, seed):
        self.engine, self.oracle = engine, oracle
        self.rng = random.Random(seed)
        self.idents = [key(i + 1) for i in range(6)]
        self.live: list = []  # (msg_id, sender, recipient) of live records
        self.t = NOW
        self.rounds = 0

    def _request(self):
        rng, live, idents = self.rng, self.live, self.idents
        c = rng.random()
        if c < 0.4 or not live:
            return req(C.REQUEST_TYPE_CREATE, rng.choice(idents),
                       recipient=rng.choice(idents), tag=rng.randrange(256))
        mid, snd, rcp = rng.choice(live)
        if c < 0.6:
            return req(C.REQUEST_TYPE_READ, rng.choice([snd, rcp]), msg_id=mid)
        if c < 0.7:
            return req(C.REQUEST_TYPE_READ, rng.choice(idents))
        if c < 0.8:
            return req(C.REQUEST_TYPE_UPDATE, rng.choice([snd, rcp]),
                       msg_id=mid, recipient=rcp, tag=rng.randrange(256))
        if c < 0.9:
            return req(C.REQUEST_TYPE_DELETE, rng.choice([snd, rcp]),
                       msg_id=mid, recipient=rcp)
        return req(C.REQUEST_TYPE_DELETE, rng.choice(idents))

    def round(self, seconds_later: int):
        self.t += seconds_later
        bs = self.engine.ecfg.batch_size
        reqs = [self._request() for _ in range(self.rng.randrange(1, bs + 1))]
        dev = self.engine.handle_queries(reqs, self.t)
        forced = [d.record.msg_id
                  if r.request_type == C.REQUEST_TYPE_CREATE
                  and d.status_code == C.STATUS_CODE_SUCCESS else None
                  for r, d in zip(reqs, dev)]
        ora = self.oracle.handle_batch(reqs, self.t, forced)
        for j, (r, d, o) in enumerate(zip(reqs, dev, ora)):
            assert_responses_equal(
                d, o, f"round {self.rounds} slot {j} rt {r.request_type}")
            if o.status_code != C.STATUS_CODE_SUCCESS:
                continue
            if r.request_type == C.REQUEST_TYPE_CREATE:
                self.live.append(
                    (o.record.msg_id, o.record.sender, o.record.recipient))
            elif r.request_type == C.REQUEST_TYPE_DELETE:
                self.live = [e for e in self.live
                             if e[0] != o.record.msg_id]
        self.rounds += 1
        self.same_counts()

    def sweep(self) -> int:
        evicted = self.engine.expire(self.t)
        assert evicted == self.oracle.expire(self.t)
        gone = set(self.live) - {
            e for e in self.live if e[0] in self.oracle.records}
        self.live = [e for e in self.live if e not in gone]
        self.same_counts()
        return evicted

    def same_counts(self):
        assert self.engine.message_count() == self.oracle.message_count()
        assert self.engine.recipient_count() == self.oracle.recipient_count()


def test_ttl_journal_and_monitor_together_match_the_reference_through_a_sweep_a_crash_and_a_recovery(
        tmp_path):
    """Rounds, a due sweep among them, the crash, the restart from no
    checkpoint (every frame replayed, the sweep's in its place), rounds
    after: every answer the reference's, the counts too; the auditor saw
    every round and reads PASS."""
    cfg = dataclasses.replace(SMALL, expiry_period=TTL)
    engine = GrapevineEngine(cfg, seed=7, durability={
        "state_dir": str(tmp_path / "state"),
        "checkpoint_every_rounds": 10_000})
    monitor = EngineLeakMonitor.for_engine(engine, LeakMonitorConfig.coerce({}))
    engine.attach_leakmon(monitor)
    dm, reg = engine.durability, engine.metrics.registry
    s = _Session(engine, ReferenceEngine(config=cfg, rng=random.Random(8)), 9)
    for _ in range(8):
        s.round(10)  # 80 s: the first rounds' records are older than TTL
    before = s.engine.message_count()
    assert s.sweep() > 0 and 0 < s.engine.message_count() < before
    for _ in range(4):
        s.round(10)
    assert monitor.flush(30.0)
    audit = monitor.verdict()
    assert audit["verdict"] == PASS and audit["rounds_dropped"] == 0
    assert audit["rounds_observed"] == s.rounds == 12
    assert reg.get("grapevine_leakmon_rounds_total").get() == 12
    assert reg.get("grapevine_leakmon_seconds_total").get() >= 0.0
    # rounds and the sweep, each journaled before it ran, none lost
    assert dm.seq == dm.status()["last_durable_seq"] == 13
    assert not dm.ckpt_seq and not dm.recovered_from_checkpoint
    engine.abandon()
    assert engine.state is None
    engine.recover()
    assert dm.replayed == 13 and not dm.recovered_from_checkpoint
    replayed = reg.get("grapevine_recover_replayed_total")
    assert (replayed.get(kind="round"), replayed.get(kind="sweep")) == (12, 1)
    assert reg.get("grapevine_recover_replay_seconds").get() > 0
    s.same_counts()
    # by-id and next-message ops on records from both sides of the
    # sweep's cut and from before the crash, and a second due sweep
    for _ in range(6):
        s.round(10)
    assert s.sweep() > 0
    s.round(1)
    assert engine.health()["stash_overflow"] == 0
    monitor.close()
    engine.close()


@pytest.mark.parametrize("value, want", [
    (None, None),
    ({}, LeakMonitorConfig()),
    ({"window_rounds": 64, "queue_depth": 8},
     LeakMonitorConfig(window_rounds=64, queue_depth=8)),
    (LeakMonitorConfig(repeat_threshold=0.1),
     LeakMonitorConfig(repeat_threshold=0.1)),
])
def test_a_leak_monitor_config_is_coerced_from_what_a_file_holds(value, want):
    got = LeakMonitorConfig.coerce(value)
    assert got == want
    if isinstance(value, LeakMonitorConfig):
        assert got is value


def test_a_leak_monitor_config_refuses_a_key_that_is_no_field():
    with pytest.raises(TypeError, match="window"):
        LeakMonitorConfig.coerce({"window": 64})


def test_a_server_takes_its_leak_monitor_as_a_mapping():
    """What a JSON configuration file holds under ``server.leakmon``,
    the empty mapping included (``--leakmon`` at every default, not "no
    monitor")."""
    from grapevine_tpu.server.service import GrapevineServer

    for fields, window in (({}, 256), ({"window_rounds": 32}, 32)):
        server = GrapevineServer(SMALL, leakmon=fields)
        try:
            assert server.leakmon.cfg == LeakMonitorConfig(
                window_rounds=window)
            assert server.engine.leakmon is server.leakmon
        finally:
            server.stop()


@pytest.fixture(scope="module")
def compiles() -> list:
    """Every backend compile of the process from here on, by event
    name (jax keeps a listener for the life of the process: one)."""
    import jax.monitoring

    seen: list = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *_a, **_k: seen.append(name)
        if name.endswith("backend_compile_duration") else None)
    return seen


@pytest.mark.parametrize("shards", [1, 4])
def test_an_engine_built_with_a_ttl_compiles_nothing_in_its_first_expire_or_a_replayed_sweep(
        shards, tmp_path, compiles):
    """The sweep program is compiled (or loaded) when the engine is
    built: on a served bus the first ``expire`` comes
    ``expiry_period / 10`` after start, under the engine's lock and
    inside every waiting Query's deadline, and a recovery meets the same
    program at its first sweep frame. An engine without a TTL, asked to
    sweep all the same, compiles then: the listener sees such a
    compile."""
    # a geometry no other test's engine shares (the jit's cache is the
    # process's): stashes of 88
    cfg = GrapevineConfig(max_messages=128, max_recipients=32, mailbox_cap=4,
                          batch_size=4, stash_size=88, shards=shards,
                          expiry_period=77)
    engine = GrapevineEngine(cfg, seed=3, durability={
        "state_dir": str(tmp_path / "state")})
    built = len(compiles)
    assert built > 0
    assert engine.expire(NOW) == 0
    assert len(compiles) == built, "the first expire compiled"
    # the restart builds its empty state first (on a mesh through a
    # fresh jit of the initialiser, which compiles): what is held here
    # is the frame's replay itself
    inside, replay = [], engine._replay_record

    def counted(state, rec):
        before = len(compiles)
        state = jax.block_until_ready(replay(state, rec))
        inside.append(len(compiles) - before)
        return state

    engine._replay_record = counted
    engine.abandon()
    engine.recover()
    assert engine.durability.replayed == 1
    assert inside == [0], "the replayed sweep frame compiled"
    engine.close()
    cold = GrapevineEngine(dataclasses.replace(cfg, expiry_period=0,
                                               stash_size=72), seed=3)
    built = len(compiles)
    assert cold.expire(NOW, period=77) == 0
    assert len(compiles) > built


def test_a_sweep_has_spans_for_its_lock_and_its_journal_frame(tmp_path):
    """``expire``: ``sweep_lock`` (call to lock held), ``sweep_journal``
    (the frame sealed, written, fsynced; no sample with no state
    directory) and ``sweep`` (the device's pass), each a series of
    ``grapevine_phase_seconds``."""
    cfg = dataclasses.replace(SMALL, expiry_period=TTL)

    def counts(engine):
        phases = engine.metrics.registry.get("grapevine_phase_seconds")
        return [phases.labels(phase=p).state()[2]
                for p in ("sweep_lock", "sweep_journal", "sweep")]

    durable = GrapevineEngine(cfg, seed=1, durability={
        "state_dir": str(tmp_path / "state")})
    durable.expire(NOW)
    durable.expire(NOW + 1)
    assert counts(durable) == [2, 2, 2]
    assert durable.durability.seq == 2
    durable.close()
    volatile = GrapevineEngine(cfg, seed=1)
    volatile.expire(NOW)
    assert counts(volatile) == [1, 0, 1]
