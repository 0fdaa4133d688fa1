"""Journal-shipped hot standby (engine/replication.py, ISSUE 19).

Tier-1 half of the PR-19 acceptance: the follower read path's liveness
contract (torn tails poll, roll/prune races rescan, transient reads
retry), epoch fencing (O_EXCL single winner, stale-primary appends
refused), the replication fingerprint's normalization story, and the
full loopback ship → link-cut → fenced-promote → bit-identical-serve
cycle — plus the cross-knob rolling-upgrade drill and one chaos
--standby smoke trial. The kill-at-every-site sweep and the live CLI
flip drill live in tests/test_chaos_recovery.py (-m slow).
"""

import builtins
import dataclasses
import errno
import os
import sys
import time

import pytest

from grapevine_tpu.config import DurabilityConfig, GrapevineConfig
from grapevine_tpu.engine import journal as jr
from grapevine_tpu.engine.batcher import GrapevineEngine, pack_batch
from grapevine_tpu.engine.checkpoint import engine_fingerprint, state_to_bytes
from grapevine_tpu.engine.replication import (
    JournalShipper,
    ReplicationError,
    StandbyReplica,
    replication_fingerprint,
)
from grapevine_tpu.engine.state import EngineConfig
from grapevine_tpu.testing.compare import assert_logical_state_equal
from grapevine_tpu.wire import constants as C
from grapevine_tpu.wire.records import QueryRequest, RequestRecord

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ROOT = bytes(range(32))
NOW = 1_700_000_000


def _cfg(**kw):
    base = dict(
        max_messages=64, max_recipients=8, mailbox_cap=4,
        batch_size=4, stash_size=64, bucket_cipher_rounds=0,
        tree_top_cache_levels=0, pipeline_depth=1,
    )
    base.update(kw)
    return GrapevineConfig(**base)


SMALL = _cfg()


def _plant_key(d: str) -> None:
    """Both ends of a replication pair unseal under ONE root key — the
    production secret-mount story (OPERATIONS.md §23)."""
    os.makedirs(d, exist_ok=True)
    path = os.path.join(d, "root.key")
    fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_EXCL, 0o600)
    try:
        os.write(fd, ROOT)
    finally:
        os.close(fd)


def _dcfg(d: str, **kw) -> DurabilityConfig:
    kw.setdefault("checkpoint_every_rounds", 1 << 20)
    return DurabilityConfig(state_dir=d, **kw)


def _req(tag: int, rt=C.REQUEST_TYPE_CREATE):
    return QueryRequest(
        request_type=rt,
        auth_identity=bytes([tag & 0xFF]) * 32,
        auth_signature=b"\x01" * C.SIGNATURE_SIZE,
        record=RequestRecord(
            msg_id=C.ZERO_MSG_ID,
            recipient=bytes([(tag ^ 0x5A) & 0xFF]) * 32,
            payload=bytes([tag & 0xFF]) * C.PAYLOAD_SIZE,
        ),
    )


def _round_batch(ecfg, tag: int):
    return pack_batch([_req(tag)], ecfg.batch_size, NOW + tag), 1


def _fresh_journal(d, ecfg, **kw):
    j = jr.BatchJournal(str(d), ROOT, ecfg, **kw)
    list(j.replay(after_seq=0))
    j.open_for_append()
    return j


def _wait(pred, timeout=60.0, what=""):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.02)
    pytest.fail(f"timed out waiting for {what}")


@pytest.fixture(scope="module")
def ecfg():
    return EngineConfig.from_config(SMALL)


# -- follower liveness contract (journal.py follow/_follow_scan) --------


def test_follow_torn_final_frame_is_poll_again_not_error(tmp_path, ecfg):
    """A half-written FINAL frame means "not yet durable": the scan
    yields everything before it, stops silently, and a later call (the
    writer finished the append) picks the frame up."""
    j = _fresh_journal(tmp_path, ecfg)
    j.append_round(*_round_batch(ecfg, 1))
    j.append_round(*_round_batch(ecfg, 2))
    j.close()
    (_, path), = jr.BatchJournal(str(tmp_path), ROOT, ecfg)._segments()
    blob = open(path, "rb").read()
    frame_len = len(blob) // 2

    reader = jr.BatchJournal(str(tmp_path), ROOT, ecfg)
    for cut in (frame_len + 1, frame_len + jr._HEADER.size,
                len(blob) - 1):
        with open(path, "wb") as fh:
            fh.write(blob[:cut])
        assert [s for s, _ in reader.follow_frames(after_seq=0)] == [1]
    # the writer's append completes: the next poll yields the frame
    with open(path, "wb") as fh:
        fh.write(blob)
    assert [s for s, _ in reader.follow_frames(after_seq=1)] == [2]


def test_follow_rescans_when_roll_prune_races_the_reader(tmp_path, ecfg,
                                                         monkeypatch):
    """A segment vanishing between listdir and open (roll/prune racing
    the reader) triggers a directory rescan, not an error."""
    j = _fresh_journal(tmp_path, ecfg)
    j.append_round(*_round_batch(ecfg, 1))
    j.append_round(*_round_batch(ecfg, 2))
    j.close()

    real = jr.BatchJournal._read_segment
    calls = {"n": 0}

    def flaky(self, path):
        calls["n"] += 1
        if calls["n"] == 1:
            raise FileNotFoundError(path)
        return real(self, path)

    monkeypatch.setattr(jr.BatchJournal, "_read_segment", flaky)
    reader = jr.BatchJournal(str(tmp_path), ROOT, ecfg)
    assert [s for s, _ in reader.follow_frames(after_seq=0)] == [1, 2]
    assert calls["n"] == 2  # first open raced a roll; the rescan read


def test_follow_behind_prune_horizon_demands_rebootstrap(tmp_path, ecfg):
    """Segments covering consumed frames may vanish freely; a follower
    whose NEXT frame was pruned gets a hard error pointing at the
    checkpoint bootstrap path."""
    j = _fresh_journal(tmp_path, ecfg)
    j.append_round(*_round_batch(ecfg, 1))
    j.append_round(*_round_batch(ecfg, 2))
    j.roll()  # checkpoint covering seq 2 landed: frames 1-2 pruned
    j.append_round(*_round_batch(ecfg, 3))
    j.close()

    reader = jr.BatchJournal(str(tmp_path), ROOT, ecfg)
    # already past the pruned prefix: fine
    assert [s for s, _ in reader.follow_frames(after_seq=2)] == [3]
    # behind it: frames 1-2 are gone for good
    with pytest.raises(jr.JournalError, match="prune horizon"):
        list(reader.follow_frames(after_seq=0))


def test_follow_retries_transient_reads_with_bounded_backoff(tmp_path, ecfg,
                                                             monkeypatch):
    """EIO from a flaky mount retries (bounded, backed off) before
    raising; exhaustion is a JournalError, not a raw OSError."""
    j = _fresh_journal(tmp_path, ecfg)
    j.append_round(*_round_batch(ecfg, 1))
    j.append_round(*_round_batch(ecfg, 2))
    j.close()

    real_open = builtins.open
    fails = {"n": 2}

    def flaky(path, *a, **kw):
        if str(path).endswith(".wal") and fails["n"] > 0:
            fails["n"] -= 1
            raise OSError(errno.EIO, "flaky mount")
        return real_open(path, *a, **kw)

    monkeypatch.setattr(builtins, "open", flaky)
    monkeypatch.setattr(jr.time, "sleep", lambda s: None)
    reader = jr.BatchJournal(str(tmp_path), ROOT, ecfg)
    assert [s for s, _ in reader.follow_frames(after_seq=0)] == [1, 2]

    fails["n"] = 10_000  # never recovers: bounded retries then raise
    with pytest.raises(jr.JournalError, match="transient read errors"):
        list(reader.follow_frames(after_seq=0))


# -- epoch fencing (journal.py write_fence/_check_fence) ----------------


def test_fence_is_o_excl_exactly_one_winner(tmp_path):
    d = str(tmp_path)
    payload = jr.write_fence(d, epoch=3, fingerprint="fp-a")
    assert payload["epoch"] == 3
    assert jr.read_fence(d)["epoch"] == 3
    with pytest.raises(jr.JournalError, match="already fenced"):
        jr.write_fence(d, epoch=4, fingerprint="fp-b")
    # the loser's attempt did not clobber the winner's marker
    assert jr.read_fence(d)["fingerprint"] == "fp-a"


def test_epoch_file_roundtrip_and_default(tmp_path):
    d = str(tmp_path)
    assert jr.read_epoch(d) == 0
    jr.write_epoch(d, 7)
    assert jr.read_epoch(d) == 7
    jr.write_epoch(d, 8)  # re-promote into the same dir bumps again
    assert jr.read_epoch(d) == 8


def test_fenced_journal_refuses_stale_appends_and_reopen(tmp_path, ecfg):
    """The split-brain guard, both halves: a live stale primary's next
    append raises the moment a newer-epoch fence lands, and a REVIVED
    stale primary refuses in open_for_append — before it would truncate
    the tail the promoted replica already drained."""
    j = _fresh_journal(tmp_path, ecfg)
    j.append_round(*_round_batch(ecfg, 1))
    jr.write_fence(str(tmp_path), epoch=j.epoch + 1, fingerprint="fp")
    with pytest.raises(jr.JournalError, match="fenced"):
        j.append_round(*_round_batch(ecfg, 2))
    j.close()

    j2 = jr.BatchJournal(str(tmp_path), ROOT, ecfg)
    assert [r.seq for r in j2.replay(after_seq=0)] == [1]  # reads stay legal
    with pytest.raises(jr.JournalError, match="fenced"):
        j2.open_for_append()

    # the promoted owner itself (epoch == fence epoch) appends freely
    jr.write_epoch(str(tmp_path), jr.read_fence(str(tmp_path))["epoch"])
    j3 = _fresh_journal(tmp_path, ecfg)
    assert j3.append_round(*_round_batch(ecfg, 2)) == 2
    j3.close()


# -- replication fingerprint --------------------------------------------


def test_replication_fingerprint_normalizes_placement_knobs_only():
    """Frames replay across tree-top-cache depths and host-side round
    scheduling (the rolling-upgrade surface), but never across frame
    geometry."""
    base = SMALL
    # k is placement-only: normalized out
    assert replication_fingerprint(base) == replication_fingerprint(
        dataclasses.replace(base, tree_top_cache_levels=4))
    # pipeline depth is host-side scheduling: outside the frame format
    assert replication_fingerprint(base) == replication_fingerprint(
        dataclasses.replace(base, pipeline_depth=2))
    # geometry changes the frame sizes: fences
    assert replication_fingerprint(base) != replication_fingerprint(
        dataclasses.replace(base, max_messages=128))
    # ...while the FULL fingerprint (checkpoint compatibility) still
    # distinguishes the k=4 placement the repl fingerprint normalizes
    assert engine_fingerprint(
        EngineConfig.from_config(base)
    ) != engine_fingerprint(
        EngineConfig.from_config(
            dataclasses.replace(base, tree_top_cache_levels=4))
    )


def test_shipper_requires_a_journal_to_tail():
    eng = GrapevineEngine(SMALL, seed=0)
    try:
        with pytest.raises(ReplicationError, match="state-dir"):
            JournalShipper(eng, "127.0.0.1:1")
    finally:
        eng.close()


# -- the loopback cycle: ship → cut → promote → fence → serve -----------


def test_ship_promote_fence_cycle_bit_identical(tmp_path):
    """One continuous drill over a real socket: live catch-up at round
    cadence (leakmon's ship_cadence book PASS), link cut, primary dies
    with a durable tail the standby never saw, fenced promote drains it
    off disk (RPO 0, bit-identical state), the promoted replica serves,
    and every split-brain door is shut: shipped frames refused, the
    revived stale primary refused, the second promoter refused."""
    from grapevine_tpu.obs.leakmon import EngineLeakMonitor, LeakMonitorConfig

    primary_dir = str(tmp_path / "primary")
    standby_dir = str(tmp_path / "standby")
    _plant_key(primary_dir)
    _plant_key(standby_dir)

    primary = GrapevineEngine(SMALL, seed=0,
                              durability=_dcfg(primary_dir))
    monitor = EngineLeakMonitor.for_engine(
        primary, LeakMonitorConfig(window_rounds=64))
    primary.attach_leakmon(monitor)
    replica = StandbyReplica(SMALL, seed=0,
                             durability=_dcfg(standby_dir))
    port = replica.listen()
    shipper = JournalShipper(primary, ("127.0.0.1", port))
    monitor.attach_shipper(shipper)
    shipper.start()
    primary_open = True
    try:
        for i in range(4):
            primary.handle_queries([_req(i + 1)], NOW + i)
        primary.expire(NOW + 10, period=3600)
        _wait(lambda: replica.dm.applied_seq == primary.durability.seq,
              what="live catch-up")
        assert replica.connected and not replica.promoted
        healthy, detail = replica.healthz()
        assert healthy and detail["role"] == "standby"

        # the cadence book: every on-wire frame was one of the
        # geometry's constant sizes — content-independent by size
        v = monitor.verdict()
        ship = [d for d in v["detectors"] if d["name"] == "ship_cadence"]
        assert ship and ship[0]["verdict"] == "PASS"
        assert v["replication"]["cadence_ok"]
        # 4 rounds + 1 sweep
        assert v["replication"]["frames_shipped"] == 5

        # link cut; the primary's final rounds reach disk only
        shipper.close()
        for i in range(3):
            primary.handle_queries([_req(40 + i)], NOW + 20 + i)
        dead_seq = primary.durability.seq
        dead_bytes = state_to_bytes(primary.ecfg, primary.state)
        primary.close()
        primary_open = False

        res = replica.promote(primary_state_dir=primary_dir)
        assert res["epoch"] == 1
        assert res["rpo_durable_frames"] == 0
        assert res["applied_seq"] == dead_seq
        assert res["drained_frames"] == dead_seq - 5
        assert state_to_bytes(replica.engine.ecfg,
                              replica.engine.state) == dead_bytes
        healthy, detail = replica.healthz()
        assert healthy and detail["promoted"]
        assert jr.read_epoch(standby_dir) == 1

        # serves inside the same process: its own journal advances
        replica.engine.handle_queries([_req(99)], NOW + 40)
        assert replica.dm.seq > dead_seq

        # door 1: shipped frames bounce off a promoted replica
        with pytest.raises(ReplicationError, match="promoted"):
            replica.apply_frame(replica.dm.seq + 1, b"\x00" * 64)

        # door 2: the revived stale primary dies in open_for_append,
        # before truncating the tail the replica drained
        with pytest.raises(jr.JournalError, match="fenced"):
            GrapevineEngine(SMALL, seed=0, durability=_dcfg(primary_dir))

        # door 3: a double-promote has exactly one winner
        loser_dir = str(tmp_path / "loser")
        _plant_key(loser_dir)
        loser = StandbyReplica(SMALL, seed=0,
                               durability=_dcfg(loser_dir))
        try:
            with pytest.raises(jr.JournalError, match="already fenced"):
                loser.promote(primary_state_dir=primary_dir)
            assert not loser.promoted
        finally:
            loser.close()
    finally:
        shipper.close()
        if primary_open:
            primary.close()
        monitor.close()
        replica.close()


# -- rolling-upgrade drill: cross-knob legal, cross-geometry fenced -----


def test_cross_knob_standby_promotes_under_k4_depth2_primary(tmp_path):
    """The rolling-upgrade shape: a k=0/depth-1 standby follows a
    k=4/depth-2 primary from genesis (same frame fingerprint — k and
    pipeline depth are placement/scheduling, not frame format) and
    promotes to the logically identical store."""
    pcfg = _cfg(tree_top_cache_levels=4, pipeline_depth=2)
    scfg = SMALL
    assert replication_fingerprint(pcfg) == replication_fingerprint(scfg)

    primary_dir = str(tmp_path / "primary")
    standby_dir = str(tmp_path / "standby")
    _plant_key(primary_dir)
    _plant_key(standby_dir)
    primary = GrapevineEngine(pcfg, seed=0, durability=_dcfg(primary_dir))
    replica = StandbyReplica(scfg, seed=0, durability=_dcfg(standby_dir))
    port = replica.listen()
    shipper = JournalShipper(primary, ("127.0.0.1", port))
    shipper.start()
    primary_open = True
    try:
        for i in range(4):
            primary.handle_queries([_req(i + 1)], NOW + i)
        _wait(lambda: replica.dm.applied_seq == primary.durability.seq,
              what="cross-knob catch-up")
        shipper.close()
        primary.handle_queries([_req(9)], NOW + 9)
        dead_seq = primary.durability.seq
        dead_state = primary.state
        primary.close()
        primary_open = False

        res = replica.promote(primary_state_dir=primary_dir)
        assert res["applied_seq"] == dead_seq
        # different placement → different bits; logically equal store
        assert_logical_state_equal(primary.ecfg, dead_state,
                                   replica.engine.ecfg,
                                   replica.engine.state,
                                   ctx="cross-knob promote")
    finally:
        shipper.close()
        if primary_open:
            primary.close()
        replica.close()


def test_cross_geometry_ship_refused_with_fingerprint_error(tmp_path):
    """Capacity changes the frames' geometry: the handshake refuses,
    permanently (reconnects can never fix it)."""
    primary_dir = str(tmp_path / "primary")
    standby_dir = str(tmp_path / "standby")
    _plant_key(primary_dir)
    _plant_key(standby_dir)
    primary = GrapevineEngine(SMALL, seed=0, durability=_dcfg(primary_dir))
    replica = StandbyReplica(_cfg(max_messages=128), seed=0,
                             durability=_dcfg(standby_dir))
    port = replica.listen()
    shipper = JournalShipper(primary, ("127.0.0.1", port))
    shipper.start()
    try:
        _wait(lambda: shipper.fatal is not None,
              what="fingerprint refusal")
        assert "fingerprint" in shipper.fatal
        assert replica.dm.seq == 0 and not replica.promoted
    finally:
        shipper.close()
        primary.close()
        replica.close()


# -- chaos --standby smoke (full sweep is -m slow) ----------------------


def test_chaos_standby_smoke_round_boundary_kill():
    """One --standby trial at the nastiest site (round.pre_dispatch:
    round frame durable, round never dispatched): SIGKILL the
    primary, promote the parent's replica, finish the event schedule,
    and match the serial oracle bit-identically with leakmon PASS."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import chaos_run as chaos

    args = chaos.parse_args(
        ["--standby", "--events", "10", "--seed", "11"]
    )
    failures = chaos.run_trials(0, args, modes=["round.pre_dispatch"])
    assert not failures, "\n".join(failures)
