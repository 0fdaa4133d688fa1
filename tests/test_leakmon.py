"""The streaming leak monitor has the canaries' teeth (ISSUE 2).

tests/test_leak_canary.py proves the *pytest* detectors catch
deliberately-leaky round variants; these tests prove the *continuous*
monitor (obs/leakmon.py) catches the same leaks when fed round-by-round
like production — every leak built through the public ``oram_round``
parameters, so the monitor is auditing the real round code path:

- the no-remap canary (remap target = current leaf) flips the verdict
  to SUSPECT within 64 rounds at batch 256 (the ISSUE acceptance
  criterion), via the cross-round repeat detector;
- the no-dedup canary (dummy fetches reuse the real leaf) trips the
  same-key collision detector;
- the biased-dummy canary (constant leaf 0) trips the uniformity
  detector;
- 512 honest rounds at batch 256 report PASS on all three detectors
  (the false-positive side of the acceptance criterion);
- the streaming collision counter agrees with the quadratic pytest
  detector; the flight recorder enforces its batch-level schema so a
  dump can never carry logical keys, recipient ids, or per-op
  timestamps;
- the auditor's round is array work (ISSUE 51): the per-op key grouping
  and the per-key LRU it replaced are kept here as oracles, and the
  array forms are held to them element for element and call for call,
  the tracker's table overflowing inside calls.
"""

import json
import zlib
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from grapevine_tpu.engine.round_step import transcript_key_groups
from grapevine_tpu.obs.flightrec import FlightRecorder
from grapevine_tpu.obs.leakmon import (
    PASS,
    SUSPECT,
    LeakMonitorConfig,
    TranscriptLeakMonitor,
    _RepeatTable,
)
from grapevine_tpu.obs.registry import TelemetryLeakError, TelemetryRegistry
from grapevine_tpu.oram.path_oram import OramConfig, init_oram
from grapevine_tpu.oram.round import oram_round
from grapevine_tpu.wire import constants as C
from grapevine_tpu.testing.leakcheck import (
    samekey_collision_counts,
    samekey_leaf_collisions,
    uniformity_z,
    uniformity_z_from_counts,
)

U32 = jnp.uint32

CFG = OramConfig(height=12, value_words=4, stash_size=512)
B = 256  # the acceptance criterion's batch size

#: acceptance-shaped monitor config: production thresholds, a window
#: that spans the whole honest soak
MCFG = LeakMonitorConfig(window_rounds=512)


def _passthrough(vals0, present0):
    return {}, vals0, present0


def _step(state, idxs, nl, dl):
    st, _, leaves = oram_round(CFG, state, idxs, nl, dl, _passthrough)
    return st, leaves


STEP = jax.jit(_step)


def _uniform(key, n=B):
    return jax.random.bits(key, (n,), U32) & U32(CFG.leaves - 1)


def _populated(seed=0):
    state = init_oram(CFG, jax.random.PRNGKey(seed))

    def ins(vals0, present0):
        return {}, jnp.ones_like(vals0), jnp.ones_like(present0)

    key = jax.random.PRNGKey(seed + 100)
    k1, k2 = jax.random.split(key)
    idxs = jnp.arange(B, dtype=U32)
    state, _, _ = oram_round(CFG, state, idxs, _uniform(k1), _uniform(k2), ins)
    return state


def _mon(cfg=MCFG, registry=None):
    return TranscriptLeakMonitor({"oram": CFG.leaves}, cfg, registry)


def _keys_np(idxs):
    """Monitor key ids from round indices: dummies have no key (-1)."""
    k = np.asarray(idxs).astype(np.int64)
    return np.where(k == CFG.dummy_index, -1, k)


def test_no_remap_leak_flips_suspect_within_64_rounds():
    """ISSUE acceptance: a no-remap leaky variant (remap target = the
    key's current leaf, so every re-access repeats its path) is SUSPECT
    within 64 rounds at batch 256."""
    mon = _mon()
    state = _populated()
    # a quarter of the batch re-reads tracked keys each round; the rest
    # is padding — a realistic partially-filled round
    idxs = jnp.where(
        jnp.arange(B) < B // 4, jnp.arange(B, dtype=U32),
        U32(CFG.dummy_index),
    )
    key = jax.random.PRNGKey(2)
    flipped_at = None
    for r in range(64):
        key, k2 = jax.random.split(key)
        nl = state.posmap[idxs]  # THE LEAK: remap to the current leaf
        state, leaves = STEP(state, idxs, nl, _uniform(k2))
        mon.observe("oram", _keys_np(idxs), np.asarray(leaves))
        if mon.verdict()["verdict"] == SUSPECT:
            flipped_at = r + 1
            break
    assert flipped_at is not None and flipped_at <= 64, (
        f"no-remap leak not flagged within 64 rounds (verdict "
        f"{mon.verdict()})"
    )
    tripped = [
        d["name"] for d in mon.verdict()["detectors"]
        if d["verdict"] == SUSPECT
    ]
    assert "cross_round_repeat" in tripped


def test_no_dedup_leak_trips_collision_detector():
    """Dummy fetches reusing the key's real leaf correlate same-key ops
    within a round — the collision detector's case."""
    mon = _mon()
    state = _populated()
    idxs = jnp.zeros((B,), U32)  # every op touches key 0
    key = jax.random.PRNGKey(3)
    for _ in range(4):
        key, k1 = jax.random.split(key)
        real_leaf = jnp.broadcast_to(state.posmap[0], (B,))
        state, leaves = STEP(state, idxs, _uniform(k1), real_leaf)
        mon.observe("oram", _keys_np(idxs), np.asarray(leaves))
    v = mon.verdict()
    coll = next(
        d for d in v["detectors"] if d["name"] == "samekey_collision"
    )
    assert v["verdict"] == SUSPECT and coll["verdict"] == SUSPECT, v
    assert coll["statistic"] > 0.9  # every same-key pair collides


def test_biased_dummy_leak_trips_uniformity_detector():
    """All-padding rounds fetching constant leaf 0 skew the pooled
    histogram — the uniformity detector's case."""
    mon = _mon()
    state = _populated()
    idxs = jnp.full((B,), U32(CFG.dummy_index))
    key = jax.random.PRNGKey(4)
    for _ in range(8):
        key, k1 = jax.random.split(key)
        state, leaves = STEP(
            state, idxs, _uniform(k1), jnp.zeros((B,), U32)
        )
        mon.observe("oram", _keys_np(idxs), np.asarray(leaves))
    v = mon.verdict()
    unif = next(d for d in v["detectors"] if d["name"] == "uniformity")
    assert unif["verdict"] == SUSPECT, v
    assert unif["statistic"] > 50  # orders of magnitude past threshold


def test_honest_soak_512_rounds_passes_all_detectors():
    """ISSUE acceptance: 512 honest rounds at batch 256 PASS on all
    three detectors — with every detector holding enough samples that
    PASS means 'measured honest', not 'insufficient evidence'."""
    reg = TelemetryRegistry()
    mon = _mon(registry=reg)
    state = _populated()
    # mixed traffic: re-read a rotating slice of keys (cross-round
    # repeats + same-key pairs), half the batch padding
    key = jax.random.PRNGKey(5)
    for r in range(512):
        key, k1, k2 = jax.random.split(key, 3)
        base = (r * 16) % B
        track = (jnp.arange(B, dtype=U32) + U32(base)) % U32(B)
        # duplicate keys within the round: slots 2i and 2i+1 share a key
        track = track // U32(2)
        idxs = jnp.where(
            jnp.arange(B) < B // 2, track, U32(CFG.dummy_index)
        )
        state, leaves = STEP(state, idxs, _uniform(k1), _uniform(k2))
        mon.observe("oram", _keys_np(idxs), np.asarray(leaves))
    v = mon.verdict()
    assert v["verdict"] == PASS, v
    for d in v["detectors"]:
        assert d["verdict"] == PASS, d
        assert d["samples"] >= d["min_samples"], (
            f"{d['name']}: PASS by insufficient evidence, not by "
            f"measurement ({d['samples']} < {d['min_samples']})"
        )
    # aggregate gauges exported, sane
    assert reg.get("grapevine_leakmon_uniformity_z") is not None
    z = reg.get("grapevine_leakmon_uniformity_z").get(tree="oram")
    assert abs(z) < 8


def test_streaming_collision_counts_match_quadratic_detector():
    """The O(B log B) windowed counter is the same statistic as the
    all-pairs pytest detector."""
    rng = np.random.default_rng(11)
    for _ in range(20):
        keys = rng.integers(0, 12, size=64)
        leaves = rng.integers(0, 16, size=64)
        coll, pairs = samekey_collision_counts(keys, leaves)
        assert coll == samekey_leaf_collisions(keys, leaves)
        same = keys[:, None] == keys[None, :]
        upper = np.triu(np.ones_like(same, dtype=bool), k=1)
        assert pairs == int(np.sum(same & upper))
    # the -1 no-key sentinel is excluded
    coll, pairs = samekey_collision_counts(
        np.array([-1, -1, 3, 3]), np.array([5, 5, 7, 7])
    )
    assert (coll, pairs) == (1, 1)
    # the combined key's edge: the auditor's largest group id beside the
    # largest leaf of the largest tree, next to their neighbours
    g, lf = 2 ** 13 - 1, 2 ** 21 - 1
    keys = np.array([g, g, g, g - 1, g - 1, 0, 0, 0])
    leaves = np.array([lf, lf, lf - 1, lf, lf, 0, lf, 0])
    coll, pairs = samekey_collision_counts(keys, leaves)
    assert coll == samekey_leaf_collisions(keys, leaves) == 3
    assert pairs == 3 + 1 + 3
    # u32 leaves straight off a transcript fold without wrapping
    assert samekey_collision_counts(
        keys, leaves.astype(np.uint32)) == (coll, pairs)
    # what does not fit an i64 is refused, not wrapped
    with pytest.raises(ValueError, match="i64"):
        samekey_collision_counts(
            np.array([2 ** 62, 2 ** 62]), np.array([lf, lf]))
    with pytest.raises(ValueError, match="non-negative"):
        samekey_collision_counts(np.array([1, 1]), np.array([-1, 4]))


def test_uniformity_from_counts_matches_pooled_detector():
    rng = np.random.default_rng(13)
    leaves = rng.integers(0, 4096, size=8192)
    z_pooled = uniformity_z(leaves, 4096, bins=16)
    counts = np.bincount(leaves * 16 // 4096, minlength=16)
    assert uniformity_z_from_counts(counts) == pytest.approx(z_pooled)


def test_window_slides_and_verdict_recovers():
    """Old rounds age out: a burst of leaky rounds followed by honest
    traffic drains the window and the verdict returns to PASS — the
    re-baseline behavior the runbook describes."""
    cfg = LeakMonitorConfig(window_rounds=8, min_opportunities=4)
    mon = TranscriptLeakMonitor({"oram": 4096}, cfg)
    # leaky burst: one key repeating its leaf every round
    for _ in range(8):
        mon.observe("oram", np.zeros(4, np.int64), np.full(4, 9))
    assert mon.verdict()["verdict"] == SUSPECT
    rng = np.random.default_rng(7)
    for _ in range(16):
        mon.observe(
            "oram",
            np.arange(4, dtype=np.int64),
            rng.integers(0, 4096, size=4),
        )
    assert mon.verdict()["verdict"] == PASS


def test_undeclared_stream_raises():
    mon = _mon()
    with pytest.raises(KeyError):
        mon.observe("nope", None, np.zeros(4, np.int64))


# ---------------------------------------------------------------------
# the auditor's round in arrays (ISSUE 51): the loops it replaced, kept
# as the oracles the array forms are held to
# ---------------------------------------------------------------------


def _loop_key_groups(batch: dict, mb_choices: int):
    """engine/round_step.py ``transcript_key_groups`` as it stood before
    ISSUE 51: a dictionary operation per op, ``bytes`` ids."""
    rt = np.asarray(batch["req_type"]).astype(np.uint32)
    auth = np.asarray(batch["auth"], dtype=np.uint32)
    recipient = np.asarray(batch["recipient"], dtype=np.uint32)
    msg_id = np.asarray(batch["msg_id"], dtype=np.uint32)
    b = rt.shape[0]
    is_real = (rt >= C.REQUEST_TYPE_CREATE) & (rt <= C.REQUEST_TYPE_DELETE)
    is_create = rt == C.REQUEST_TYPE_CREATE
    id_zero = ~msg_id.any(axis=1)
    ka = np.where((is_create | ~id_zero)[:, None], recipient, auth)

    d = mb_choices
    mb_keys = np.full((b * d,), -1, np.int64)
    mb_stable = [None] * (b * d)
    mb_groups: dict = {}
    rec_keys = np.full((b,), -1, np.int64)
    rec_stable = [None] * b
    rec_groups: dict = {}
    for j in range(b):
        if not is_real[j]:
            continue
        kb = ka[j].tobytes()
        g = mb_groups.setdefault(kb, len(mb_groups))
        for c in range(d):
            mb_keys[j * d + c] = g * d + c
            mb_stable[j * d + c] = kb + bytes([c])
        if not is_create[j] and not id_zero[j]:
            mid = msg_id[j].tobytes()
            rec_keys[j] = rec_groups.setdefault(mid, len(rec_groups))
            rec_stable[j] = mid
    return (mb_keys, mb_stable), (rec_keys, rec_stable)


class _LoopTracker:
    """obs/leakmon.py ``_track_repeats`` as it stood before ISSUE 51:
    an ``OrderedDict`` popped, inserted and trimmed once per key."""

    def __init__(self, track: int):
        self.track = track
        self.last_leaf: OrderedDict = OrderedDict()

    def touch(self, skeys, leaves):
        repeats = opportunities = 0
        for skey, leaf in zip(skeys, leaves):
            leaf = int(leaf)
            prev = self.last_leaf.pop(skey, None)
            if prev is not None:
                opportunities += 1
                if prev == leaf:
                    repeats += 1
            self.last_leaf[skey] = leaf
            while len(self.last_leaf) > self.track:
                self.last_leaf.popitem(last=False)
        return repeats, opportunities

    def track_repeats(self, keys, leaves, stable):
        real_idx = np.nonzero(keys >= 0)[0]
        if real_idx.size == 0:
            return 0, 0
        _, first = np.unique(keys[real_idx], return_index=True)
        at = real_idx[first]
        return self.touch(
            [stable[i] if stable is not None else int(keys[i]) for i in at],
            leaves[at],
        )


def _unique_axis0_collision_counts(keys, leaves):
    """testing/leakcheck.py ``samekey_collision_counts`` as it stood
    before ISSUE 51: a structured sort over stacked (key, leaf) rows."""
    real = keys >= 0
    k, lf = keys[real], leaves[real]
    if k.size < 2:
        return 0, 0

    def _pairs(counts):
        counts = counts.astype(np.int64)
        return int(np.sum(counts * (counts - 1) // 2))

    _, key_counts = np.unique(k, return_counts=True)
    _, pair_counts = np.unique(
        np.stack([k.astype(np.int64), lf.astype(np.int64)], axis=1),
        axis=0, return_counts=True)
    return _pairs(pair_counts), _pairs(key_counts)


@pytest.mark.parametrize("track", [3, 16, 64])
@pytest.mark.parametrize("universe,m_max", [(24, 12), (40, 40), (200, 90)])
def test_repeat_table_equals_the_per_key_lru_call_for_call(
    track, universe, m_max
):
    """The array table against the OrderedDict it replaced on streams
    built to overflow inside calls: a call mixes the table's oldest keys
    with keys it has never held, in an order that lets an earlier miss
    evict a later key (the sequential rule's case), and some calls hold
    more keys than the table."""
    rng = np.random.default_rng(universe * 1000 + m_max * 10 + track)
    table, loop = _RepeatTable(track), _LoopTracker(track)
    for call in range(400):
        m = int(rng.integers(1, m_max + 1))
        keys = rng.choice(universe, size=min(m, universe), replace=False)
        if call % 3 == 0 and loop.last_leaf:
            # the oldest keys the oracle holds, each behind a fresh one
            oldest = np.array(list(loop.last_leaf)[: m // 2], np.int64)
            fresh = universe + call * m_max + np.arange(oldest.size)
            keys = np.stack([fresh, oldest], axis=1).ravel()
        keys = keys.astype(np.int64)
        # leaves from a small range, so repeats happen
        leaves = rng.integers(0, 3, size=keys.size)
        assert table.touch(keys, leaves) == loop.touch(
            keys.tolist(), leaves), f"call {call}"
        held, held_leaf = table.by_recency()
        assert held.tolist() == list(loop.last_leaf), f"call {call}"
        assert held_leaf.tolist() == list(loop.last_leaf.values())


def _campaign_batch(rng, b, traffic, idents, ids):
    """One round's host-side columns, no engine: ``idents`` u32[N, 8]
    the identities, ``ids`` u32[M, 4] the msg_ids in circulation."""
    n = idents.shape[0]
    if traffic == "zipf":
        who = np.minimum(rng.zipf(1.5, size=b) - 1, n - 1)
    else:
        who = rng.integers(0, n, size=b)
    rt = rng.integers(C.REQUEST_TYPE_CREATE, C.REQUEST_TYPE_DELETE + 1,
                      size=b).astype(np.uint32)
    msg_id = ids[rng.integers(0, ids.shape[0], size=b)].copy()
    msg_id[rng.random(b) < 0.5] = 0  # zero-id READ / DELETE
    if traffic == "padding":
        rt[rng.random(b) < 0.6] = 0  # padding rows
        rt[rng.random(b) < 0.05] = 7  # and a type out of range
    elif traffic == "all_create":
        rt[:] = C.REQUEST_TYPE_CREATE
    elif traffic == "all_zero_id":
        rt[:] = rng.integers(C.REQUEST_TYPE_READ, C.REQUEST_TYPE_DELETE + 1,
                             size=b)
        msg_id[:] = 0
    elif traffic == "dup_msg_ids":
        rt[:] = rng.integers(C.REQUEST_TYPE_READ, C.REQUEST_TYPE_DELETE + 1,
                             size=b)
        msg_id = ids[rng.integers(0, max(2, b // 8), size=b)].copy()
    return {
        "req_type": rt,
        "auth": idents[rng.integers(0, n, size=b)],
        "recipient": idents[who],
        "msg_id": msg_id,
    }


def _stable_bytes(stable_rows, at):
    """A new stable id as the bytes the loop made of it: the ``ka``
    words then the choice as one byte, or the msg_id's words."""
    row = stable_rows[at]
    if row.size == 9:
        return row[:8].tobytes() + bytes([int(row[8])])
    return row.tobytes()


@pytest.mark.parametrize("track", ["small", "production"])
@pytest.mark.parametrize("traffic", [
    "zipf", "uniform", "padding", "all_create", "all_zero_id",
    "dup_msg_ids",
])
@pytest.mark.parametrize("b", [8, 2048])
@pytest.mark.parametrize("d", [1, 2])
def test_the_round_in_arrays_equals_the_round_per_key(d, b, traffic, track):
    """64 consecutive rounds through the monitor as ``_process`` feeds
    it (mailbox A, records, mailbox C), the table small enough to
    overflow inside calls: group ids equal element for element,
    (collisions, pairs, repeats, opportunities) equal call for call, the
    tracker's table equal in content and recency order at the end."""
    track_keys = {"small": 4 if b == 8 else 64,
                  "production": 64 if b == 8 else 8192}[track]
    rng = np.random.default_rng(
        zlib.crc32(repr((d, b, traffic, track_keys)).encode()))
    n_idents = 16 if b == 8 else 65536
    idents = rng.integers(0, 2 ** 32, size=(n_idents, 8), dtype=np.uint32)
    idents[0] = 0           # an all-zero key row
    idents[1, :7] = 0       # and rows that differ in their last word only
    idents[2] = idents[1]
    idents[2, 7] ^= 1
    ids = rng.integers(0, 2 ** 32, size=(4 * b, 4), dtype=np.uint32)
    mb_leaves, rec_leaves = 1 << 15, 1 << 21
    mon = TranscriptLeakMonitor(
        {"rec": rec_leaves, "mb": mb_leaves},
        LeakMonitorConfig(track_keys=track_keys),
    )
    loops = {"rec": _LoopTracker(track_keys), "mb": _LoopTracker(track_keys)}
    overflowed = 0
    for r in range(64):
        batch = _campaign_batch(rng, b, traffic, idents, ids)
        (mb_keys, mb_stable), (rec_keys, rec_stable) = transcript_key_groups(
            batch, d)
        (o_mb_keys, o_mb_stable), (o_rec_keys, o_rec_stable) = (
            _loop_key_groups(batch, d))
        np.testing.assert_array_equal(mb_keys, o_mb_keys)
        np.testing.assert_array_equal(rec_keys, o_rec_keys)
        assert mb_keys.dtype == rec_keys.dtype == np.int64
        for slot in np.flatnonzero(mb_keys >= 0)[:: max(1, b // 16)]:
            assert _stable_bytes(mb_stable, slot) == o_mb_stable[slot]
        for slot in np.flatnonzero(rec_keys >= 0)[:: max(1, b // 16)]:
            assert _stable_bytes(rec_stable, slot) == o_rec_stable[slot]
        # a tenth of a no-remap engine's repeats, so both counts move
        tr = np.stack(
            [rng.integers(0, mb_leaves, size=b * d) for _ in range(2)]
            + [np.resize(rng.integers(0, rec_leaves, size=b), b * d)])
        if r:
            keep = rng.random(tr.shape) < 0.1
            tr = np.where(keep, last_tr, tr)
        last_tr = tr
        for tree, keys, leaves, stable, o_stable in (
            ("mb", mb_keys, tr[0], mb_stable, o_mb_stable),
            ("rec", rec_keys, tr[2][:b], rec_stable, o_rec_stable),
            ("mb", mb_keys, tr[1], mb_stable, o_mb_stable),
        ):
            before = len(loops[tree].last_leaf)
            want = _unique_axis0_collision_counts(keys, leaves) + (
                loops[tree].track_repeats(keys, leaves, o_stable))
            overflowed += before + np.unique(keys[keys >= 0]).size > track_keys
            mon.observe(tree, keys, leaves, stable)
            got = mon._streams[tree].window[-1][1:]
            assert tuple(int(x) for x in got) == want, (r, tree)
    if traffic in ("zipf", "uniform", "padding") and track == "small":
        assert overflowed > 32  # the campaign did overflow inside calls
    for tree, loop in loops.items():
        held, held_leaf = mon._streams[tree].last_leaf.by_recency()
        width = 9 if tree == "mb" else 4
        rows = held.view(np.uint32).reshape(-1, width) if held.size else []
        assert [_stable_bytes(rows, i) for i in range(len(rows))] == list(
            loop.last_leaf), tree
        assert held_leaf.tolist() == list(loop.last_leaf.values()), tree


# ---------------------------------------------------------------------
# flight recorder leak policy (ISSUE satellite: tier-1 proof the dump
# carries no logical keys, recipient ids, or per-op timestamps)
# ---------------------------------------------------------------------


def test_flight_recorder_dump_is_batch_level_only():
    """Schema enforcement: the ring rejects any field that could carry
    per-op or per-client data, so no dump ever can."""
    fr = FlightRecorder(capacity=4)
    ok = {
        "seq": 1, "t_mono_s": 12.5, "batch_size": 256, "n_real": 100,
        "fill": 0.39, "phase_s": {"dispatch": 0.001, "round": 0.004},
        "stats": {"rec": {"uniformity_z": 0.3, "pooled_leaves": 512}},
        "verdict": "PASS",
    }
    fr.record(ok)
    # a recursive posmap engine's rounds carry the internal-ORAM streams
    # too (leakmon *_pm, PR 7) — the schema must admit them or every
    # round with --posmap-impl recursive raises in the leakmon worker
    fr.record({**ok, "stats": {
        t: {"uniformity_z": 0.1, "pooled_leaves": 64}
        for t in ("rec", "mb", "rec_pm", "mb_pm")
    }})
    for bad in (
        {"recipient": "deadbeef"},            # identity field
        {"msg_id": 7},                        # message id field
        {"keys": [1, 2, 3]},                  # logical keys
        {"op_timestamps": [0.1, 0.2]},        # per-op timestamps
        {**ok, "seq": [1, 2]},                # array-valued scalar slot
        {**ok, "phase_s": {"op_0": 0.1}},     # per-op phase key
        {**ok, "stats": {"client": {}}},      # per-client stat tree
    ):
        with pytest.raises(TelemetryLeakError):
            fr.record(bad)
    # the dump round-trips as JSON and carries only schema'd fields
    dump = json.loads(fr.dump_json())
    assert dump["retained"] == 2  # the ok summary + the *_pm one
    from grapevine_tpu.obs.flightrec import ALLOWED_FIELDS

    for summary in dump["rounds"]:
        assert set(summary) <= ALLOWED_FIELDS
    text = fr.dump_json()
    for forbidden in ("recipient", "msg_id", "auth", "client", "op_"):
        assert forbidden not in text


def test_flight_recorder_ring_wraps():
    fr = FlightRecorder(capacity=3)
    for i in range(7):
        fr.record({"seq": i, "verdict": "PASS"})
    d = fr.dump()
    assert d["recorded_total"] == 7 and d["retained"] == 3
    assert [r["seq"] for r in d["rounds"]] == [4, 5, 6]


def test_flight_recorder_dump_to_file(tmp_path):
    fr = FlightRecorder(capacity=2)
    fr.record({"seq": 0, "verdict": "SUSPECT"})
    path = str(tmp_path / "flight.json")
    fr.dump_to(path)
    with open(path, encoding="utf-8") as fh:
        payload = json.load(fh)
    assert payload["rounds"][0]["verdict"] == "SUSPECT"
