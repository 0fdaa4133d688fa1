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
  timestamps.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from grapevine_tpu.obs.flightrec import FlightRecorder
from grapevine_tpu.obs.leakmon import (
    PASS,
    SUSPECT,
    LeakMonitorConfig,
    TranscriptLeakMonitor,
)
from grapevine_tpu.obs.registry import TelemetryLeakError, TelemetryRegistry
from grapevine_tpu.oram.path_oram import OramConfig, init_oram
from grapevine_tpu.oram.round import oram_round
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
