"""The whole-round trace reduction, on a small recorded trace of
backlog-1chip on a TPU v5 lite (tests/data/trace_backlog_v5e.json; its
``note`` says how it was trimmed) and on hand-made events."""

import json
import os

import pytest

from benchmarks.lib import xplane
from benchmarks.readers import xplane_busy, xplane_ops

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "trace_backlog_v5e.json")) as f:
        return json.load(f)


def test_busy_union_and_gaps_by_hand():
    ev = [["a", 10, 10], ["b", 15, 10], ["c", 40, 5], ["d", 90, 30]]
    busy, gaps = xplane.union_ns(ev, 0, 100)
    assert busy == 15 + 5 + 10  # a|b overlap; d clipped at 100
    assert gaps == [(0, 10), (25, 40), (45, 90)]
    assert xplane.union_ns([], 0, 7) == (0.0, [(0, 7)])


def test_gap_attribution_by_hand():
    spans = [["bench/submit", 0, 30], ["bench/wait_round", 30, 100]]
    assert xplane.attribute_gap((5, 20), spans) == "bench/submit"
    assert xplane.attribute_gap((25, 60), spans) == "bench/wait_round"
    assert xplane.attribute_gap((200, 300), spans) == "host:unattributed"


def test_recorded_rounds_are_found_by_program_name(recorded):
    plane = xplane.device_planes(recorded)[0][1]
    mods = xplane.round_modules(plane)
    assert len(mods) == 4
    assert {m[0].split("(")[0] for m in mods} == {"jit_engine_round_step"}
    lo, hi, rounds = xplane.whole_rounds_window(plane)
    assert rounds == 2 and lo == mods[1][1] and hi == mods[3][1]
    assert (hi - lo) / 2 / 1e6 == pytest.approx(358.67, abs=0.01)


def test_the_program_the_trace_began_in_is_not_a_round():
    """The profiler starts in the middle of a round and gives the
    program then running an event that begins with the trace: 100 of
    its 360 ms here. Counted as a round, the three periods below would
    read (100 + 720) / 3 = 273 ms each."""
    mods = [["jit_round(1)", 0.0, 100e6], ["jit_round(1)", 100e6, 360e6],
            ["jit_round(1)", 460e6, 360e6], ["jit_round(1)", 820e6, 40e6]]
    ops = [["%fusion.1 = u32[8]{0} fusion(u32[8]{0} %p)", t, 350e6]
           for t in (-260e6, 100e6, 460e6)] + [
           ["%fusion.1 = u32[8]{0} fusion(u32[8]{0} %p)", 820e6, 40e6]]
    resolves = [["bench/resolve", t, 5e6] for t in (95e6, 455e6, 815e6)]
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": xplane.MODULES_LINE, "events": mods},
            {"name": xplane.OPS_LINE, "events": ops}]},
        {"name": xplane.HOST_PLANE, "lines": [
            {"name": "python3", "events": resolves}]}]}
    plane = xplane.device_planes(trace)[0][1]
    assert xplane.whole_rounds_window(plane) == (100e6, 820e6, 2)
    s = xplane.busy_summary(trace)
    assert s["rounds"] == 2 and s["programs"] == 4
    assert s["period_ms"] == pytest.approx(360.0)
    assert s["host_period_ms"] == pytest.approx(360.0)
    assert s["busy_ms_per_round"] == pytest.approx(350.0)
    assert s["first_program_ms"] == pytest.approx(100.0)
    two = dict(trace, planes=[dict(trace["planes"][0], lines=[
        {"name": xplane.MODULES_LINE, "events": mods[:2]},
        {"name": xplane.OPS_LINE, "events": ops}])])
    assert xplane.busy_summary(two) is None  # no whole round to count


def test_an_event_as_long_as_the_program_is_not_work(recorded):
    plane = xplane.device_planes(recorded)[0][1]
    ops = xplane.line_events(plane, xplane.OPS_LINE)
    wrapper = [e for e in ops if e[0].startswith("%copy.2424 = u32[0,1520]")]
    assert len(wrapper) == 1 and wrapper[0][2] > 358e6
    assert len(xplane.work_ops(plane)) == len(ops) - 1
    # counted, it would fill every gap of its round: busy == window
    busy, _ = xplane.union_ns(ops, 0.0, 358.6e6)
    assert busy == pytest.approx(358.6e6)
    busy, _ = xplane.union_ns(xplane.work_ops(plane), 0.0, 358.6e6)
    assert busy < 356e6


def test_recorded_busy_per_round_and_breakdown(recorded):
    s = xplane.busy_summary(recorded)
    assert s["rounds"] == 2 and s["programs"] == 4
    assert s["window_s"] == pytest.approx(0.71733576)
    # the fixture dropped the ops under 20 us, so it reads a little less
    # busy than the whole trace did
    assert s["busy_s"] == pytest.approx(0.709072469)
    assert s["busy_ms_per_round"] == pytest.approx(354.5362345)
    # the device's period and the host's, on the trace's clock
    assert s["period_ms"] == pytest.approx(358.66788)
    assert s["host_period_ms"] == pytest.approx(359.057509)
    ops = s["breakdown"]["device_ops"]
    assert ops[0][0] == "fusion.188 u32[180224,1520]"
    assert ops[0][1] == pytest.approx(0.016749605)
    assert len(ops) == 10 and all(a[1] >= b[1] for a, b in zip(ops, ops[1:]))
    gaps = dict(s["breakdown"]["idle_gaps"])
    assert set(gaps) <= {"bench/submit", "bench/wait_round", "bench/dispatch",
                         "bench/build_wave", "bench/resolve",
                         "host:unattributed"}
    assert sum(gaps.values()) == pytest.approx(s["window_s"] - s["busy_s"])


def test_recorded_op_name_match_per_round(recorded):
    assert xplane.matching_ms_per_round(
        recorded, r"^fusion\.188 ", 0) == pytest.approx(8.3748025)
    # %fusion.187 is an operand of fusion.188: operands do not match
    assert xplane.matching_ms_per_round(recorded, r"fusion\.187 ", 0) < 8.0
    assert xplane.matching_ms_per_round(recorded, "all-reduce", 0) == 0.0
    assert xplane.matching_ms_per_round(recorded, "all-reduce", 3) is None
    obs = {"trace": recorded}
    assert xplane_ops.read({"match": "all-reduce"}, obs) is None
    assert xplane_ops.read({"match": r"^fusion\.18[38] "}, obs) == \
        pytest.approx(16.746606)


def test_readers_turn_busy_time_into_metrics(recorded):
    geometry = {"shards": 1, "trees": {"t": {
        "accesses": 2048, "passes": 1, "path_len": 20, "cached_levels": 4,
        "bucket_slots": 4, "value_words": 256, "encrypted": True}}}
    obs = {"trace": recorded, "geometry": geometry,
           "device_kind": "TPU v5 lite"}
    ms = xplane_busy.read({"quantity": "busy_ms_per_round"}, obs)
    assert ms == pytest.approx(354.5362345)
    # levels 4..10 whole (2,032 buckets), 11..19 a row per access
    least = 2 * (2032 + 9 * 2048) * 1030 * 4  # read + written
    share = xplane_busy.read({"quantity": "hbm_roofline_pct"}, obs)
    assert share == pytest.approx(100 * least / 819e9 * 1e3 / ms)
    assert 0 < share < 100
    with pytest.raises(KeyError):
        xplane_busy.read({"quantity": "hbm_roofline_pct"},
                         dict(obs, device_kind="cpu", _busy=None) | {
                             "_busy": xplane.busy_summary(recorded)})
    assert xplane_busy.read({"quantity": "busy_ms_per_round"},
                            {"trace": None}) is None


def test_op_labels_name_the_op_and_its_opcode():
    psum = ("%psum.62 = u32[28672,6080]{1,0:T(8,128)} all-reduce(u32[28672,"
            "6080]{1,0:T(8,128)} %fusion.9), replica_groups={{0,1,2,3}}")
    assert xplane.op_label(psum) == "psum.62 all-reduce"
    start = ("%copy-start.428 = (s32[165984]{0:T(1024)}, s32[165984]{0:T(1024)"
             "S(1)}, u32[]{:S(2)}) copy-start(s32[165984]{0:T(1024)S(1)} %f.49)")
    assert xplane.op_label(start) == "copy-start.428 copy-start"
    eats = "%fusion.9 = u32[8]{0} fusion(u32[8]{0} %all-reduce.3), kind=kLoop"
    assert xplane.op_label(eats) == "fusion.9 fusion"
    assert xplane.op_label("jit_step(1)") == "jit_step(1)"


def test_short_names():
    long = ("%fusion.188 = u32[180224,1520]{1,0:T(8,128)} fusion(s32[184416]"
            "{0:T(1024)S(1)} %get-tuple-element.548), kind=kCustom")
    assert xplane.short_name(long) == "fusion.188 u32[180224,1520]"
    assert xplane.short_name("jit_engine_round_step(28)") == \
        "jit_engine_round_step(28)"


def test_read_turns_a_profile_into_plain_data(tmp_path):
    """A live profile of the CPU: no device plane, the benchmark's own
    spans kept from the host plane and nothing else of it."""
    import glob

    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    with jax.profiler.TraceAnnotation("bench/dispatch"):
        y = f(x)
    with jax.profiler.TraceAnnotation("other/span"):
        y.block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    plain = xplane.read(path[0])
    assert xplane.device_planes(plain) == []
    assert [e[0] for e in xplane.host_spans(plain)] == ["bench/dispatch"]
    assert xplane.busy_summary(plain) is None
    json.dumps(plain)  # plain data
