"""The device's idle gaps by what the collector was doing
(``readers/xplane_idle.py``): by-hand cases, a small capture of
backlog-1chip recorded on a TPU v5 lite with the collector's nested
spans in it (tests/data/idle_backlog_v5e.json; its ``note`` says how it
was trimmed), the data files of the metrics that read the program's new
spans and counts, and nothing read where there is nothing to read."""

import json
import os
import types

import pytest

from benchmarks.lib import xplane, xplane_scopes
from benchmarks.lib.manifest import Benchmark, load_kind
from benchmarks.readers import ledger_count_run, xplane_idle

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
FUSION = "%fusion.1 = u32[8]{0} fusion(u32[8]{0} %p), kind=kLoop"
BACKLOG = ["backlog-1chip", "backlog-4chip", "backlog-1chip-r2p16",
           "backlog-grpc-1chip", "backlog-1chip-2p21"]
GROUPS = ("idle_ms.verify", "idle_ms.pack_dispatch", "idle_ms.answers",
          "idle_ms.waiting", "idle_ms.evict")
UNSPANNED = "idle_ms.unspanned"


def _capture(ops, host_spans=()):
    """Programs of 100 ns around two whole rounds: the window is
    [100, 300)."""
    mods = [["jit_round(1)", t, 100.0] for t in (0.0, 100.0, 200.0, 300.0)]
    return {"scope_paths": [], "host_spans": sorted(host_spans,
                                                    key=lambda e: e[1]),
            "planes": [{"name": "/device:TPU:0", "lines": [
                {"name": xplane.MODULES_LINE, "events": mods},
                {"name": xplane.OPS_LINE, "events": ops}]}]}


def _obs(capture, said=None):
    return {"trace": capture, "_scopes": capture,
            "ctx": types.SimpleNamespace(
                say=lambda **kv: said.append(kv) if said is not None else 0)}


def _spans(metric):
    return Benchmark.load().layer_metric(metric)["params"]


def test_innermost_segments_by_hand():
    g = "grapevine/"
    spans = [[g + "cycle", 0.0, 100.0, "t"], [g + "verify", 10.0, 50.0, "t"],
             [g + "verify_prep", 10.0, 5.0, "t"],
             [g + "verify_native", 20.0, 30.0, "t"],
             # ends a rounding after its parent: cut at the parent's end
             [g + "stage", 95.0, 5.5, "t"],
             [g + "cycle", 120.0, 10.0, "t"]]
    assert xplane_idle.innermost_segments(spans) == [
        (0.0, 10.0, g + "cycle"), (10.0, 15.0, g + "verify_prep"),
        (15.0, 20.0, g + "verify"), (20.0, 50.0, g + "verify_native"),
        (50.0, 60.0, g + "verify"), (60.0, 95.0, g + "cycle"),
        (95.0, 100.0, g + "stage"), (120.0, 130.0, g + "cycle")]
    assert xplane_idle.innermost_segments([]) == []
    total = xplane_idle.by_span([(5.0, 25.0), (98.0, 125.0)],
                                xplane_idle.innermost_segments(spans))
    assert total == {g + "cycle": 5.0 + 5.0, g + "verify_prep": 5.0,
                     g + "verify": 5.0, g + "verify_native": 5.0,
                     g + "stage": 2.0, xplane_idle.NO_SPAN: 20.0}


def _one_gap():
    """Busy 100-140 and 200-300: one gap of 60 ns in a window of two
    rounds, and the collector's spans over it."""
    ops = [[FUSION, 100.0, 40.0, -1], [FUSION, 200.0, 50.0, -1],
           [FUSION, 250.0, 50.0, -1]]
    g = "grapevine/"
    collector = [
        [g + "cycle", 90.0, 100.0, "collector"],
        [g + "verify", 130.0, 30.0, "collector"],          # 140-160
        [g + "verify_native", 145.0, 10.0, "collector"],   # 145-155
        [g + "pack", 160.0, 10.0, "collector"],            # 160-170
        [g + "dispatch", 170.0, 12.0, "collector"],        # 170-182
        [g + "journal", 172.0, 4.0, "collector"],          # 172-176
        [g + "settle", 182.0, 6.0, "collector"],           # 182-188
        [g + "cycle", 190.0, 50.0, "collector"],           # 190-200 bare
        [g + "assembly", 196.0, 2.0, "collector"]]
    return ops, collector


def test_a_gap_goes_to_the_innermost_span_of_the_thread_that_cycles():
    ops, collector = _one_gap()
    g = "grapevine/"
    # another thread's spans never count, whatever they cover: a
    # handler's, the expiry timer's checkpoint inside its sweep
    others = [[g + "ingress", 150.0, 25.0, "python3"],
              [g + "sweep", 100.0, 200.0, "python3"],
              [g + "checkpoint", 165.0, 30.0, "python3"]]
    cap = _capture(ops, collector + others)
    table, rounds = xplane_idle.idle_table(cap)
    assert rounds == 2
    ns = {k.removeprefix(g): v * 2 * 1e6 for k, v in table.items()}
    assert ns == pytest.approx({
        "verify": 10.0, "verify_native": 10.0, "pack": 10.0,
        "dispatch": 8.0, "journal": 4.0, "settle": 6.0,
        "cycle": 2.0 + 6.0 + 2.0, "assembly": 2.0,
        xplane_idle.NO_SPAN: 0.0})
    assert sum(ns.values()) == pytest.approx(60.0)
    said = []
    obs = _obs(cap, said)
    got = {m: xplane_idle.read(_spans(m), obs) for m in (*GROUPS, UNSPANNED)}
    assert got == pytest.approx({
        "idle_ms.verify": 20 / 2 / 1e6, "idle_ms.pack_dispatch": 22 / 2 / 1e6,
        "idle_ms.answers": 6 / 2 / 1e6, "idle_ms.waiting": 2 / 2 / 1e6,
        "idle_ms.evict": 0.0, UNSPANNED: 10 / 2 / 1e6})
    # the whole table is said once, by the first read
    assert [s["phase"] for s in said] == ["idle_by_span"]
    assert said[0]["idle_ms_per_round"] == pytest.approx(60 / 2 / 1e6)
    assert {"cycle", "verify_native", xplane_idle.NO_SPAN} <= set(
        dict(said[0]["ms_per_round"]))


def test_a_second_threads_span_on_the_collectors_line_is_refused():
    """A program that does not name its collector's thread shares one
    line among all its threads: a span that starts inside another and
    ends after it says so, and nothing is read as if it were right."""
    ops, collector = _one_gap()
    mixed = collector + [["grapevine/ingress", 150.0, 25.0, "collector"]]
    with pytest.raises(ValueError, match="two threads' spans on one line"):
        xplane_idle.idle_table(_capture(ops, mixed))
    with pytest.raises(ValueError, match="grapevine/ingress"):
        xplane_idle.read(_spans("idle_ms.verify"),
                         _obs(_capture(ops, mixed)))
    # within the rounding of the stamps a span that ends as the next
    # starts is its sibling, not its holder
    near = collector + [["grapevine/stage", 198.0, 1.2, "collector"],
                        ["grapevine/demux", 199.0, 0.8, "collector"]]
    table, _ = xplane_idle.idle_table(_capture(ops, near))
    assert table["grapevine/stage"] * 2 * 1e6 == pytest.approx(1.2)
    assert table["grapevine/demux"] * 2 * 1e6 == pytest.approx(0.6)


def test_nothing_is_read_where_there_is_nothing_to_read():
    ops = [[FUSION, 100.0, 40.0, -1], [FUSION, 200.0, 100.0, -1]]
    params = _spans("idle_ms.verify")
    # no thread keeps a cycle: a program with no span at all, and one
    # that keeps the old spans only (the parent)
    assert xplane_idle.read(params, _obs(_capture(ops))) is None
    assert xplane_idle.read(params, _obs(_capture(ops, [
        ["grapevine/verify", 140.0, 10.0, "t"],
        ["grapevine/dispatch", 150.0, 5.0, "t"]]))) is None
    # a span the cycle's thread never took reads 0
    kept = _capture(ops, [["grapevine/cycle", 100.0, 100.0, "t"],
                          ["grapevine/verify", 140.0, 10.0, "t"]])
    assert xplane_idle.read(params, _obs(kept)) == pytest.approx(10 / 2 / 1e6)
    assert xplane_idle.read(_spans("idle_ms.answers"), _obs(kept)) == 0.0
    # no capture (an untraced run, a CPU rehearsal), no device plane
    assert xplane_idle.read(params, {"trace": None}) is None
    assert xplane_idle.read(params, {"trace": {}, "_scopes": None}) is None
    assert xplane_idle.read(params, _obs(
        {"scope_paths": [], "host_spans": [], "planes": []})) is None


def test_the_new_metric_files_load_and_name_the_programs_spans():
    """Every metric this reader and the program's new spans and counts
    are for: its file loads, its reader is one the benchmark has, its
    cells report what it moves, and what it reads is a name the program
    keeps (obs/phases.py SPAN_NAMES, obs/tracer.py ROUND_COUNTS)."""
    from grapevine_tpu.obs.phases import ANNOTATION_NAMES, SPAN_NAMES
    from grapevine_tpu.obs.tracer import ROUND_COUNTS

    bench = Benchmark.load()
    spans = {"cycle_ms": ["cycle"], "cycle_ms.trickle": ["cycle"],
             "pack_ms": ["pack"], "observe_ms": ["observe"],
             "observe_ms.trickle": ["observe"],
             "release_ms": ["release"], "release_ms.trickle": ["release"],
             "verify_prep_ms": ["verify_prep"],
             "verify_native_ms": ["verify_native"], "stage_ms": ["stage"]}
    counts = {"cycle_wait_ms": "cycle_wait_s",
              "cycle_wait_ms.trickle": "cycle_wait_s",
              "cycle_cpu_ms": "cycle_cpu_s",
              "cycle_cpu_ms.trickle": "cycle_cpu_s",
              "cycle_native_wait_ms": "cycle_native_wait_s",
              "cycle_unspanned_ms.trickle": "cycle_unspanned_s",
              "cycle_blocked_ms": "cycle_blocked_s",
              "cycle_blocked_ms.trickle": "cycle_blocked_s",
              "cycle_unspanned_ms": "cycle_unspanned_s"}
    entries = {m["name"]: m for m in bench.manifest["per_layer"]}
    for name in (*spans, *counts, *GROUPS, UNSPANNED):
        entry, spec = entries[name], bench.layer_metric(name)
        assert entry["unit"] == "ms" and entry["better"] == "lower"
        trickle = name.endswith(".trickle")
        assert entry["workloads"] == (["trickle-1chip"] if trickle
                                      else BACKLOG)
        assert entry["moves"] == ("commit_p50_ms" if trickle else "ops_per_s")
        for cell in entry["workloads"]:
            assert entry["moves"] in {
                e["name"] for e in bench.end_to_end(cell)}
        assert callable(load_kind("readers", spec["reader"]).read)
        if name in spans:
            assert spec["reader"] == "ledger_span"
            assert spec["params"] == {"spans": spans[name]}
            assert entry["source"] == "program_span"
        elif name in counts:
            # CPU seconds come in ticks: averaged over runs of rounds
            coarse = counts[name] in ("cycle_cpu_s", "cycle_blocked_s")
            assert spec["reader"] == ("ledger_count_run" if coarse
                                      else "ledger_count")
            assert spec["params"] == {"count": counts[name],
                                      "scale": 1000.0}
            assert counts[name] in ROUND_COUNTS
        else:
            assert spec["reader"] == "xplane_idle"
            assert entry["source"] == "device_trace"
        # (the sleep outside any cycle is an annotation only)
        assert set(spec["params"].get("spans", ())) <= (
            SPAN_NAMES | ANNOTATION_NAMES)
    # the groups share no span, and leave out only the cycle's own time,
    # which has a metric to itself (and what never runs on the
    # collector's thread)
    grouped = [s for m in GROUPS for s in _spans(m)["spans"]]
    assert len(grouped) == len(set(grouped))
    assert _spans(UNSPANNED)["spans"] == ["cycle"]
    assert SPAN_NAMES - set(grouped) == {"cycle", "sweep", "replay"}
    assert set(grouped) - SPAN_NAMES == {"asleep"}


# -- the recorded capture ------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "idle_backlog_v5e.json")) as f:
        return json.load(f)


def test_recorded_gaps_go_to_the_innermost_span(recorded):
    """Three whole rounds of backlog-1chip on a TPU v5 lite, the
    collector's spans nested as the program takes them."""
    from grapevine_tpu.obs.phases import SPAN_NAMES

    g = xplane_scopes.PROGRAM_SPAN
    names = {e[0].removeprefix(g) for e in recorded["host_spans"]}
    assert {"cycle", "verify", "verify_prep", "verify_native", "stage",
            "pack", "dispatch", "evict", "demux", "observe", "settle",
            "assembly"} <= names <= SPAN_NAMES
    assert {e[3] for e in recorded["host_spans"]} == {"gv-collector"}
    table, rounds = xplane_idle.idle_table(recorded)
    assert rounds == 3
    ops, lo, hi, _ = xplane_scopes.device_window(recorded)
    _, gaps = xplane.union_ns([e[:3] for e in ops], lo, hi)
    idle = sum(b - a for a, b in gaps) / rounds / 1e6
    assert sum(table.values()) == pytest.approx(idle)
    assert 20.0 < idle < 45.0  # the host paces this cell
    # verify holds verify_prep and verify_native, the cycle holds them
    # all: a gap under the native call is the native call's alone
    spans = [e for e in recorded["host_spans"]]
    by_name = lambda n: [e for e in spans if e[0] == g + n]  # noqa: E731
    for inner in by_name("verify_native"):
        assert any(o[1] <= inner[1] and inner[1] + inner[2] <= o[1] + o[2]
                   for o in by_name("verify"))
    covered = lambda n: xplane.union_ns(  # noqa: E731
        [e[:3] for e in by_name(n)], lo, hi)[0]
    idle_under = lambda n: sum(  # noqa: E731
        xplane.union_ns([e[:3] for e in by_name(n)], a, b)[0]
        for a, b in gaps) / rounds / 1e6
    assert table[g + "verify_native"] == pytest.approx(
        idle_under("verify_native"))
    assert table[g + "verify"] == pytest.approx(
        idle_under("verify") - idle_under("verify_native")
        - idle_under("verify_prep"))
    assert table[g + "verify_native"] > table[g + "verify"] > 0
    assert covered("cycle") == pytest.approx(hi - lo, rel=0.001)
    assert table[g + "cycle"] < 0.05 * idle


def test_recorded_groups_and_unattributed_add_up_to_the_idle_time(recorded):
    table, rounds = xplane_idle.idle_table(recorded)
    idle = sum(table.values())
    said = []
    obs = _obs(recorded, said)
    groups = {m: xplane_idle.read(_spans(m), obs) for m in GROUPS}
    unattributed = xplane_scopes.idle_unattributed_ms(recorded)
    assert unattributed < 1.0
    assert unattributed == pytest.approx(table[xplane_idle.NO_SPAN])
    assert sum(groups.values()) + unattributed == pytest.approx(
        idle, rel=0.05)
    g = xplane_scopes.PROGRAM_SPAN
    assert sum(groups.values()) + unattributed + table[g + "cycle"] \
        == pytest.approx(idle)
    # the device waits for the signature check, then for the pack
    assert groups["idle_ms.verify"] > groups["idle_ms.pack_dispatch"] \
        > groups["idle_ms.answers"]
    assert said[0]["rounds"] == rounds


def test_a_count_in_ticks_is_read_over_runs_of_rounds():
    """CPU seconds that come in 10 ms ticks: 8 rounds of 0.03 or 0.04
    average to what they are, where the median over rounds is a tick's
    multiple; a stall moves one run, not the result."""
    ticks = [0.04, 0.03, 0.04, 0.04, 0.03, 0.04, 0.03, 0.04] * 3  # 0.03625
    ticks[9] = 2.5  # a stall inside the second run
    ledger = [{"name": "grapevine/round", "ph": "X", "ts": 1_000_000 + 10 * k,
               "dur": 5, "args": {"seq": k, "cycle_cpu_s": v}}
              for k, v in enumerate(ticks)]
    ledger.reverse()  # runs are taken in seq order, whatever the ring's
    ledger += [{"name": "grapevine/round", "ph": "X", "ts": 5_000_000,
                "dur": 5, "args": {"seq": 99, "cycle_cpu_s": 9.0}},
               {"name": "grapevine/evict", "ph": "X", "ts": 1_000_000,
                "dur": 1, "args": {"seq": 3}}]
    obs = {"window": (1.0, 2.0), "ledger": ledger}
    params = {"count": "cycle_cpu_s", "scale": 1000.0}
    assert ledger_count_run.RUN == 8
    assert ledger_count_run.read(params, obs) == pytest.approx(36.25)
    # fewer rounds than a run, or a program that keeps no such count
    assert ledger_count_run.read(
        params, {**obs, "ledger": ledger[-ledger_count_run.RUN - 1:]}) is None
    assert ledger_count_run.read({**params, "count": "cycle_x"}, obs) is None
    assert ledger_count_run.read(params, {"window": (1.0, 2.0),
                                          "ledger": []}) is None
