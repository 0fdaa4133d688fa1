"""The capture reduced by the program's own names
(``lib/xplane_scopes.py`` and the three new readers): by-hand cases, a
small capture of backlog-1chip recorded on a TPU v5 lite
(tests/data/scopes_backlog_v5e.json; its ``note`` says how it was
trimmed), the protobuf reader on a live CPU profile, and each reader
returning nothing where there is nothing to read."""

import glob
import json
import os
import re
import types

import pytest

from benchmarks.lib import xplane, xplane_scopes
from benchmarks.lib.manifest import Benchmark
from benchmarks.readers import (ledger_count, loadgen_cpu, service_stage,
                                xplane_busy, xplane_scope)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
FUSION = "%fusion.1 = u32[8]{0} fusion(u32[8]{0} %p), kind=kLoop"


def _scope_ms(capture, pattern, device=0):
    """Own ms per round of the ops whose scope path matches, as the
    reader sums them from ``scope_table``."""
    table = xplane_scopes.scope_table(capture, device)
    if table is None:
        return None
    return sum(ms for path, ms in table[0].items()
               if re.search(pattern, path))


def _capture(ops, host_spans=(), paths=()):
    """Three whole-less programs of 100 ns around two whole rounds:
    the window is [100, 300)."""
    mods = [["jit_round(1)", t, 100.0] for t in (0.0, 100.0, 200.0, 300.0)]
    return {"scope_paths": list(paths), "host_spans": list(host_spans),
            "planes": [{"name": "/device:TPU:0", "lines": [
                {"name": xplane.MODULES_LINE, "events": mods},
                {"name": xplane.OPS_LINE, "events": ops}]}]}


def test_own_time_partitions_the_union_by_hand():
    # a holds b (a `while` and its body); c overlaps the end of a
    ev = [["a", 10, 50, 0], ["b", 20, 10, 1], ["c", 55, 20, 2],
          ["d", 90, 30, 3]]
    own = xplane_scopes.own_time(ev, 0, 100)
    assert own == [10 + 25, 10, 20, 10]  # d clipped at 100
    busy, _ = xplane.union_ns([e[:3] for e in ev], 0, 100)
    assert sum(own) == busy == 75
    assert xplane_scopes.own_time(ev, 30, 60) == [25, 0, 5, 0]
    assert xplane_scopes.own_time([], 0, 10) == []


def test_scope_time_by_hand():
    paths = ["jit(step)/grapevine/round_a_mailbox/grapevine/oram_fetch/gather",
             "jit(step)/grapevine/round_a_mailbox/grapevine/oram_evict/sort",
             "jit(step)/grapevine/respond/select_n", "jit(step)/reshape"]
    ops = [[FUSION, 100.0, 40.0, 0], [FUSION, 140.0, 20.0, 1],
           [FUSION, 160.0, 10.0, 2], [FUSION, 170.0, 6.0, 3],
           [FUSION, 176.0, 4.0, -1],
           [FUSION, 200.0, 40.0, 0], [FUSION, 240.0, 20.0, 1],
           [FUSION, 320.0, 50.0, 0]]  # the last lies outside the window
    cap = _capture(ops, paths=paths)
    per_round = lambda rx: _scope_ms(cap, rx)  # noqa: E731
    assert per_round("grapevine/round_a_mailbox(?:/|$)") == \
        pytest.approx(120 / 2 / 1e6)
    assert per_round("grapevine/oram_fetch(?:/|$)") == pytest.approx(40e-6)
    assert per_round(xplane_scopes.UNSCOPED) == pytest.approx(10 / 2 / 1e6)
    table, rounds = xplane_scopes.scope_table(cap)
    assert rounds == 2 and table[""] == pytest.approx(2e-6)
    assert sum(table.values()) == pytest.approx(140 / 2 / 1e6)
    assert _scope_ms(cap, "x", device=3) is None


def test_idle_unattributed_by_hand():
    # busy 100-150 and 180-300: one gap of 30 ns, 20 of them inside
    # grapevine/evict on the collector thread
    ops = [[FUSION, 100.0, 50.0, -1], [FUSION, 180.0, 60.0, -1],
           [FUSION, 240.0, 60.0, -1]]
    spans = [["grapevine/evict", 140.0, 30.0, "python3"],
             ["grapevine/verify", 400.0, 30.0, "python3"]]
    cap = _capture(ops, host_spans=spans)
    assert xplane_scopes.idle_unattributed_ms(cap) == \
        pytest.approx(10 / 2 / 1e6)
    assert xplane_scopes.idle_unattributed_ms(_capture(ops)) == \
        pytest.approx(30 / 2 / 1e6)


def test_stage_patterns_take_the_first_stage_inside_a_round():
    """The manifest's four stage metrics split a round's ops by the
    first stage scope after the round scope: a recursive position map's
    inner round (evict inside posmap inside fetch) is fetch time."""
    bench = Benchmark.load()
    rx = {stage: bench.layer_metric(f"scope_ms.{stage}")["params"]["scope"]
          for stage in ("fetch", "apply", "evict", "writeback")}
    a = "jit(engine_round_step)/grapevine/round_a_mailbox/"
    cases = {
        a + "grapevine/oram_fetch/grapevine/path_gather/gather": "fetch",
        a + "grapevine/oram_fetch/grapevine/posmap/grapevine/oram_evict/"
            "grapevine/oram_evict_sort/sort": "fetch",
        a + "grapevine/oram_apply/jit(f)/grapevine/round_a_mailbox/"
            "grapevine/oram_apply/select_n": "apply",
        a + "grapevine/oram_evict/grapevine/oram_evict_sort/sort": "evict",
        a + "grapevine/oram_writeback/grapevine/cipher_encrypt/xor":
            "writeback",
        "jit(engine_round_step)/grapevine/round_b_records/grapevine/"
        "oram_evict": "evict",
        "jit(engine_round_step)/grapevine/request_unpack/eq": None,
        "jit(engine_round_step)/grapevine/engine_flush/grapevine/"
        "oram_flush/scatter": None,
    }
    for path, want in cases.items():
        got = [s for s, pattern in rx.items() if re.search(pattern, path)]
        assert got == ([want] if want else []), path
    rounds = {k: bench.layer_metric(f"scope_ms.{k}")["params"]["scope"]
              for k in ("mailbox_a", "records_b", "mailbox_c")}
    assert [k for k, p in rounds.items()
            if re.search(p, a + "grapevine/oram_fetch")] == ["mailbox_a"]


def test_the_protobuf_reader_on_a_live_cpu_profile(tmp_path):
    """A live profile of the CPU: no device plane, the program's own
    host spans kept with their thread, and the same file still reads
    through ``lib/xplane.py``."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "trace"),
                             profiler_options=options)
    with jax.profiler.TraceAnnotation("grapevine/dispatch"):
        y = f(x)
    with jax.profiler.TraceAnnotation("bench/resolve"):
        y.block_until_ready()
    jax.profiler.stop_trace()
    path = xplane_scopes.capture_file(str(tmp_path))
    assert path in glob.glob(str(tmp_path / "trace" / "plugins" / "profile"
                                 / "*" / "*.xplane.pb"))
    cap = xplane_scopes.read(path)
    assert cap["planes"] == [] and cap["scope_paths"] == []
    (span,) = cap["host_spans"]
    assert span[0] == "grapevine/dispatch" and span[2] > 0 and span[3]
    same = [e for p in xplane.read(
        path, keep_host=lambda n: n == "grapevine/dispatch")["planes"]
        for ln in p["lines"] for e in ln["events"]]
    assert same[0][1] == pytest.approx(span[1], abs=1.0)
    assert same[0][2] == pytest.approx(span[2], abs=1.0)
    json.dumps(cap)  # plain data
    assert xplane_scopes.scope_table(cap) is None
    assert xplane_scopes.capture_file(str(tmp_path / "nowhere")) is None
    # the reader over it: a capture with no device plane gives nothing
    said = []
    obs = {"trace": {"planes": []}, "ctx": types.SimpleNamespace(
        scratch=str(tmp_path), say=lambda **kv: said.append(kv))}
    for params in ({"scope": "grapevine/"}, {"quantity": "unscoped"},
                   {"quantity": "idle_unattributed"}):
        assert xplane_scope.read(params, obs) is None
    assert said == []
    assert xplane_scope.read({"scope": "x"}, {"trace": None}) is None
    with pytest.raises(ValueError):
        xplane_scope.read({"quantity": "nonsense"}, obs)


def _round_event(seq, ts, **args):
    return {"name": "grapevine/round", "ph": "X", "ts": ts, "dur": 5,
            "args": {"seq": seq, **args}}


def test_ledger_count_reader():
    obs = {"window": (1.0, 2.0), "ledger": [
        {"name": "process_name", "ph": "M", "args": {"name": "x"}},
        _round_event(1, 900_000, ops=4, queue_wait_sum_s=9.0, rounds_ahead=0),
        _round_event(2, 1_100_000, ops=4, queue_wait_sum_s=0.4, rounds_ahead=1),
        _round_event(3, 1_400_000, ops=2, queue_wait_sum_s=0.4, rounds_ahead=2),
        _round_event(4, 1_700_000, ops=0, queue_wait_sum_s=0.0, rounds_ahead=2),
        {"name": "grapevine/evict", "ph": "X", "ts": 1_500_000, "dur": 1,
         "args": {"seq": 3}}]}
    wait = {"count": "queue_wait_sum_s", "per": "ops", "scale": 1000.0}
    # rounds 2 and 3 (1 lies before the window, 4 admitted no op)
    assert ledger_count.read(wait, obs) == pytest.approx(150.0)
    assert ledger_count.read({"count": "rounds_ahead"}, obs) == 2
    # a program whose ledger keeps no counts: nothing to read
    bare = {"window": (1.0, 2.0), "ledger": [
        {"name": "grapevine/round", "ph": "X", "ts": 1_100_000, "dur": 5,
         "args": {"seq": 2}}]}
    assert ledger_count.read(wait, bare) is None
    assert ledger_count.read({"count": "rounds_ahead"}, bare) is None


def test_service_stage_reader():
    from grapevine_tpu.obs.registry import TelemetryRegistry

    reg = TelemetryRegistry()
    obs = {"ctx": types.SimpleNamespace(
        server=types.SimpleNamespace(metrics_registry=reg))}
    wake = {"phases": ["wake"], "scale": 1000.0}
    work = {"phases": ["open", "seal"], "scale": 1e6}
    assert service_stage.read(wake, obs) is None  # no such counters
    seconds = reg.counter("grapevine_service_seconds_total", "s",
                          labels={"phase": ("open", "wait", "wake", "seal")})
    queries = reg.counter("grapevine_service_queries_total", "n")
    assert service_stage.read(wake, obs) is None  # no Query served
    for phase, s in (("open", 0.001), ("wait", 4.0), ("wake", 0.1),
                     ("seal", 0.003)):
        seconds.inc(s, phase=phase)
    queries.inc(4)
    assert service_stage.read(wake, obs) == pytest.approx(25.0)
    assert service_stage.read(work, obs) == pytest.approx(1000.0)
    no_registry = {"ctx": types.SimpleNamespace(server=object())}
    assert service_stage.read(wake, no_registry) is None


def test_loadgen_cpu_reader():
    waves = [[0.010, 0.002], [0.012, 0.004], [0.030, 0.003]]
    obs = {"observed": {"loadgen_cpu": waves}}
    # means: the thread clock ticks in 10 ms steps on the chip's host
    assert loadgen_cpu.read({"quantity": "submit_ms"}, obs) == \
        pytest.approx(52.0 / 3)
    assert loadgen_cpu.read({"quantity": "own_ms"}, obs) == pytest.approx(3.0)
    # a cell loaded from client processes takes no such stamps
    assert loadgen_cpu.read({"quantity": "own_ms"}, {"observed": {}}) is None
    assert loadgen_cpu.read({"quantity": "own_ms"},
                            {"observed": {"loadgen_cpu": []}}) is None
    with pytest.raises(ValueError):
        loadgen_cpu.read({"quantity": "wall_ms"}, obs)


def test_scope_chains_by_hand():
    chain = xplane_scopes.scope_chain
    assert chain("jit(engine_round_step)/grapevine/round_a_mailbox/"
                 "grapevine/oram_apply/gather:") == "round_a_mailbox/oram_apply"
    # a scope entered twice (a callback traced inside itself) folds
    assert chain("jit(f)/grapevine/round_b_records/grapevine/oram_fetch/"
                 "grapevine/oram_fetch/grapevine/path_gather") == \
        "round_b_records/oram_fetch/path_gather"
    assert chain("jit(f)/mul") == "" and chain("") == ""
    fusion2 = FUSION.replace("fusion.1 ", "fusion.2 ")
    capture = _capture(
        [[FUSION, 110.0, 10.0, 0], [fusion2, 130.0, 10.0, -1]],
        paths=["jit(f)/grapevine/round_c_mailbox/grapevine/oram_evict/sort"])
    assert xplane_scopes.op_scope_chains(capture) == {
        "fusion.1 u32[8]": "round_c_mailbox/oram_evict", "fusion.2 u32[8]": ""}
    assert xplane_scopes.op_scope_chains(capture, device=3) == {}


def test_the_breakdown_names_an_op_with_its_scope_chain(recorded):
    trace = {"planes": [{"name": p["name"], "lines": [
        {"name": ln["name"], "events": [e[:3] for e in ln["events"]]}
        for ln in p["lines"]]} for p in recorded["planes"]]}
    obs = {"trace": trace, "ctx": types.SimpleNamespace(scratch="/nowhere"),
           "_scopes": recorded}
    ops = xplane_busy.device_busy(obs)["breakdown"]["device_ops"]
    assert len(ops) == 10
    chains = xplane_scopes.op_scope_chains(recorded)
    for name, seconds in ops:
        scope, _, op = name.rpartition(" ")[0].rpartition(" ")
        assert seconds > 0 and re.match(r"^[a-z_.\-0-9]+$", op), name
        assert scope.split("/")[0] in (
            "round_a_mailbox", "round_b_records", "round_c_mailbox"), name
        assert chains[name[len(scope) + 1:]] == scope
    assert ops[0][0].startswith("round_")
    # without a capture the names stay as the trace has them
    bare = xplane_busy.device_busy({"trace": trace})
    assert [s for _, s in bare["breakdown"]["device_ops"]] == [
        s for _, s in ops]
    assert not any(n.startswith("round_")
                   for n, _ in bare["breakdown"]["device_ops"])


# -- the recorded capture ------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "scopes_backlog_v5e.json")) as f:
        return json.load(f)


def _metric_pattern(name):
    return Benchmark.load().layer_metric(name)["params"]["scope"]


def test_recorded_rounds_split_by_scope(recorded):
    """Two whole rounds of backlog-1chip on a TPU v5 lite: the three
    tree rounds and the four stages each partition the scoped time."""
    table, rounds = xplane_scopes.scope_table(recorded)
    assert rounds == 2
    total = sum(table.values())
    ops, lo, hi, _ = xplane_scopes.device_window(recorded)
    busy, _ = xplane.union_ns([e[:3] for e in ops], lo, hi)
    assert total == pytest.approx(busy / 2 / 1e6) == pytest.approx(354.507982)
    got = {k: _scope_ms(
        recorded, _metric_pattern(f"scope_ms.{k}"))
        for k in ("mailbox_a", "records_b", "mailbox_c",
                  "fetch", "apply", "evict", "writeback")}
    assert got["mailbox_a"] == pytest.approx(148.883617)
    assert got["records_b"] == pytest.approx(78.943745)
    assert got["mailbox_c"] == pytest.approx(126.538601)
    assert got["fetch"] == pytest.approx(84.017977)
    assert got["apply"] == pytest.approx(36.317800)
    assert got["evict"] == pytest.approx(143.853049)
    assert got["writeback"] == pytest.approx(90.177138)
    in_rounds = got["mailbox_a"] + got["records_b"] + got["mailbox_c"]
    assert got["fetch"] + got["apply"] + got["evict"] + got["writeback"] == \
        pytest.approx(in_rounds)
    unscoped = _scope_ms(
        recorded, xplane_scopes.UNSCOPED)
    assert unscoped == pytest.approx(0.11160385)
    outside = _scope_ms(
        recorded, r"grapevine/(request_unpack|freelist_counters|respond|"
        r"transcript)(?:/|$)")
    assert in_rounds + outside + unscoped == pytest.approx(total)
    # every scope met is one the program declares
    from grapevine_tpu.obs.phases import DEVICE_SCOPES

    met = {n for p in recorded["scope_paths"]
           for n in re.findall(r"grapevine/([A-Za-z0-9_]+)", p)}
    assert met <= set(DEVICE_SCOPES)
    assert {"cipher_decrypt", "cipher_encrypt", "path_gather",
            "path_scatter", "cache_read", "cache_write"} <= met


def test_recorded_host_spans_and_idle(recorded):
    names = {e[0] for e in recorded["host_spans"]}
    assert names == {"grapevine/assembly", "grapevine/verify",
                     "grapevine/dispatch", "grapevine/evict",
                     "grapevine/demux", "grapevine/settle"}
    assert {e[3] for e in recorded["host_spans"]} == {"python3"}
    # the fixture dropped the ops under 20 us, so it shows gaps the
    # whole capture did not have; grapevine/evict covers most of them
    idle = xplane_scopes.idle_unattributed_ms(recorded)
    ops, lo, hi, _ = xplane_scopes.device_window(recorded)
    _, gaps = xplane.union_ns([e[:3] for e in ops], lo, hi)
    assert 0 < idle < sum(b - a for a, b in gaps) / 2 / 1e6
    said = []
    obs = {"trace": recorded, "_scopes": recorded, "ctx": types.SimpleNamespace(
        say=lambda **kv: said.append(kv))}
    assert xplane_scope.read({"quantity": "idle_unattributed"}, obs) == idle
    assert said == []
    assert xplane_scope.read({"quantity": "unscoped"}, obs) == \
        pytest.approx(0.11160385)
    assert xplane_scope.read(
        {"scope": _metric_pattern("scope_ms.evict")}, obs) == \
        pytest.approx(143.853049)
    (line,) = said  # the table is reduced and said once
    assert line["phase"] == "scopes" and line["rounds"] == 2
    assert line["ms_per_round"][0][0] == "round_b_records/oram_evict"
    assert line["total_ms_per_round"] == pytest.approx(354.507982)
    assert line["host_spans"]["grapevine/settle@python3"] == 4


# -- the capture's own bytes: a hand-made XSpace -------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _f(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _hlo_inst(name, inst_id, op_name="", operands=(), called=()):
    body = _f(1, name) + _f(35, inst_id)
    if op_name:
        body += _f(7, _f(2, op_name))
    if operands:
        body += _f(36, b"".join(_varint(o) for o in operands))  # packed
    for c in called:
        body += _f(38, c)
    return _f(2, body)


def _xspace():
    """A device plane whose ops are: one with ``tf_op``, a fusion the
    compiler made without it (its body votes), a layout copy of the
    fusion's result (takes its operand's scope) and one nothing names;
    beside it the host plane and the compiled program."""
    fetch = "jit(step)/grapevine/round_a_mailbox/grapevine/oram_fetch"
    evict = "jit(step)/grapevine/round_a_mailbox/grapevine/oram_evict"
    hlo = _f(1, _f(3, _f(5, 7)  # the fusion's body
                      + _hlo_inst("add.1", 11, fetch + "/grapevine/cipher_decrypt/add")
                      + _hlo_inst("xor.2", 12, fetch + "/grapevine/cipher_decrypt/xor")
                      + _hlo_inst("iota.3", 13, evict + "/iota"))
             + _f(3, _f(5, 1)
                  + _hlo_inst("gather.9", 21, fetch + "/gather")
                  + _hlo_inst("made_fusion.4", 22, operands=(21,), called=(7,))
                  + _hlo_inst("copy.5", 23, operands=(22, 21))
                  + _hlo_inst("constant.6", 24)))
    stat_names = {1: "tf_op", 2: "Hlo Proto"}
    stat_meta = b"".join(_f(5, _f(1, k) + _f(2, _f(1, k) + _f(2, v)))
                         for k, v in stat_names.items())

    def meta(meta_id, name, stats=b""):
        return _f(4, _f(1, meta_id) + _f(2, _f(1, meta_id) + _f(2, name) + stats))

    def event(meta_id, offset_ps, duration_ps):
        return _f(4, _f(1, meta_id) + _f(2, offset_ps) + _f(3, duration_ps))

    ops = [("%gather.9 = u32[8]{0} gather(u32[8]{0} %p)",
            _f(5, _f(1, 1) + _f(5, fetch + "/gather:"))),
           ("%made_fusion.4 = u32[8]{0} fusion(u32[8]{0} %gather.9)", b""),
           ("%copy.5 = u32[8]{0} copy(u32[8]{0} %made_fusion.4)", b""),
           ("%constant.6 = u32[] constant(0)", b"")]
    device = _f(2, "/device:TPU:0") + stat_meta
    for i, (name, stats) in enumerate(ops, start=1):
        device += meta(i, name, stats)
    device += meta(9, "jit_step(1)")
    device += _f(3, _f(2, xplane.OPS_LINE) + _f(3, 1000)
                 + b"".join(event(i, 10_000 * i, 5_000) for i in range(1, 5)))
    device += _f(3, _f(2, xplane.MODULES_LINE) + _f(3, 1000)
                 + event(9, 0, 60_000))
    host = (_f(2, xplane.HOST_PLANE) + meta(1, "grapevine/settle")
            + meta(2, "PjitFunction(step)")
            + _f(3, _f(2, "collector") + _f(3, 2000)
                 + event(1, 500, 1_500) + event(2, 0, 9_000)))
    program = (_f(2, xplane_scopes.HLO_PLANE) + stat_meta
               + meta(1, "jit_step(1)", _f(5, _f(1, 2) + _f(6, hlo))))
    return _f(1, device) + _f(1, host) + _f(1, program), fetch


def test_read_takes_tf_op_and_falls_back_to_the_compiled_program(tmp_path):
    blob, fetch = _xspace()
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(blob)
    cap = xplane_scopes.read(str(path))
    (plane,) = cap["planes"]
    ops = xplane.line_events(plane, xplane.OPS_LINE)
    assert [xplane.short_name(e[0]) for e in ops] == [
        "gather.9 u32[8]", "made_fusion.4 u32[8]", "copy.5 u32[8]",
        "constant.6 u32[]"]
    paths = [cap["scope_paths"][e[3]] if e[3] >= 0 else None for e in ops]
    assert paths == [
        fetch + "/gather:",  # its own tf_op
        fetch + "/grapevine/cipher_decrypt",  # two of its body's three
        fetch + "/grapevine/cipher_decrypt",  # its first operand's
        None]  # nothing names it
    # line timestamp (ns) + offset (ps): 1000 ns + 10 ns, 5 ns long
    assert ops[0][1:3] == [1010.0, 5.0]
    assert xplane.line_events(plane, xplane.MODULES_LINE) == [
        ["jit_step(1)", 1000.0, 60.0]]
    assert cap["host_spans"] == [["grapevine/settle", 2000.5, 1.5,
                                  "collector"]]
    # a capture that holds no compiled program: unnamed ops stay unnamed
    assert xplane_scopes.hlo_scope_resolver(b"")(
        "%copy.5 = u32[8]{0} copy(u32[8]{0} %made_fusion.4)") == ""
