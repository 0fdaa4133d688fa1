"""From the profiler's ``.xplane.pb`` to plain data, and the whole-round
reductions over it.

``read()`` turns the file into ``{"planes": [{"name", "lines": [{"name",
"events": [[name, start_ns, duration_ns], ...]}]}]}`` with nothing but
``jax.profiler.ProfileData``; everything below works on that plain form,
so the reductions are checked on a small recorded trace kept as JSON
(tests/data). Device planes are named ``/device:TPU:<n>``; their
``XLA Ops`` line holds one event per executed operation and their
``XLA Modules`` line one per executed program. Host threads are lines of
``/host:CPU``; the benchmark's own ``TraceAnnotation`` spans are the
events there whose names start with ``bench/``.
"""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
BENCH_SPAN = "bench/"


def read(path: str, keep_host=lambda name: name.startswith(BENCH_SPAN)) -> dict:
    """Device planes whole; of the host plane only the events
    ``keep_host`` accepts (the host plane of a served bus is large)."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = DEVICE_PLANE.match(plane.name)
        if not device and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events
                      if device or keep_host(e.name)]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace: dict) -> list[tuple[int, dict]]:
    out = []
    for p in trace["planes"]:
        m = DEVICE_PLANE.match(p["name"])
        if m:
            out.append((int(m.group(1)), p))
    return sorted(out, key=lambda x: x[0])


def line_events(plane: dict, line_name: str) -> list:
    for ln in plane["lines"]:
        if ln["name"] == line_name:
            return sorted(ln["events"], key=lambda e: e[1])
    return []


def union_ns(events, lo: float, hi: float) -> tuple[float, list]:
    """Length of the union of the events' intervals clipped to
    [lo, hi], and the gaps between them as (start, end)."""
    busy = 0.0
    gaps = []
    edge = lo
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        a, b = max(start, lo), min(start + dur, hi)
        if b <= a:
            continue
        if a > edge:
            gaps.append((edge, a))
            busy += b - a
            edge = b
        elif b > edge:
            busy += b - edge
            edge = b
    if hi > edge:
        gaps.append((edge, hi))
    return busy, gaps


def round_modules(plane: dict) -> list:
    """The executions of the round program on one device: the events of
    the ``XLA Modules`` line that carry the name taking most time there
    (a served bus runs one program over and over)."""
    mods = line_events(plane, MODULES_LINE)
    total: dict[str, float] = {}
    for name, _, dur in mods:
        total[name] = total.get(name, 0.0) + dur
    if not total:
        return []
    top = max(total, key=total.get)
    return [e for e in mods if e[0] == top]


def work_ops(plane: dict) -> list:
    """The ``XLA Ops`` events that are work. The trace also carries an
    event as long as the whole round program (on the v5e a zero-sized
    ``copy`` that opens with the program and closes with it); an
    operation cannot outlast the program that runs it, so an event of
    98 % of a round program's length or more is a wrapper, and counting
    it would report a device that is never idle."""
    mods = round_modules(plane)
    ops = line_events(plane, OPS_LINE)
    if not mods:
        return ops
    durations = sorted(m[2] for m in mods)
    limit = 0.98 * durations[len(durations) // 2]
    return [e for e in ops if e[2] < limit]


def whole_rounds_window(plane: dict):
    """(start_ns, end_ns, rounds): from the start of the second round
    program in the trace to the start of the last, which spans a whole
    number of round periods, idle time between them included. The first
    is left out: the profiler starts in the middle of a round and gives
    the program then running an event that begins with the trace, so
    counting from it made every round look shorter by that missing part
    (333-351 ms busy "per round" where the period was 358.6; my chip
    runs, PR 24)."""
    mods = round_modules(plane)
    if len(mods) < 3:
        return None
    return mods[1][1], mods[-1][1], len(mods) - 2


def host_round_period_ns(trace: dict):
    """Median time between the ends of consecutive ``bench/resolve``
    spans: the round period as the host sees it, on the trace's clock,
    to set beside the device's. None with fewer than two."""
    ends = sorted(start + dur for name, start, dur in host_spans(trace)
                  if name == BENCH_SPAN + "resolve")
    steps = sorted(b - a for a, b in zip(ends, ends[1:]))
    return steps[len(steps) // 2] if steps else None


def host_spans(trace: dict) -> list:
    """The benchmark's own spans, from every host thread."""
    out = []
    for p in trace["planes"]:
        if p["name"] != HOST_PLANE:
            continue
        for ln in p["lines"]:
            out += [e for e in ln["events"] if e[0].startswith(BENCH_SPAN)]
    return sorted(out, key=lambda e: e[1])


def attribute_gap(gap: tuple[float, float], spans: list) -> str:
    """The benchmark span that covers most of an idle gap; the spans
    nest at most one deep here, so the longest overlap names it."""
    a, b = gap
    best, best_len = "host:unattributed", 0.0
    for name, start, dur in spans:
        if start >= b:
            break
        overlap = min(b, start + dur) - max(a, start)
        if overlap > best_len:
            best, best_len = name, overlap
    return best


def short_name(name: str) -> str:
    """The trace prints a device op as its whole HLO line (``%fusion.188
    = u32[180224,1520]{...} fusion(...)``): keep the op's own name and
    the shape it produces."""
    head, eq, rest = name.partition(" = ")
    if not eq:
        return name[:96]
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{head.lstrip('%')} {shape}"[:96]


_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


def op_label(name: str) -> str:
    """``<the op's own name> <its opcode>``, e.g. ``psum.62 all-reduce``:
    what a metric's regular expression is matched against. The rest of
    the HLO line names the operands, and a fusion that consumes an
    all-reduce is not a collective."""
    head, eq, rest = name.partition(" = ")
    if not eq:
        return name
    m = _OPCODE.search(" " + rest)
    return f"{head.lstrip('%')} {m.group(1)}" if m else head.lstrip("%")


def top_by_name(events, lo: float, hi: float, n: int = 10) -> list:
    total: dict[str, float] = {}
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            name = short_name(name)
            total[name] = total.get(name, 0.0) + (b - a)
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in top]


def busy_summary(trace: dict) -> dict | None:
    """Per device: busy seconds, window seconds and whole rounds in the
    aligned window; over devices: the means, the operations that took
    most time on device 0 and the longest idle gaps there by the
    benchmark span that covers each."""
    per_device = []
    for idx, plane in device_planes(trace):
        win = whole_rounds_window(plane)
        if win is None:
            continue
        lo, hi, rounds = win
        ops = work_ops(plane)
        busy, gaps = union_ns(ops, lo, hi)
        per_device.append({"device": idx, "busy_ns": busy, "lo": lo,
                           "hi": hi, "rounds": rounds, "gaps": gaps,
                           "ops": ops, "programs": round_modules(plane)})
    if not per_device:
        return None
    n = len(per_device)
    d0 = per_device[0]
    spans = host_spans(trace)
    gap_total: dict[str, float] = {}
    for gap in d0["gaps"]:
        name = attribute_gap(gap, spans)
        gap_total[name] = gap_total.get(name, 0.0) + (gap[1] - gap[0])
    idle_gaps = [[k, v / 1e9] for k, v in
                 sorted(gap_total.items(), key=lambda kv: -kv[1])[:10]]
    mods = d0["programs"]
    host_period = host_round_period_ns(trace)
    return {
        "busy_s": sum(d["busy_ns"] for d in per_device) / n / 1e9,
        "window_s": sum(d["hi"] - d["lo"] for d in per_device) / n / 1e9,
        "rounds": d0["rounds"],
        # the trace's own round period, the host's on the same clock,
        # and the first and last program events (cut by the trace's ends)
        "period_ms": (d0["hi"] - d0["lo"]) / d0["rounds"] / 1e6,
        "host_period_ms": host_period / 1e6 if host_period else None,
        "programs": len(mods),
        "first_program_ms": mods[0][2] / 1e6,
        "last_program_ms": mods[-1][2] / 1e6,
        "busy_ms_per_round": sum(d["busy_ns"] / d["rounds"]
                                 for d in per_device) / n / 1e6,
        "per_device": [{"device": d["device"],
                        "busy_s": d["busy_ns"] / 1e9,
                        "window_s": (d["hi"] - d["lo"]) / 1e9,
                        "rounds": d["rounds"]} for d in per_device],
        "breakdown": {"device_ops": top_by_name(d0["ops"], d0["lo"], d0["hi"]),
                      "idle_gaps": idle_gaps},
    }


def matching_ms_per_round(trace: dict, pattern: str, device: int):
    """Summed duration of the device ops whose names match ``pattern``
    on one device, per whole round. None without such a device."""
    rx = re.compile(pattern)
    for idx, plane in device_planes(trace):
        if idx != device:
            continue
        win = whole_rounds_window(plane)
        if win is None:
            return None
        lo, hi, rounds = win
        total = 0.0
        for name, start, dur in work_ops(plane):
            if rx.search(op_label(name)):
                total += max(0.0, min(start + dur, hi) - max(start, lo))
        return total / rounds / 1e6
    return None
