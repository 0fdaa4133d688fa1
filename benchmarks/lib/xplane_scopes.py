"""The capture reduced by the program's own names: device-op time per
``grapevine/`` scope path, and idle gaps against the program's host
spans.

``lib/xplane.py`` reads a capture through ``jax.profiler.ProfileData``,
which shows an event's own stats only. The scope path an op was traced
under (``jax.named_scope``; ``obs/phases.py`` ``DEVICE_SCOPES``) is the
HLO ``op_name``, and on jax 0.9 / TPU v5e the profiler keeps it in the
``tf_op`` stat of the event's *metadata* (found on the chip, PR 25:
``jit(engine_round_step)/grapevine/round_b_records/grapevine/oram_fetch/
gather:``). So this module reads the ``.xplane.pb`` itself, with a
protobuf wire reader of its own (XSpace > XPlane > XLine > XEvent, and
the plane's event and stat metadata maps; tsl/profiler/protobuf/
xplane.proto). One op in nine by time carries no ``tf_op`` there: ops
the compiler made (layout copies, the cipher's keystream loop fused
into ``add_dynamic-update-slice_fusion``; 38.4 of 358.6 ms a round on
the parent's capture, my chip run, PR 25). For those the path comes
from the compiled program the capture itself holds (the ``Hlo Proto``
of the ``/host:metadata`` plane, xla/service/hlo.proto): a fusion takes
the scope most of the instructions fused into it were traced under, any
other op its first operand's. What is kept:

    {"scope_paths": [path, ...],
     "planes": [{"name", "lines": [{"name", "events":
         [[name, start_ns, duration_ns, path index or -1], ...]}]}],
     "host_spans": [[name, start_ns, duration_ns, thread], ...]}

``planes`` holds the device planes in ``lib/xplane.py``'s plain form
with one more field per ``XLA Ops`` event, so its ``whole_rounds_window``
and ``work_ops`` apply unchanged; ``host_spans`` holds the host plane's
``grapevine/*`` events (the program's ``TraceAnnotation`` spans) with
the thread each ran on. Everything below works on that plain form, so
it is checked on a small recorded capture kept as JSON (tests/data).
"""

from __future__ import annotations

import glob
import os
import re

from . import xplane

PROGRAM_SPAN = "grapevine/"
SCOPE_STAT = "tf_op"
HLO_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
#: an op_name up to its innermost ``grapevine/<scope>``
SCOPE_PREFIX = re.compile(r"^(.*grapevine/[A-Za-z0-9_]+)")
#: a scope path that names no ``grapevine/`` scope (the empty one too)
UNSCOPED = r"^(?!.*grapevine/)"
_SCOPE_NAME = re.compile(r"grapevine/([A-Za-z0-9_]+)")


# -- the protobuf wire format, as far as an XSpace needs it --------------

def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        if byte < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields are
    skipped (no field read here is one)."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
            continue
        elif wire == 5:
            i += 4
            continue
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {i}")
        yield tag >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(buf):
    key = value = None
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _stat_names(plane_buf) -> dict[int, str]:
    names = {}
    for f, v in _fields(plane_buf):
        if f == 5:  # map<int64, XStatMetadata>
            key, meta = _map_entry(v)
            for mf, mv in _fields(meta):
                if mf == 2:
                    names[key] = _text(mv)
    return names


def _event_names(plane_buf, stat_names: dict[int, str]):
    """metadata id -> (name, scope path or None)."""
    scope_ids = {k for k, n in stat_names.items() if n == SCOPE_STAT}
    out = {}
    for f, v in _fields(plane_buf):
        if f != 4:  # map<int64, XEventMetadata>
            continue
        key, meta = _map_entry(v)
        name, path = "", None
        for mf, mv in _fields(meta):
            if mf == 2:
                name = _text(mv)
            elif mf == 5:  # XStat
                stat_id = text = ref = None
                for sf, sv in _fields(mv):
                    if sf == 1:
                        stat_id = sv
                    elif sf == 5:
                        text = sv
                    elif sf == 7:
                        ref = sv
                if stat_id in scope_ids:
                    path = (_text(text) if text is not None
                            else stat_names.get(ref))
        out[key] = (name, path)
    return out


def _packed(value):
    """A repeated int64 field's values: one varint, or a packed run."""
    if isinstance(value, int):
        yield value
        return
    i = 0
    while i < len(value):
        v, i = _varint(value, i)
        yield v


def _hlo_module(plane_buf):
    """The serialized HloProto a ``/host:metadata`` plane holds for the
    program that took most of the capture (its largest), or None."""
    stat_names = _stat_names(plane_buf)
    best = None
    for f, v in _fields(plane_buf):
        if f != 4:
            continue
        _, meta = _map_entry(v)
        for mf, mv in _fields(meta):
            if mf != 5:
                continue
            stat_id = blob = None
            for sf, sv in _fields(mv):
                if sf == 1:
                    stat_id = sv
                elif sf == 6:
                    blob = sv
            if (stat_names.get(stat_id) == HLO_STAT and blob is not None
                    and (best is None or len(blob) > len(best))):
                best = blob
    return best


def hlo_scope_resolver(hlo_proto):
    """instruction name -> scope path ("" when nothing names one), from
    the compiled program: the instruction's own ``op_name``; for one the
    compiler made without it, the scope most instructions of the
    computations it calls (a fusion's body) were traced under; else its
    first operand's, and so on up the operands."""
    by_name: dict[str, int] = {}
    insts: dict[int, tuple] = {}  # id -> (op_name, operand ids, called ids)
    bodies: dict[int, list[int]] = {}  # computation id -> instruction ids
    module = next((v for f, v in _fields(hlo_proto) if f == 1), None)
    for f, comp in (_fields(module) if module is not None else ()):
        if f != 3:  # HloComputationProto
            continue
        comp_id, members = None, []
        for cf, cv in _fields(comp):
            if cf == 5:
                comp_id = cv
            elif cf == 2:  # HloInstructionProto
                name, op_name, inst_id, operands, called = "", "", None, [], []
                for jf, jv in _fields(cv):
                    if jf == 1:
                        name = _text(jv)
                    elif jf == 7:  # OpMetadata
                        for mf, mv in _fields(jv):
                            if mf == 2:
                                op_name = _text(mv)
                    elif jf == 35:
                        inst_id = jv
                    elif jf == 36:
                        operands.extend(_packed(jv))
                    elif jf == 38:
                        called.extend(_packed(jv))
                insts[inst_id] = (op_name, operands, called)
                by_name[name] = inst_id
                members.append(inst_id)
        bodies[comp_id] = members
    memo: dict[int, str] = {}

    def prefix(op_name: str) -> str:
        m = SCOPE_PREFIX.match(op_name)
        return m.group(1) if m else op_name

    def resolve(inst_id: int, depth: int = 0) -> str:
        if inst_id in memo:
            return memo[inst_id]
        op_name, operands, called = insts.get(inst_id, ("", [], []))
        path = op_name
        if not path and depth < 64:
            votes: dict[str, int] = {}
            for comp_id in called:
                for member in bodies.get(comp_id, ()):
                    inner = prefix(resolve(member, depth + 1))
                    if PROGRAM_SPAN in inner:
                        votes[inner] = votes.get(inner, 0) + 1
            if votes:
                path = max(votes, key=votes.get)
            elif operands:
                path = prefix(resolve(operands[0], depth + 1))
        memo[inst_id] = path
        return path

    def by_event_name(event_name: str) -> str:
        inst = by_name.get(event_name.partition(" = ")[0].lstrip("%"))
        return resolve(inst) if inst is not None else ""

    return by_event_name


def _line(buf):
    """(name, timestamp_ns, [(metadata id, offset_ps, duration_ps)])."""
    name, display, t0, events = "", "", 0, []
    for f, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 11:
            display = _text(v)
        elif f == 3:
            t0 = v
        elif f == 4:
            meta = offset = dur = 0
            for ef, ev in _fields(v):
                if ef == 1:
                    meta = ev
                elif ef == 2:
                    offset = ev
                elif ef == 3:
                    dur = ev
            events.append((meta, offset, dur))
    return name or display, t0, events


def read(path: str) -> dict:
    """The capture as plain data (module docstring)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    paths: dict[str, int] = {}
    planes, host_spans = [], []
    plane_bufs = []
    for f, plane_buf in _fields(space):
        if f == 1:
            name = next((_text(v) for pf, v in _fields(plane_buf) if pf == 2),
                        "")
            plane_bufs.append((name, plane_buf))
    from_hlo = None  # built when the first op without a path is met

    def compiled_scope(event_name: str) -> str:
        nonlocal from_hlo
        if from_hlo is None:
            blob = next((b for b in (_hlo_module(buf) for n, buf in plane_bufs
                                     if n == HLO_PLANE) if b is not None),
                        None)
            from_hlo = (hlo_scope_resolver(blob) if blob is not None
                        else lambda _name: "")
        return from_hlo(event_name)

    for plane_name, plane_buf in plane_bufs:
        device = xplane.DEVICE_PLANE.match(plane_name)
        if not device and plane_name != xplane.HOST_PLANE:
            continue
        names = _event_names(plane_buf, _stat_names(plane_buf))
        if device:
            names = {m: (name, compiled_scope(name) if path is None else path)
                     for m, (name, path) in names.items()}
        lines = []
        for pf, pv in _fields(plane_buf):
            if pf != 3:
                continue
            line_name, t0, raw = _line(pv)
            if not device:
                host_spans += [
                    [names[m][0], t0 + off / 1e3, dur / 1e3, line_name]
                    for m, off, dur in raw
                    if names.get(m, ("",))[0].startswith(PROGRAM_SPAN)]
                continue
            if line_name not in (xplane.OPS_LINE, xplane.MODULES_LINE):
                continue
            events = []
            for m, off, dur in raw:
                name, scope = names.get(m, ("", None))
                event = [name, t0 + off / 1e3, dur / 1e3]
                if line_name == xplane.OPS_LINE:
                    event.append(paths.setdefault(scope, len(paths))
                                 if scope else -1)
                events.append(event)
            lines.append({"name": line_name, "events": events})
        if device:
            planes.append({"name": plane_name, "lines": lines})
    return {"scope_paths": list(paths), "planes": planes,
            "host_spans": sorted(host_spans, key=lambda e: e[1])}


def capture_file(scratch: str):
    """The newest ``.xplane.pb`` under ``<scratch>/trace``, or None."""
    files = glob.glob(os.path.join(
        scratch, "trace", "plugins", "profile", "*", "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


# -- reductions over the plain form --------------------------------------

def own_time(events, lo: float, hi: float) -> list[float]:
    """Each event's own nanoseconds inside [lo, hi]: an instant belongs
    to the event that started last among those running then (a ``while``
    event holds its body's ops, and gets what they leave), so the
    values add up to the union of the intervals exactly. ``events`` are
    sorted by start; returns one value per event, in that order."""
    own = [0.0] * len(events)
    stack: list[tuple[int, float]] = []  # (event index, its end)
    t = lo

    def advance(to: float):
        nonlocal t
        to = min(to, hi)
        while stack and t < to:
            idx, end = stack[-1]
            if end <= t:
                stack.pop()
                continue
            step = min(end, to)
            own[idx] += step - t
            t = step
        t = max(t, to)

    for i, e in enumerate(events):
        start, end = e[1], e[1] + e[2]
        if end <= lo or start >= hi:
            continue
        advance(start)
        stack.append((i, end))
    advance(hi)
    return own


def device_window(capture: dict, device: int = 0):
    """(ops sorted by start, lo, hi, rounds) of one device's whole-round
    window, wrapper events left out; None without such a window."""
    for idx, plane in xplane.device_planes(capture):
        if idx != device:
            continue
        win = xplane.whole_rounds_window(plane)
        if win is None:
            return None
        lo, hi, rounds = win
        return xplane.work_ops(plane), lo, hi, rounds
    return None


def scope_table(capture: dict, device: int = 0):
    """{scope path ("" = none): own ms per round} and the rounds."""
    win = device_window(capture, device)
    if win is None:
        return None
    ops, lo, hi, rounds = win
    paths = capture["scope_paths"]
    total: dict[str, float] = {}
    for e, ns in zip(ops, own_time(ops, lo, hi)):
        if ns:
            path = paths[e[3]] if e[3] >= 0 else ""
            total[path] = total.get(path, 0.0) + ns
    return {p: ns / rounds / 1e6 for p, ns in total.items()}, rounds


def scope_chain(path: str) -> str:
    """A scope path's ``grapevine/`` names in order, repeats folded,
    joined by ``/``: ``round_a_mailbox/oram_apply``; "" for none."""
    names: list[str] = []
    for name in _SCOPE_NAME.findall(path):
        if name not in names:
            names.append(name)
    return "/".join(names)


def op_scope_chains(capture: dict, device: int = 0) -> dict[str, str]:
    """{``xplane.short_name`` of a device op: its scope chain}: what
    lets the ten longest ops of a ``breakdown`` be read without the
    capture. An op's name is its own in the compiled program, so it
    has one path."""
    for idx, plane in xplane.device_planes(capture):
        if idx == device:
            paths = capture["scope_paths"]
            return {xplane.short_name(e[0]):
                    scope_chain(paths[e[3]]) if e[3] >= 0 else ""
                    for e in xplane.line_events(plane, xplane.OPS_LINE)}
    return {}


def idle_unattributed_ms(capture: dict, device: int = 0):
    """Idle-gap time that no ``grapevine/*`` host span covers, ms per
    round: what the program's own spans cannot explain."""
    win = device_window(capture, device)
    if win is None:
        return None
    ops, lo, hi, rounds = win
    _, gaps = xplane.union_ns([e[:3] for e in ops], lo, hi)
    spans = [e[:3] for e in capture["host_spans"]]
    bare = 0.0
    for a, b in gaps:
        covered, _ = xplane.union_ns(spans, a, b)
        bare += (b - a) - covered
    return bare / rounds / 1e6
