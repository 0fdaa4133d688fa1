"""Per-layer metrics of the expiry sweep from the capture: the sweep is
a program of its own on the device (``jit_expiry_sweep`` on the ``XLA
Modules`` line), between two round programs, and one device runs one
program at a time, so what ran inside a sweep's module event is the
sweep's and nothing else is. The capture is ``lib/xplane_scopes.py``'s
plain form, as the ``xplane_scope`` reader keeps it for the run. Only
whole sweeps are read: from the second sweep program in the capture to
the last but one (the profiler starts and stops in the middle of
programs). ``params``: ``module`` is a regular expression over a module
event's name (default ``expiry_sweep``), ``device`` the device plane
(default 0), and ``quantity`` is

- ``device_ms``: the median length of a sweep's module event, ms;
- ``scope_ms``: the own time of the ops inside whole sweeps whose scope
  path matches ``scope`` (``obs/phases.py`` ``DEVICE_SCOPES``:
  ``grapevine/sweep_records``, ``grapevine/sweep_mailbox``), ms a sweep;
- ``wait_ms``: from the start of the benchmark's ``bench/sweep`` span
  (``expire`` called, inside the log's lock) to the start of the sweep's
  program on the device, the median, ms: the engine's lock, the rounds
  in flight that the sweep waits out, and the enqueue;
- ``hbm_roofline_pct``: the least bytes a sweep must move
  (``lib/sweep_bytes.py``, from the geometry the program resolved) over
  the published HBM peak (``lib/peaks.py``), over ``device_ms``: the
  share of its roofline at which the sweep ran, bound by bytes.

An op event as long as the program that holds it is a wrapper (``lib/
xplane.py`` ``work_ops``); here each program is measured against its
own length, because a capture of this cell holds two programs of
different lengths. Nothing without a capture, a device plane or three
sweep programs in it (a CPU rehearsal; a cell that does not sweep)."""

from __future__ import annotations

import bisect
import re
import statistics

from ..lib import peaks, sweep_bytes, xplane, xplane_scopes
from . import xplane_scope

#: an op this long relative to its program is the program's wrapper
WRAPPER = 0.98


def sweep_view(capture: dict, module: str = "expiry_sweep",
               device: int = 0):
    """{"sweeps": whole sweep modules [name, start, dur], "ops": the
    device's work ops sorted by start, "window": (lo, hi)}; None where
    the capture holds fewer than three sweep programs on that device."""
    rx = re.compile(module)
    for idx, plane in xplane.device_planes(capture):
        if idx != device:
            continue
        modules = xplane.line_events(plane, xplane.MODULES_LINE)
        sweeps = [m for m in modules if rx.search(m[0])]
        if len(sweeps) < 3:
            return None
        starts = [m[1] for m in modules]
        ops = []
        for e in xplane.line_events(plane, xplane.OPS_LINE):
            # the program an op ran in: the last one to start at or
            # before it
            i = bisect.bisect_right(starts, e[1]) - 1
            if i >= 0 and e[2] >= WRAPPER * modules[i][2] > 0:
                continue
            ops.append(e)
        return {"sweeps": sweeps[1:-1], "ops": ops,
                "window": (sweeps[1][1], sweeps[-1][1])}
    return None


def _inside(ops, intervals):
    """The ops that start inside one of the disjoint ``intervals``."""
    starts = [a for a, _ in intervals]
    out = []
    for e in ops:
        i = bisect.bisect_right(starts, e[1]) - 1
        if i >= 0 and e[1] < intervals[i][1]:
            out.append(e)
    return out


def _view(params: dict, obs: dict):
    if obs.get("trace") is None:
        return None
    cap = xplane_scope.capture(obs)
    if cap is None:
        return None
    key = ("_sweep_view", params.get("module", "expiry_sweep"),
           params.get("device", 0))
    if key not in obs:
        view = obs[key] = sweep_view(cap, key[1], key[2])
        if view is not None:
            # the benchmark's own span around each ``expire`` call
            view["calls"] = [e for e in xplane.host_spans(obs["trace"])
                             if e[0] == xplane.BENCH_SPAN + "sweep"]
    return obs[key]


def quantity(params: dict, view: dict, capture: dict):
    """``params['quantity']`` over a :func:`sweep_view` (``hbm_roofline_
    pct`` apart: it needs the run's geometry)."""
    q = params["quantity"]
    sweeps = view["sweeps"]
    if q == "device_ms":
        return statistics.median(m[2] for m in sweeps) / 1e6
    if q == "wait_ms":
        calls = sorted(e[1] for e in view["calls"])
        waits = []
        for _, start, _ in sweeps:
            i = bisect.bisect_right(calls, start) - 1
            if i >= 0:
                waits.append(start - calls[i])
        return statistics.median(waits) / 1e6 if waits else None
    if q == "scope_ms":
        rx = re.compile(params["scope"])
        paths = capture["scope_paths"]
        ops = _inside(view["ops"], [(a, a + d) for _, a, d in sweeps])
        lo, hi = view["window"]
        own = xplane_scopes.own_time(ops, lo, hi)
        total = sum(ns for e, ns in zip(ops, own)
                    if e[3] >= 0 and rx.search(paths[e[3]]))
        return total / len(sweeps) / 1e6
    raise ValueError(f"xplane_sweep reader: unknown quantity {q!r}")


def read(params: dict, obs: dict):
    view = _view(params, obs)
    if view is None:
        return None
    if params["quantity"] == "hbm_roofline_pct":
        ctx = obs["ctx"]
        least = sweep_bytes.least_sweep_bytes_per_chip(
            sweep_bytes.sweep_geometry(ctx.engine.ecfg, obs["shards"]))
        floor_ms = least / (peaks.peak_hbm_gbps(obs["device_kind"]) * 1e9) * 1e3
        return 100.0 * floor_ms / quantity(dict(params, quantity="device_ms"),
                                           view, None)
    return quantity(params, view, xplane_scope.capture(obs))
