"""Per-layer metrics from the device trace as a whole: how long the
device was busy per round, and what share of that the least bytes of a
round would need at the published HBM peak. ``params``: ``quantity`` is
``busy_ms_per_round`` or ``hbm_roofline_pct``."""

from __future__ import annotations

from ..lib import peaks, round_bytes, xplane, xplane_scopes
from . import xplane_scope


def device_busy(obs: dict):
    """The harness's own reading of the same trace: ``busy_s`` and
    ``window_s`` for the result's ``device``, the ``breakdown``, and a
    summary line. None without a trace."""
    if obs.get("trace") is None:
        return None
    if "_busy" not in obs:
        obs["_busy"] = xplane.busy_summary(obs["trace"])
    s = obs["_busy"]
    if s is None:
        return None
    return {"busy_s": s["busy_s"], "window_s": s["window_s"],
            "breakdown": dict(s["breakdown"], device_ops=_named_by_scope(
                obs, s["breakdown"]["device_ops"])),
            "summary": {"rounds_in_trace": s["rounds"],
                        "trace_period_ms": s["period_ms"],
                        "host_period_ms_on_trace_clock": s["host_period_ms"],
                        "programs_in_trace": s["programs"],
                        "first_program_ms": s["first_program_ms"],
                        "last_program_ms": s["last_program_ms"],
                        "idle_share": 1.0 - s["busy_s"] / s["window_s"],
                        "busy_ms_per_round": s["busy_ms_per_round"],
                        "per_device": s["per_device"]}}


def _named_by_scope(obs: dict, device_ops: list) -> list:
    """Each op of the breakdown with the chain of program scopes it ran
    under in front (``round_a_mailbox/oram_apply fusion.106
    s32[507904]``), from the capture on disk; as it is without one."""
    cap = xplane_scope.capture(obs) if "ctx" in obs else None
    if cap is None:
        return device_ops
    chains = xplane_scopes.op_scope_chains(cap)
    return [[f"{chains[name]} {name}"[:96] if chains.get(name) else name,
             seconds] for name, seconds in device_ops]


def read(params: dict, obs: dict):
    busy = device_busy(obs)
    if busy is None:
        return None
    ms = busy["summary"]["busy_ms_per_round"]
    q = params["quantity"]
    if q == "busy_ms_per_round":
        return ms
    if q == "hbm_roofline_pct":
        least = round_bytes.least_round_bytes_per_chip(obs["geometry"])
        floor_ms = least / (peaks.peak_hbm_gbps(obs["device_kind"]) * 1e9) * 1e3
        return 100.0 * floor_ms / ms
    raise ValueError(f"xplane_busy reader: unknown quantity {q!r}")
