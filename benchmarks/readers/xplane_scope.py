"""Per-layer metrics from the capture reduced by the program's own
names (``lib/xplane_scopes.py``): the device scopes compiled into the
round (``obs/phases.py`` ``DEVICE_SCOPES``) and the ``grapevine/*`` host
spans. The capture is read from ``<scratch>/trace``, still on disk while
readers run. ``params``: ``quantity`` is

- ``scope`` (the default): ``scope`` is a regular expression searched in
  each device op's scope path (the HLO ``op_name``, e.g. ``jit(engine_
  round_step)/grapevine/round_a_mailbox/grapevine/oram_fetch/gather``;
  an op with no path has the empty one); the ops' own time, ms per
  whole round;
- ``unscoped``: ops whose path names no ``grapevine/`` scope;
- ``idle_unattributed``: idle-gap time no ``grapevine/*`` host span
  covers;

each on ``device`` (index, default 0), per whole round. Returns nothing
without a capture, a device plane or a whole round (a CPU rehearsal).
The first read says the whole table once, as a ``scopes`` line."""

from __future__ import annotations

import re

from ..lib import xplane_scopes


def capture(obs: dict):
    """The run's capture as ``lib/xplane_scopes.py`` reads it, once a
    run; None without one."""
    if "_scopes" not in obs:
        path = xplane_scopes.capture_file(obs["ctx"].scratch)
        obs["_scopes"] = xplane_scopes.read(path) if path else None
    return obs["_scopes"]


def _table(obs: dict, device: int):
    """({scope path: ms per round}, rounds) of one device, reduced once
    a run; the first reduction of device 0 says the table."""
    cache = obs.setdefault("_scope_tables", {})
    if device not in cache:
        cache[device] = xplane_scopes.scope_table(capture(obs), device)
        if device == 0 and cache[device] is not None:
            _say_table(obs, cache[device])
    return cache[device]


def _say_table(obs: dict, table) -> None:
    """One line with ms per round by chain of scope names (repeats
    folded), largest first: PERF.md's scope table."""
    by_chain: dict[str, float] = {}
    for path, ms in table[0].items():
        chain = xplane_scopes.scope_chain(path) or "(no scope)"
        by_chain[chain] = by_chain.get(chain, 0.0) + ms
    top = sorted(by_chain.items(), key=lambda kv: -kv[1])
    spans: dict[str, int] = {}
    for name, _, _, thread in obs["_scopes"]["host_spans"]:
        spans[f"{name}@{thread}"] = spans.get(f"{name}@{thread}", 0) + 1
    obs["ctx"].say(phase="scopes", rounds=table[1],
                   total_ms_per_round=sum(by_chain.values()),
                   ms_per_round=[[k, round(v, 4)] for k, v in top[:48]],
                   host_spans=spans)


def read(params: dict, obs: dict):
    if obs.get("trace") is None:
        return None
    cap = capture(obs)
    if cap is None:
        return None
    device = params.get("device", 0)
    q = params.get("quantity", "scope")
    if q in ("scope", "unscoped"):
        table = _table(obs, device)
        if table is None:
            return None
        rx = re.compile(params["scope"] if q == "scope"
                        else xplane_scopes.UNSCOPED)
        return sum(ms for path, ms in table[0].items() if rx.search(path))
    if q == "idle_unattributed":
        return xplane_scopes.idle_unattributed_ms(cap, device)
    raise ValueError(f"xplane_scope reader: unknown quantity {q!r}")
