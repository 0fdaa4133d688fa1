"""A per-layer metric from the device ops that match a regular
expression: their summed duration on one device, per whole round, in
ms. ``params``: ``match`` (matched against each op's own name and
opcode, ``psum.62 all-reduce``, not against its operands), ``device``
(index, default 0). Returns nothing when no op matches (a one-chip run
has no collectives)."""

from __future__ import annotations

from ..lib import xplane


def read(params: dict, obs: dict):
    if obs.get("trace") is None:
        return None
    ms = xplane.matching_ms_per_round(
        obs["trace"], params["match"], params.get("device", 0))
    return ms if ms else None
