"""Per-layer metrics of the mesh's assembly of the fetched rows.
``params``: ``quantity`` is

- ``ici_roofline_pct``: the bytes a chip must receive to hold every
  fetched row it does not own (``lib/ici_bytes.py``, from the run's
  ``geometry`` and the stored row widths of the program's resolved
  configuration) over the chip's published inter-chip bandwidth, over
  the own time of the device ops whose scope path matches ``scope``
  (as the ``xplane_scope`` reader sums them, ``device`` 0 by default):
  the share of its roofline at which the assembly ran, bound by bytes
  received. Nothing on one chip, without a capture or a whole round, or
  where no op ran under the scope (a CPU rehearsal; a program with no
  such scope)."""

from __future__ import annotations

from ..lib import ici_bytes
from . import xplane_scope


def _stored_words(obs: dict) -> dict:
    """Each tree's stored value-row words, where the program's resolved
    configuration says them (a row kept on whole lane tiles)."""
    ecfg = getattr(getattr(obs["ctx"], "engine", None), "ecfg", None)
    out = {}
    for name, attr in (("records", "rec"), ("mailbox", "mb")):
        words = getattr(getattr(ecfg, attr, None), "stored_row_words", None)
        if words is not None:
            out[name] = int(words)
    return out


def read(params: dict, obs: dict):
    q = params["quantity"]
    if q != "ici_roofline_pct":
        raise ValueError(f"mesh_psum reader: unknown quantity {q!r}")
    geometry = obs["geometry"]
    if geometry["shards"] <= 1:
        return None
    ms = xplane_scope.read({"scope": params["scope"],
                            "device": params.get("device", 0)}, obs)
    if not ms:
        return None
    least = ici_bytes.least_received_bytes_per_chip(
        geometry, _stored_words(obs))
    floor_ms = least / (ici_bytes.peak_ici_gbps(obs["device_kind"]) * 1e9) * 1e3
    return 100.0 * floor_ms / ms
