"""Per-layer metrics of one of the round's two ORAM trees.
``params``: ``quantity`` is

- ``hbm_roofline_pct``: the least bytes a round must move in ``tree``
  (``records`` or ``mailbox`` of the run's ``geometry``;
  ``lib/round_bytes.py`` ``least_round_bytes_per_chip`` of that tree
  alone, so the written bytes are taken over the shards) over the
  published HBM peak (``lib/peaks.py``), over the own time of the
  device ops whose scope path matches ``scope`` (the tree's rounds, as
  the ``xplane_scope`` reader sums them): the share of its roofline at
  which the tree's rounds ran, bound by bytes. Nothing without a
  capture, a whole round, or an op under the scope (a CPU rehearsal);
- ``stash_peak``: the program's ``grapevine_stash_high_water`` gauge
  under ``tree`` = ``label`` (``rec``, ``mb``), read from the server's
  registry after the run: the largest stash occupancy the program
  sampled. It samples at health and scrape cadence, never per round,
  so in a benchmark run that is the occupancy the drained rounds left
  behind, sampled when the run is judged. Nothing where the program
  keeps no such gauge or keeps one gauge over both trees."""

from __future__ import annotations

from ..lib import peaks, round_bytes
from . import xplane_scope


def read(params: dict, obs: dict):
    q = params["quantity"]
    if q == "hbm_roofline_pct":
        ms = xplane_scope.read({"scope": params["scope"],
                                "device": params.get("device", 0)}, obs)
        if not ms:
            return None
        tree, geometry = params["tree"], obs["geometry"]
        least = round_bytes.least_round_bytes_per_chip(
            {"shards": geometry["shards"],
             "trees": {tree: geometry["trees"][tree]}})
        floor_ms = least / (peaks.peak_hbm_gbps(obs["device_kind"]) * 1e9) * 1e3
        return 100.0 * floor_ms / ms
    if q == "stash_peak":
        registry = getattr(obs["ctx"].server, "metrics_registry", None)
        gauge = registry and registry.get("grapevine_stash_high_water")
        if not gauge or "tree" not in gauge.label_keys:
            return None
        return gauge.get(tree=params["label"])
    raise ValueError(f"oram_tree reader: unknown quantity {q!r}")
