"""Per-layer metrics from the server's own account of a Query's stages
(``server/service.py``: ``grapevine_service_seconds_total{phase=open|
wait|wake|seal}`` over ``grapevine_service_queries_total``), read from
the server's registry after the run. They are totals over every Query
served: set-up sends none over gRPC, the drain's few are in.
``params``: ``phases`` are summed; ``scale`` multiplies the mean seconds
per Query (1e3 for ms, 1e6 for us). Returns nothing when the program
keeps no such counters or served no Query (a cell fed in-process)."""

from __future__ import annotations


def read(params: dict, obs: dict):
    registry = getattr(obs["ctx"].server, "metrics_registry", None)
    if registry is None:
        return None
    seconds = registry.get("grapevine_service_seconds_total")
    queries = registry.get("grapevine_service_queries_total")
    if seconds is None or queries is None or not queries.get():
        return None
    total = sum(seconds.get(phase=p) for p in params["phases"])
    return total / queries.get() * params.get("scale", 1.0)
