"""Per-layer metrics of the split tier, from what the closed-loop driver
collected after the window (``drivers/grpc_closedloop.py``,
``observed["tier"]``): each frontend's ``/metrics`` (the stage counters
of ``server/service.py`` and the stub's counters of ``server/tier.py``),
the engine listener's counters in the server's registry, and the client
children's CPU seconds. All are totals over every op of the run, the
drain's few included. ``params``: ``quantity`` is

- ``loadgen_cpu_share``: the busiest child's CPU seconds (all its
  threads) between the go line and the window's end, over the window's
  seconds, in %: near 100 the generator, not the tier, is the limit;
- ``frontend_work_us``: ``grapevine_service_seconds_total{phase=open}``
  + ``{phase=seal}`` over ``grapevine_service_queries_total``, the mean
  over the frontends, in us per Query;
- ``frontend_wake_ms``: the same with ``{phase=wake}``, in ms: the
  engine's answers reached the frontend -> the handler thread ran again;
- ``engine_ingress_us``: ``grapevine_engine_ingress_seconds_total`` over
  ``grapevine_engine_submit_ops_total``, in us per op: what an op costs
  the engine's process from its message's entry to ``submit_many``
  returned;
- ``submit_batch_ops``: ``grapevine_engine_submit_ops_total`` over
  ``grapevine_engine_submit_batches_total``: ops a message.

Returns nothing where the driver collected no such account or the
program keeps no such counter (a program without the batched ingress)."""

from __future__ import annotations

import statistics


def _per_query(frontend: dict, phases) -> float | None:
    seconds = frontend.get("grapevine_service_seconds_total", {})
    queries = sum(frontend.get("grapevine_service_queries_total",
                               {}).values())
    if not queries or any(f'phase="{p}"' not in seconds for p in phases):
        return None
    return sum(seconds[f'phase="{p}"'] for p in phases) / queries


def read(params: dict, obs: dict):
    tier = obs["observed"].get("tier")
    if not tier:
        return None
    q = params["quantity"]
    if q == "loadgen_cpu_share":
        if not tier["children_cpu_s"]:
            return None
        return 100.0 * max(tier["children_cpu_s"]) / tier["window_s"]
    if q in ("frontend_work_us", "frontend_wake_ms"):
        phases, scale = ((("open", "seal"), 1e6) if q == "frontend_work_us"
                         else (("wake",), 1e3))
        means = [_per_query(f, phases) for f in tier["frontends"]]
        if not means or None in means:
            return None
        return statistics.fmean(means) * scale
    engine = tier["engine"]
    ops = engine.get("grapevine_engine_submit_ops_total")
    if not ops:
        return None
    if q == "engine_ingress_us":
        seconds = engine.get("grapevine_engine_ingress_seconds_total")
        return None if seconds is None else 1e6 * seconds / ops
    if q == "submit_batch_ops":
        return ops / engine["grapevine_engine_submit_batches_total"]
    raise ValueError(f"tier_stage reader: unknown quantity {q!r}")
