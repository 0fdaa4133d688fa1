"""Per-layer metrics from an unlabelled gauge of the program's own
registry (``server.metrics_registry``, what ``/metrics`` exports), read
after the run has been judged: the program refreshes its sampled gauges
when its health is read, and the judge reads it. ``params``: ``gauge``
names the gauge; ``over`` optionally names a second one to divide by
(``grapevine_hbm_peak_bytes`` over ``grapevine_hbm_limit_bytes``);
``scale`` multiplies the result (100 for a share in %). Nothing where
the server has no registry, the program keeps no such gauge (this
reader's parent) or the gauge carries labels. Where the divisor reads 0
the share reads 0: the program's memory gauges read 0 on a backend that
reports no memory statistics (a CPU rehearsal), as the harness's own
``memory_peak_bytes`` does there."""

from __future__ import annotations


def _plain(registry, name: str):
    gauge = registry.get(name) if registry is not None else None
    if not gauge or gauge.label_keys:
        return None
    return gauge.get()


def read(params: dict, obs: dict):
    registry = getattr(obs["ctx"].server, "metrics_registry", None)
    value = _plain(registry, params["gauge"])
    if value is None:
        return None
    if "over" in params:
        base = _plain(registry, params["over"])
        if base is None:
            return None
        value = value / base if base else 0.0
    return value * params.get("scale", 1.0)
