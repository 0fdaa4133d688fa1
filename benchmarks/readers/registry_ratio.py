"""Per-layer metrics that are a sum of series of the program's own
registry (``server.metrics_registry``, what ``/metrics`` exports) over
another such sum, read after the run has been judged: what
``registry_gauge`` cannot say, a family with labels, a histogram's sum
and count, a divisor of two counters added.

``params``: ``num`` and ``den`` are lists of series; ``scale``
multiplies the quotient. A series is ``{"name": family}`` and,
optionally, ``"labels"``: a mapping that picks one child of a labelled
family (without it every child is added up), and ``"of"``: ``"sum"`` or
``"count"`` of a histogram's child (a counter's or gauge's child has one
value). Nothing where the server has no registry, the program keeps no
such family or no such child (this reader's parent), or the divisor
reads 0 (nothing was counted: no round audited, no frame replayed, no
sweep journaled)."""

from __future__ import annotations


def _total(registry, series: dict):
    family = registry.get(series["name"])
    if family is None:
        return None
    want = series.get("labels")
    total = 0.0
    found = False
    for values, child in family.series():
        labels = dict(zip(family.label_keys, values))
        if want is not None and any(labels.get(k) != str(v)
                                    for k, v in want.items()):
            continue
        found = True
        if hasattr(child, "state"):  # a histogram: (buckets, sum, count)
            _, h_sum, h_count = child.state()
            total += h_sum if series.get("of", "sum") == "sum" else h_count
        else:
            total += child.value
    return total if found else None


def read(params: dict, obs: dict):
    registry = getattr(obs["ctx"].server, "metrics_registry", None)
    if registry is None:
        return None
    sums = []
    for part in ("num", "den"):
        totals = [_total(registry, s) for s in params[part]]
        if any(t is None for t in totals):
            return None
        sums.append(sum(totals))
    num, den = sums
    if not den:
        return None
    return num / den * params.get("scale", 1.0)
