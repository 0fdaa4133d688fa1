"""Per-layer metrics from the capture: the device's idle gaps by what
the program's collector thread was doing meanwhile.

The collector takes every span with one primitive (``obs/phases.py``
``span``), so on its thread the ``grapevine/*`` ``TraceAnnotation``
events nest, and each instant belongs to the innermost one open then:
``grapevine/cycle`` holds them all, ``verify`` holds ``verify_prep`` and
``verify_native``, ``dispatch`` holds ``journal``. Annotations and
device ops are in one capture on the profiler's clock, so no clock is
matched by hand. Over the plain form ``lib/xplane_scopes.py`` keeps
(``host_spans`` with the thread of each):

- the collector's thread is the one that carries ``grapevine/cycle``.
  The plain form names a thread by its line, the line has the thread's
  OS name, and the program gives the collector one of its own
  (``obs/phases.py`` ``COLLECTOR_THREAD``; every other thread of the
  process is ``python3``), so another thread's spans (a handler's
  ``ingress``, the expiry timer's ``sweep`` with a ``checkpoint``
  inside) are never read;
- for each idle gap of device 0 inside the whole-rounds window, the
  gap's time goes to the innermost ``grapevine/*`` span open on that
  thread, or to no span;
- ``params.spans`` names the spans to sum; the result is ms per whole
  round.

One thread's spans nest. Where two of the thread overlap without one
holding the other, a second thread's spans have come under the
collector's name and the account would be wrong without a sign of it:
that raises. Returns nothing without a capture, a device plane, a whole
round (a CPU rehearsal) or a ``cycle`` span (a program that keeps no
account of its collector). The first read says the whole table once, as
an ``idle_by_span`` line: every span's share, ``cycle`` being what the
cycle's own time covers (no span inside it), ``asleep`` the collector
with nothing queued and nothing in flight, and ``(no span)`` what its
thread leaves bare."""

from __future__ import annotations

from ..lib import xplane, xplane_scopes
from . import xplane_scope

CYCLE = xplane_scopes.PROGRAM_SPAN + "cycle"
NO_SPAN = "(no span)"
#: how far a span may end after its parent, or after the next of its
#: thread has started, ns: the capture's stamps are whole ns, and the
#: plain form keeps an event's start and length as two floats
ROUNDING_NS = 1.0


def innermost_segments(spans) -> list[tuple[float, float, str]]:
    """One thread's nested ``[name, start, duration, ...]`` events as
    disjoint ``(start, end, name)`` pieces, in time order: each instant
    under the event that started last among those open then. Within
    ``ROUNDING_NS`` a span that ends as the next starts has ended, and a
    child that ends after its parent is cut at the parent's end; one
    that ends later still is no child of it, and no span of the same
    thread: ValueError."""
    out: list[tuple[float, float, str]] = []
    stack: list[tuple[str, float]] = []  # (name, end), outermost first
    t = 0.0

    def advance(to: float) -> None:
        """Close what ends by ``to`` and give the time up to it."""
        nonlocal t
        while stack:
            name, end = stack[-1]
            if end > to:
                if to > t:
                    out.append((t, to, name))
                    t = to
                return
            if end > t:
                out.append((t, end, name))
                t = end
            stack.pop()

    for e in sorted(spans, key=lambda e: (e[1], -e[2])):
        name, start, end = e[0], e[1], e[1] + e[2]
        advance(start)
        while stack and stack[-1][1] <= start + ROUNDING_NS:
            advance(stack[-1][1])  # a sibling that ends as this starts
        t = max(t, start)
        if stack:
            holder, holder_end = stack[-1]
            if end > holder_end + ROUNDING_NS:
                raise ValueError(
                    f"{name} [{start}, {end}) starts inside {holder} and "
                    f"ends {end - holder_end} ns after it: two threads' "
                    "spans on one line")
            end = min(end, holder_end)
        if end > t:
            stack.append((name, end))
    advance(float("inf"))
    return out


def by_span(gaps, segments) -> dict[str, float]:
    """{span name or ``NO_SPAN``: time of ``gaps`` under it}; ``gaps``
    and ``segments`` are disjoint and in time order."""
    total: dict[str, float] = {}
    i = 0
    for a, b in gaps:
        covered = 0.0
        while i < len(segments) and segments[i][1] <= a:
            i += 1
        j = i
        while j < len(segments) and segments[j][0] < b:
            s0, s1, name = segments[j]
            piece = min(s1, b) - max(s0, a)
            if piece > 0:
                total[name] = total.get(name, 0.0) + piece
                covered += piece
            j += 1
        total[NO_SPAN] = total.get(NO_SPAN, 0.0) + (b - a) - covered
    return total


def idle_table(capture: dict):
    """({span: idle ms per round}, rounds) of device 0 against the
    collector's thread; None where there is nothing to read."""
    win = xplane_scopes.device_window(capture)
    if win is None:
        return None
    ops, lo, hi, rounds = win
    thread = next((e[3] for e in capture["host_spans"] if e[0] == CYCLE),
                  None)
    if thread is None:
        return None
    _, gaps = xplane.union_ns([e[:3] for e in ops], lo, hi)
    segments = innermost_segments(
        [e for e in capture["host_spans"] if e[3] == thread])
    return ({name: ns / rounds / 1e6
             for name, ns in by_span(gaps, segments).items()}, rounds)


def _table(obs: dict):
    if "_idle_table" not in obs:
        found = obs["_idle_table"] = idle_table(xplane_scope.capture(obs))
        if found is not None:
            table, rounds = found
            obs["ctx"].say(
                phase="idle_by_span", rounds=rounds,
                idle_ms_per_round=sum(table.values()),
                ms_per_round=[[k.removeprefix(xplane_scopes.PROGRAM_SPAN),
                               round(v, 4)]
                              for k, v in sorted(table.items(),
                                                 key=lambda kv: -kv[1])])
    return obs["_idle_table"]


def read(params: dict, obs: dict):
    if obs.get("trace") is None or xplane_scope.capture(obs) is None:
        return None
    found = _table(obs)
    if found is None:
        return None
    return sum(found[0].get(xplane_scopes.PROGRAM_SPAN + s, 0.0)
               for s in params["spans"])
