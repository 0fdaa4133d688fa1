"""Cost observatory: the static round-cost ledger on /metrics, plus
the runtime roofline residual.

Two halves, both riding :mod:`..analysis.costmodel` (the bit-exact
cross-validated model — see tools/check_cost_model.py for the gate):

- **startup info gauges** (``grapevine_cost_*``): the modeled per-phase
  HBM bytes / gather-scatter rows / cipher rows / sort key-volume and
  the steady-state round total, set once at attach
  time. Pure functions of public geometry × knobs — the same numbers
  any observer could derive from the config — so they are trivially
  leak-free (tools/check_telemetry_policy.py audits the namespace:
  ``phase`` is the only label key, and label *values* are the fixed
  phase names, never geometry).
- **roofline residual** (runtime): each resolved round pairs the
  tracer's ``device`` span — the round's own device time as the host
  can know it (obs/tracer.py), which stays one round's time at
  pipeline depth 2 where dispatch → ready holds three — against the
  modeled floor (steady-state bytes ÷ calibrated achieved bandwidth). The exported
  ratio ``measured / floor`` reads as "how far off the bandwidth
  roofline this round ran": residual DRIFT is the alert signal — a
  regressed knob, a silently grown geometry, or a mispredicting model
  all show up here at round cadence instead of in a post-hoc bench
  (OPERATIONS.md §21 carries the triage runbook).

Bandwidth constants: ``GRAPEVINE_COST_GBPS`` (the operator's
calibrated achieved value — not measured on the chip yet), else the
device's published peak from ``PEAK_GBPS``, keyed by ``device_kind``.
A peak in place of an achieved constant shifts the residual's LEVEL,
not its drift: triage on change, not magnitude, until calibration
lands.
"""

from __future__ import annotations

import os

from ..analysis.costmodel import COST_PHASES, engine_cost_ledger

#: published peak memory bandwidth (GB/s) per ``device_kind``, with its
#: source. A device that is not here is an error, never a default.
PEAK_GBPS = {
    # Google Cloud documentation, "TPU v5e": 16 GB HBM2e at 819 GB/s
    "TPU v5 lite": 819.0,
    # XLA:CPU (tests, one sandbox core): a placeholder of the order of
    # one DDR channel, not a measurement — CPU rounds are not
    # bandwidth-bound (PERF.md Round 13), only the drift is read there
    "cpu": 8.0,
}


def resolve_bandwidth_gbps(override: float | None = None) -> float:
    """Resolution order: explicit override → ``GRAPEVINE_COST_GBPS`` →
    the published peak of ``jax.devices()[0].device_kind``."""
    if override is not None:
        return float(override)
    env = os.environ.get("GRAPEVINE_COST_GBPS")
    if env:
        return float(env)
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in PEAK_GBPS:
        raise ValueError(
            f"no published peak bandwidth for device_kind {kind!r}: add "
            "it to obs/costmon.py PEAK_GBPS with its source, or set "
            "GRAPEVINE_COST_GBPS"
        )
    return PEAK_GBPS[kind]


class CostMonitor:
    """Exports the modeled cost ledger for one engine geometry and
    scores every resolved round against the roofline floor.

    Attached by :func:`..obs.attach_round_observability`; the engine
    hands each round's span ledger to :meth:`observe_round` off the
    jit path (engine/batcher.py ``PendingRound.resolve``, next to the
    tracer's ring append — a few float ops per ROUND)."""

    def __init__(self, ecfg, registry, *,
                 bandwidth_gbps: float | None = None):
        self.ledger = engine_cost_ledger(ecfg)
        self.bandwidth_gbps = resolve_bandwidth_gbps(bandwidth_gbps)
        self.floor_ms = self.ledger.floor_ms(self.bandwidth_gbps)

        phase_labels = {"phase": COST_PHASES}
        g_bytes = registry.gauge(
            "grapevine_cost_phase_hbm_bytes",
            "Modeled HBM bytes one execution of this phase moves "
            "(static geometry x knobs; sweep is per sweep call, not per "
            "round)",
            labels=phase_labels,
        )
        g_grows = registry.gauge(
            "grapevine_cost_phase_gather_rows",
            "Modeled HBM gather rows per execution of this phase",
            labels=phase_labels,
        )
        g_srows = registry.gauge(
            "grapevine_cost_phase_scatter_rows",
            "Modeled HBM scatter rows per execution of this phase",
            labels=phase_labels,
        )
        g_cipher = registry.gauge(
            "grapevine_cost_phase_cipher_rows",
            "Modeled bucket-cipher keystream rows per execution of "
            "this phase",
            labels=phase_labels,
        )
        g_sort = registry.gauge(
            "grapevine_cost_phase_sort_keys",
            "Modeled sort key-volume per execution of this phase",
            labels=phase_labels,
        )
        for phase in COST_PHASES:
            c = self.ledger.phases[phase]
            g_bytes.set(float(c.hbm_bytes), phase=phase)
            g_grows.set(float(c.gather_rows), phase=phase)
            g_srows.set(float(c.scatter_rows), phase=phase)
            g_cipher.set(float(c.cipher_rows), phase=phase)
            g_sort.set(float(c.sort_keys), phase=phase)

        registry.gauge(
            "grapevine_cost_steady_round_hbm_bytes",
            "Modeled HBM bytes per steady-state engine round (fetch + "
            "write-back; sweep excluded — operator-cadenced)",
        ).set(float(self.ledger.steady_round_bytes))
        registry.gauge(
            "grapevine_cost_bandwidth_gbps",
            "Achieved-bandwidth constant in use for the roofline floor "
            "(GRAPEVINE_COST_GBPS / cost_calibrate fit, else a "
            "per-backend placeholder)",
        ).set(self.bandwidth_gbps)
        registry.gauge(
            "grapevine_cost_roofline_floor_ms",
            "Modeled round-time floor: steady-state bytes / calibrated "
            "bandwidth",
        ).set(self.floor_ms)
        self._g_residual = registry.gauge(
            "grapevine_cost_roofline_residual",
            "Last round's own device span / modeled roofline floor "
            "(drift, not level, is the alert signal)",
        )
        self._g_residual_max = registry.gauge(
            "grapevine_cost_roofline_residual_max",
            "Worst roofline residual observed since attach",
        )

    def observe_round(self, spans: dict) -> None:
        """Score one resolved round's device span against the floor.

        ``spans`` is the round's span ledger (name -> (start_s,
        dur_s)); the ``device`` span is the round's own device time as
        the host can know it (previous round ready, or this one's
        dispatch end, to this one ready): right at every pipeline
        depth, an upper bound while the host arrives after the device
        finished, and on the round behind an expiry sweep,
        whose device time it can hold too (``device_exact`` 0 in the
        ledger's counts)."""
        dev = spans.get("device")
        if dev is None or self.floor_ms <= 0.0:
            return
        residual = (dev[1] * 1e3) / self.floor_ms
        self._g_residual.set(residual)
        self._g_residual_max.set_max(residual)
