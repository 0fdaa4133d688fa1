"""Obliviousness-safe observability (the telemetry analog of
testing/leakcheck.py).

The engine's security claim constrains *telemetry*, not just storage:
per-op timing or op-type breakdowns would reopen exactly the side
channel the oblivious engine closes (reference grapevine.proto:120-122
— "access patterns and timings"). This package therefore enforces the
leak policy structurally rather than by convention:

- ``registry``: a central TelemetryRegistry (counters, gauges,
  histograms with fixed bucket boundaries) with a declarative allowlist
  of label keys and registration-time-declared label values — a metric
  keyed by client identity, msg id, or op type raises
  ``TelemetryLeakError`` at registration, and ``audit()`` asserts the
  whole registry is batch-level only;
- ``phases``: the canonical round-phase names, wall-clock phase timers
  feeding the registry, and ``jax`` trace annotations for TPU profiler
  runs;
- ``exporter``: Prometheus text exposition of a registry;
- ``httpd``: a stdlib ``http.server`` thread serving ``/metrics``,
  ``/healthz``, ``/leakaudit``, and ``/flightrec``;
- ``leakmon``: the streaming transcript leak monitor — the pytest
  detectors (testing/leakcheck.py) run continuously over a sliding
  window of production rounds, publishing aggregate-only statistics
  and a machine-readable PASS/SUSPECT verdict;
- ``flightrec``: a fixed-size ring of schema-checked per-round
  summaries, dumped on demand or on a PASS→SUSPECT transition;
- ``tracer``: the round-trace profiler — a fixed ring of per-round
  span ledgers exported as Chrome trace-event JSON (``/trace``,
  Perfetto-loadable) plus the derived host/device bubble-ratio gauge;
- ``slo``: end-to-end commit-latency SLOs (enqueue→settle, one sample
  per round) with multi-window burn-rate alerting folded into
  ``/healthz``;
- ``profiler``: gated programmatic ``jax.profiler`` capture of a live
  engine (``/profile?ms=N``, ``--profile-enable``);
- ``workload``: batch-level workload telemetry — fixed-bucket batch
  fill-fraction and queue-depth histograms at round cadence, an
  arrival-rate EWMA gauge, per-phase utilization from the tracer span
  ledgers, and saturation/backpressure counters (the signals the
  ``grapevine_tpu/load`` scenario harness measures against);
- ``fleet``: the multi-process observatory — a stdlib aggregator
  scraping N member processes on a fixed public cadence and serving
  merged shard-labeled /metrics, folded /healthz and /leakaudit, the
  cross-shard schedule-uniformity detectors
  (``leakmon.FleetUniformityMonitor``), and replication-lag gauges
  (ROADMAP items 1/2/4).
"""

from .registry import (  # noqa: F401
    ALLOWED_LABEL_KEYS,
    FORBIDDEN_LABEL_KEYS,
    Counter,
    Gauge,
    Histogram,
    TelemetryLeakError,
    TelemetryRegistry,
)
from .phases import PHASES, device_phase, span  # noqa: F401
from .exporter import render_prometheus  # noqa: F401
from .httpd import MetricsServer  # noqa: F401
from .flightrec import FlightRecorder  # noqa: F401
from .leakmon import (  # noqa: F401
    EngineLeakMonitor,
    FleetUniformityConfig,
    FleetUniformityMonitor,
    LeakMonitorConfig,
    TranscriptLeakMonitor,
)
from .fleet import FleetAggregator, FleetConfig, parse_exposition  # noqa: F401
from .tracer import RoundTracer  # noqa: F401
from .slo import SloConfig, SloTracker  # noqa: F401
from .profiler import ProfilerBusy, ProfilerGate  # noqa: F401
from .workload import WorkloadTelemetry  # noqa: F401
from .costmon import CostMonitor  # noqa: F401


def attach_round_observability(engine, registry, *, trace_ring_size=512,
                               slo=None, profile_enable=False):
    """Attach the round tracer + commit-latency SLO + workload
    telemetry (always on for the device owner — all three cost a few
    dict/histogram ops per ROUND, not per op) and the optional
    profiler gate to ``engine``; the ONE place the serving layers
    (server/service.py, server/tier.py) share the policy.

    No explicit SLO config = observe-only (the CLI-default contract,
    server/cli.py ``_slo_config``): latencies and burn rates export,
    but /healthz only gates once an operator-supplied config enforces
    a target. The jax.profiler capture gate stays opt-in
    (``--profile-enable``): a capture has real overhead and writes
    device traces to disk.

    Returns ``(tracer, slo_tracker, profiler_or_None)``.
    """
    tracer = RoundTracer(capacity=trace_ring_size, registry=registry)
    engine.attach_tracer(tracer)
    slo_tracker = SloTracker(
        slo if slo is not None else SloConfig(enforce=False),
        registry=registry,
    )
    engine.attach_slo(slo_tracker)
    # the workload observatory's serving-side half (obs/workload.py):
    # fill/depth at round cadence, arrival EWMA, phase utilization —
    # the queue-depth signal ROADMAP item 4's adaptive batcher needs
    # exists on every production engine, not only under the harness
    engine.attach_workload(
        WorkloadTelemetry(registry, batch_size=engine.ecfg.batch_size)
    )
    # the cost observatory (obs/costmon.py): the static grapevine_cost_*
    # ledger (pure geometry x knobs — the bit-exact model the
    # check_cost_model gate cross-validates) plus the per-round
    # roofline residual against the tracer's device span
    engine.attach_costmon(CostMonitor(engine.ecfg, registry))
    return tracer, slo_tracker, ProfilerGate() if profile_enable else None
