"""Batch-level engine metrics (SURVEY §5 observability).

The obliviousness requirement constrains telemetry: nothing here is
keyed by client identity or op type — per-op timing/type breakdowns
would themselves be the side channel the engine exists to close
(reference grapevine.proto:120-122). What IS safe to export, and what
operators need (the reference's `mc-common` logging analog):

- round counters: rounds run, real ops, padded slots → batch occupancy;
- round latency: a fixed-size ring of recent wall times → p50/p99
  (BASELINE.json tracks p99 access latency as a first-class metric);
- per-phase round timing (assembly/verify/dispatch/evict/demux/sweep)
  as fixed-bucket histograms — every phase covers the whole fixed-size
  round, so durations are functions of (capacity, batch size), never of
  the ops inside (obs/phases.py);
- scheduler/queue health: depth, high-water, under-full rounds,
  collector stalls;
- expiry sweeps run and records evicted;
- auth: batch verifications, failed signatures (counts only);
- stash pressure: sampled occupancy high-water mark per tree (polled at
  ``snapshot()`` — a per-round device reduction would stall the
  dispatch pipeline for a gauge nobody reads between scrapes).

All of it lives in an obs.TelemetryRegistry whose label allowlist makes
a per-client/per-op series a registration-time error, and which the
leak audit (tools/check_telemetry_policy.py) re-checks in tier-1.

Thread-safety: the ring is guarded by this module's own lock, registry
samples by per-child locks, and every recording entry point may be
called from any thread — `record_round` in particular runs from
`PendingRound.resolve()` outside the engine lock (the pipelined
scheduler resolves a round after dispatching the next one). Do not
weaken the internal locks based on who currently calls what.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from ..obs.phases import PHASE_BUCKETS, PHASES, STASH_BUCKETS, span
from ..obs.registry import TelemetryRegistry


class EngineMetrics:
    """Monotonic counters + a latency ring on a TelemetryRegistry;
    `snapshot()` is the merged flat export, the registry the scrapable
    one (obs/exporter.py)."""

    def __init__(self, ring_size: int = 1024, registry: TelemetryRegistry | None = None):
        self._lock = threading.Lock()
        self._ring = np.zeros((ring_size,), np.float64)
        self._ring_n = 0  # total rounds ever recorded
        self._last_round_mono: float | None = None
        r = self.registry = registry or TelemetryRegistry()
        self._c_rounds = r.counter(
            "grapevine_rounds_total", "oblivious rounds committed")
        self._c_real = r.counter(
            "grapevine_real_ops_total", "real (non-padding) ops committed")
        self._c_padded = r.counter(
            "grapevine_padded_slots_total", "dummy-padded slots committed")
        self._c_underfull = r.counter(
            "grapevine_underfull_rounds_total",
            "rounds dispatched with fewer real ops than batch_size")
        self._c_sweeps = r.counter(
            "grapevine_expiry_sweeps_total", "expiry sweeps run")
        self._c_evicted = r.counter(
            "grapevine_expired_records_total", "records evicted by expiry")
        self._c_verifies = r.counter(
            "grapevine_batch_verifies_total",
            "round-level batched signature verifications")
        self._c_authfail = r.counter(
            "grapevine_auth_failures_total",
            "challenge signatures that failed verification (count only)")
        self._c_stalls = r.counter(
            "grapevine_collector_stalls_total",
            "collection windows that hit the max_wait cap before filling")
        self._c_worker_crash = r.counter(
            "grapevine_worker_crash_total",
            "scheduler collector thread deaths (crashes, not clean close)")
        self._g_occupancy = r.gauge(
            "grapevine_batch_occupancy",
            "real ops / batch slots of the last committed round")
        self._g_qdepth = r.gauge(
            "grapevine_queue_depth", "ops waiting in the scheduler queue")
        self._g_qdepth_hw = r.gauge(
            "grapevine_queue_depth_high_water",
            "max scheduler queue depth observed")
        # per tree: with per-path levels under the dense ones the mailbox
        # stash is the first thing an eviction fault would fill, and one
        # gauge over both trees would hide it behind the records tree's
        stashes = {"tree": ("rec", "mb", "rec_pm", "mb_pm")}
        self._g_stash_hw = r.gauge(
            "grapevine_stash_high_water",
            "max sampled ORAM stash occupancy (must stay far below "
            "stash_size; overflow means the eviction invariant broke); "
            "rec_pm / mb_pm are a recursive position map's inner trees",
            labels=stashes)
        # the round's layout engages by geometry, so these are static
        # per engine: set once at construction (set_round_layout)
        trees = {"tree": ("rec", "mb")}
        self._g_dense = r.gauge(
            "grapevine_round_dense_levels",
            "top tree levels one oram_round moves whole, once, at "
            "constant addresses (a function of batch, tree height and "
            "tree-top cache levels — oram/round.py)", labels=trees)
        self._g_rows = r.gauge(
            "grapevine_round_fetched_bucket_rows",
            "HBM bucket rows one oram_round gathers and decrypts (and "
            "writes back when it evicts): the dense range under the "
            "cache plus one row per path and deeper level", labels=trees)
        self._g_perpath = r.gauge(
            "grapevine_round_perpath_bucket_rows",
            "of those rows, the ones at levels the batch does not cover "
            "(one per path and level below the dense ones): 0 says the "
            "round moves its tree whole, level by level", labels=trees)
        self._g_psum = r.gauge(
            "grapevine_mesh_psum_bytes",
            "bytes one round hands to the mesh's all-reduce for this "
            "tree (index, value, nonce and leaf planes of every fetched "
            "bucket row, every pass); 0 on one device", labels=trees)
        self._g_dma_rows = r.gauge(
            "grapevine_round_dma_placed_rows",
            "bucket rows one round's write-back places in this tree's "
            "value plane by DMA, one copy a row (oblivious/"
            "pallas_place.py: a plane that stores its rows as whole "
            "memory tiles, on a TPU); 0 where the plane keeps XLA's "
            "scatter", labels=trees)
        self._g_state_init = r.gauge(
            "grapevine_state_init_seconds",
            "wall time the engine took to build its state on the device "
            "(both trees zeroed, position maps drawn; before any "
            "recovery), the host span grapevine/state_init")
        self._g_state_bytes = r.gauge(
            "grapevine_state_bytes",
            "bytes of the engine's device-resident state, summed over "
            "its arrays (over all shards on a mesh)")
        self._g_hbm_peak = r.gauge(
            "grapevine_hbm_peak_bytes",
            "the device's peak bytes in use since the process started "
            "(memory_stats; on a mesh the chip with the largest peak); "
            "sampled with the stashes, 0 where the backend reports none")
        self._g_hbm_limit = r.gauge(
            "grapevine_hbm_limit_bytes",
            "the bytes that device lets the process use (memory_stats "
            "bytes_limit): what grapevine_hbm_peak_bytes is a share of")
        # the compiler's own count for the served round, a chip: set once
        # the round has compiled (set_compiled_memory), 0 until then
        self._g_compiled_args = r.gauge(
            "grapevine_hbm_compiled_argument_bytes",
            "bytes of the round executable's arguments on one chip "
            "(memory_analysis of the executable the engine's jit holds): "
            "the state and the batch")
        self._g_compiled_temp = r.gauge(
            "grapevine_hbm_compiled_temp_bytes",
            "bytes of temporaries the compiler gave the round "
            "executable on one chip")
        self._g_compiled = r.gauge(
            "grapevine_hbm_compiled_bytes",
            "what the round executable needs of one chip while it runs, "
            "as the compiler counted it: arguments + outputs not "
            "aliased to them + temporaries + generated code; against "
            "grapevine_hbm_limit_bytes it is the margin")
        self._h_phase = r.histogram(
            "grapevine_phase_seconds",
            "wall time per round phase (batch-level; obs/phases.py)",
            buckets=PHASE_BUCKETS, labels={"phase": PHASES})
        self._h_round = r.histogram(
            "grapevine_round_seconds",
            "dispatch-to-delivery commit latency per round",
            buckets=PHASE_BUCKETS)
        self._h_stash = r.histogram(
            "grapevine_stash_occupancy",
            "sampled stash occupancy (entries)", buckets=STASH_BUCKETS,
            labels=stashes)

    # -- recording ------------------------------------------------------

    def record_round(self, n_real: int, batch_size: int, seconds: float) -> None:
        with self._lock:
            self._ring[self._ring_n % self._ring.size] = seconds
            self._ring_n += 1
            self._last_round_mono = time.monotonic()
        self._c_rounds.inc()
        self._c_real.inc(n_real)
        self._c_padded.inc(batch_size - n_real)
        if n_real < batch_size:
            self._c_underfull.inc()
        self._g_occupancy.set(n_real / batch_size if batch_size else 0.0)
        self._h_round.observe(seconds)

    def set_round_layout(self, layout: dict) -> None:
        """``{tree: (dense_levels, fetched_bucket_rows,
        perpath_bucket_rows)}`` of one ``oram_round`` per tree, from the
        resolved geometry."""
        for tree, (dense, rows, perpath) in layout.items():
            self._g_dense.set(dense, tree=tree)
            self._g_rows.set(rows, tree=tree)
            self._g_perpath.set(perpath, tree=tree)

    def set_mesh_psum_bytes(self, nbytes: dict) -> None:
        """``{tree: bytes}`` a round all-reduces, from the geometry."""
        for tree, n in nbytes.items():
            self._g_psum.set(n, tree=tree)

    def set_dma_placed_rows(self, rows: dict) -> None:
        """``{tree: rows}`` a round places by DMA, set once: the
        mechanism engages when the round is traced, not per request."""
        for tree, n in rows.items():
            self._g_dma_rows.set(n, tree=tree)

    def set_compiled_memory(self, stats) -> None:
        """A ``CompiledMemoryStats`` of the round executable; None
        (jax had none to give) leaves the gauges at 0."""
        if stats is None:
            return
        self._g_compiled_args.set(stats.argument_size_in_bytes)
        self._g_compiled_temp.set(stats.temp_size_in_bytes)
        self._g_compiled.set(
            stats.argument_size_in_bytes + stats.output_size_in_bytes
            - stats.alias_size_in_bytes + stats.temp_size_in_bytes
            + stats.generated_code_size_in_bytes)

    def set_state_size(self, init_seconds: float, nbytes: int) -> None:
        """Static per engine, like the round layout: what building the
        state took and what it holds."""
        self._g_state_init.set(init_seconds)
        self._g_state_bytes.set(nbytes)

    def observe_device_memory(self, stats) -> None:
        """``memory_stats()`` of each device the state lives on: the
        one with the largest peak is the one that runs out first."""
        peak, limit = max(
            ((s.get("peak_bytes_in_use", 0), s.get("bytes_limit", 0))
             for s in stats), default=(0, 0))
        self._g_hbm_peak.set(peak)
        self._g_hbm_limit.set(limit)

    def record_sweep(self, evicted: int) -> None:
        self._c_sweeps.inc()
        self._c_evicted.inc(evicted)

    def record_auth(self, failures: int = 0) -> None:
        self._c_verifies.inc()
        if failures:
            self._c_authfail.inc(failures)

    def observe_stash(self, tree: str, occupancy: int) -> None:
        self._g_stash_hw.set_max(occupancy, tree=tree)
        self._h_stash.observe(occupancy, tree=tree)

    def observe_phase(self, phase: str, seconds: float) -> None:
        self._h_phase.observe(seconds, phase=phase)

    def span(self, name: str, ledger: dict | None = None):
        """The host side's one span primitive (obs/phases.py ``span``)
        with this registry's ``grapevine_phase_seconds`` behind it.
        ``ledger`` is the round's span dict where the span belongs to a
        round; a sweep, a replay or a forced checkpoint has none."""
        return span(name, ledger, self._h_phase)

    def observe_queue_depth(self, depth: int) -> None:
        self._g_qdepth.set(depth)
        self._g_qdepth_hw.set_max(depth)

    def record_stall(self) -> None:
        self._c_stalls.inc()

    def record_worker_crash(self) -> None:
        self._c_worker_crash.inc()

    # -- health probes --------------------------------------------------

    def last_round_age(self) -> float | None:
        """Seconds since the last committed round; None before the first.
        Lock-free read path on purpose: healthz must answer while a
        wedged recorder holds the ring lock."""
        t = self._last_round_mono
        return None if t is None else time.monotonic() - t

    # -- compat counter views (legacy attribute names) ------------------

    @property
    def real_ops(self) -> int:
        return int(self._c_real.get())

    @property
    def padded_slots(self) -> int:
        return int(self._c_padded.get())

    @property
    def stash_high_water(self) -> int:
        """The largest of the per-tree high-water marks."""
        return int(max(c.value for _, c in self._g_stash_hw.series()))

    # -- export ---------------------------------------------------------

    def snapshot(self) -> dict:
        with self._lock:
            rounds = self._ring_n
            # ring slice is valid both pre-wrap (first `rounds` cells)
            # and post-wrap (the whole ring holds the last ring_size)
            lat = np.sort(self._ring[: min(rounds, self._ring.size)])
        real = int(self._c_real.get())
        slots = real + int(self._c_padded.get())
        out = {
            "rounds": rounds,
            "real_ops": real,
            "batch_occupancy": (real / slots) if slots else 0.0,
            "sweeps": int(self._c_sweeps.get()),
            "evicted": int(self._c_evicted.get()),
            "batch_verifies": int(self._c_verifies.get()),
            "auth_failures": int(self._c_authfail.get()),
            "stash_high_water": self.stash_high_water,
            "underfull_rounds": int(self._c_underfull.get()),
            "collector_stalls": int(self._c_stalls.get()),
            "queue_depth": int(self._g_qdepth.get()),
            "queue_depth_high_water": int(self._g_qdepth_hw.get()),
        }
        if len(lat):
            # method="higher" (a real order statistic, never below a
            # sample): linear interpolation over a small ring
            # under-reports p99 — at 20 rounds it averaged the 19th and
            # 20th samples instead of reporting the 20th
            out["round_ms_p50"] = round(
                float(np.percentile(lat, 50, method="higher")) * 1e3, 3)
            out["round_ms_p99"] = round(
                float(np.percentile(lat, 99, method="higher")) * 1e3, 3)
        # the merged registry view (phase histograms, gauges): one flat
        # dict so loopback health readers see engine + scheduler + ORAM
        # telemetry without a second endpoint (server/service.py)
        out.update(self.registry.snapshot())
        return out
