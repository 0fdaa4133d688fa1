#!/usr/bin/env python
"""Perf-regression sentinel over the banked bench trajectory.

BENCH_trajectory.jsonl accumulates one JSON line per bench run across
PRs (bench.py --pr TAG). This tool turns that record into a gate: it
flattens every run's ``configs`` tree into directional series —
throughputs (higher is better) and latencies (lower is better), keyed
by config, metric path, and the run's geometry/sizes/backend so toy
smoke shapes are never compared against full-size runs — and fails
when a fresh observation regresses beyond a noise factor against the
**median** of the previously banked values of the SAME series.

Median, not best: this sandbox's 2-vCPU scheduler noise puts
back-to-back medians up to 2× apart (PERF.md Round 6 methodology
note), so judging against the best-ever banked value would ratchet
the bar toward the luckiest historical observation and fail tier-1
spuriously as lines accumulate. The median of history is stable under
that noise, and the default ``--factor 2.0`` (fail only past 2× of
the median) matches the sentinel's actual purpose — catching the
2-10× regressions an accidental algorithmic change causes (a
quadratic sneaking back in, a donation lost to a defensive copy), not
10% drift. Tighten ``--factor`` on quiet hardware.

Modes:

- ``--smoke`` (the tier-1 gate, wired next to check_telemetry_policy /
  check_checkpoint_seal): no bench run — milliseconds, not minutes.
  Three checks: the trajectory parses into comparable series; the
  LATEST observation of every series that repeats is within the factor
  of its prior median (the banked baseline polices itself); and a
  synthetic self-test proves the comparator actually fires on a clear
  regression and stays quiet inside the factor (a sentinel that cannot
  fail is not a sentinel).
- ``--fresh FILE`` (or ``-`` for stdin): compare a fresh bench.py
  output line against the banked baselines — the A/B workflow PERF.md
  points future perf PRs at. Exit 1 on any regression past the factor.
- ``--run``: execute ``bench.py --smoke`` in a subprocess and compare
  its output (slow; for local use, never tier-1).

Run directly::

    python tools/check_perf_regression.py --smoke
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRAJECTORY = os.path.join(REPO, "BENCH_trajectory.jsonl")

#: metric-name suffixes with a known direction. Anything else (counts,
#: notes, verdict strings, speedup ratios — already a comparison) is
#: not gated.
HIGHER_BETTER = ("ops_per_sec", "records_per_sec")
LOWER_BETTER = ("_ms", "_ms_per_op", "_s")

#: config fields that describe geometry, not performance — they key the
#: series (comparing B=8 smoke against B=2048 full would be noise, not
#: signal) and are excluded from the metrics themselves
GEOMETRY_KEYS = ("batch", "capacity_log2", "mesh", "clients",
                 "tree_density", "key_bits",
                 "rounds", "slo_target_ms", "pipeline_depth",
                 "shard_count", "tail_frames",
                 "worker_count", "adaptive_batch", "crypto_backend",
                 "host_cores", "verify_items")

#: result fields that are neither geometry nor a directional metric.
#: dispatch_skew_p99_ms is the load harness's HONESTY metric (how late
#: the replay dispatcher ran) — a property of the measuring host, not
#: of the engine; knee_target_ms is the host-CALIBRATED knee SLO
#: target (max(250, 8x unloaded round)) — config derived from a
#: measurement, neither geometry (it would fragment every capacity
#: series) nor a directional metric. Neither gates.
SKIP_KEYS = ("note", "skipped", "error", "leakaudit", "verdict",
             "interpret_trace_s", "compile_s", "wall_s",
             "dispatch_skew_p99_ms", "calibrated_round_ms",
             "knee_target_ms")


def _direction(name: str) -> int:
    """+1 higher-better, -1 lower-better, 0 not gated."""
    if name.endswith(HIGHER_BETTER):
        return 1
    if name.endswith(LOWER_BETTER) and not name.startswith("speedup"):
        return -1
    return 0


def _flatten(prefix: str, node, out: dict) -> None:
    if isinstance(node, dict):
        for k, v in sorted(node.items()):
            if k in SKIP_KEYS or k in GEOMETRY_KEYS:
                continue
            _flatten(f"{prefix}.{k}" if prefix else str(k), v, out)
        return
    if isinstance(node, (int, float)) and not isinstance(node, bool):
        d = _direction(prefix.rsplit(".", 1)[-1])
        if d and node > 0:  # zero = unmeasured placeholder, not a perf
            out[prefix] = (float(node), d)


def _geometry_sig(cfg_result: dict) -> str:
    if not isinstance(cfg_result, dict):
        return ""
    return ",".join(
        f"{k}={cfg_result[k]}" for k in GEOMETRY_KEYS if k in cfg_result
    )


def extract_series(lines: list[dict]) -> dict:
    """{series_key: [(tag, value, direction), ...]} in banked order.

    A series key is (config, metric path, geometry, sizes, backend) —
    observations are only comparable inside one key.
    """
    series: dict = {}
    for line in lines:
        sizes = line.get("sizes", "?")
        backend = line.get("backend", "?")
        tag = line.get("pr", "") or str(line.get("ts", "?"))
        for cfg_name, cfg_result in (line.get("configs") or {}).items():
            if not isinstance(cfg_result, dict):
                continue
            if "skipped" in cfg_result or "error" in cfg_result:
                continue
            flat: dict = {}
            _flatten("", cfg_result, flat)
            sig = _geometry_sig(cfg_result)
            for path, (value, d) in flat.items():
                key = f"{cfg_name}.{path}|{sig}|{sizes}|{backend}"
                series.setdefault(key, []).append((tag, value, d))
    return series


def _median(values: list[float]) -> float:
    s = sorted(values)
    n = len(s)
    return s[n // 2] if n % 2 else (s[n // 2 - 1] + s[n // 2]) / 2.0


def compare_latest(series: dict, factor: float) -> tuple[list, int]:
    """Check each repeating series' newest value against the MEDIAN of
    its earlier ones (robust to one lucky banked outlier). Returns
    (regressions, n_compared)."""
    regressions = []
    compared = 0
    for key, obs in series.items():
        if len(obs) < 2:
            continue
        *hist, (tag, value, d) = obs
        compared += 1
        base = _median([v for _, v, _ in hist])
        if d > 0:
            if value * factor < base:
                regressions.append(
                    f"{key}: {value:g} is {value / base:.2f}x of the "
                    f"banked median {base:g} (allowed ≥ 1/{factor:g}x; "
                    f"latest tag {tag!r})"
                )
        else:
            if value > base * factor:
                regressions.append(
                    f"{key}: {value:g} is {value / base:.2f}x of the "
                    f"banked median {base:g} (allowed ≤ {factor:g}x; "
                    f"latest tag {tag!r})"
                )
    return regressions, compared


def compare_fresh(fresh_line: dict, banked: list[dict],
                  factor: float) -> tuple[list, int]:
    """Compare one fresh bench line against the banked median per
    series."""
    base = extract_series(banked)
    fresh = extract_series([fresh_line])
    merged = {}
    for key, obs in fresh.items():
        if key in base:
            merged[key] = base[key] + obs
    return compare_latest(merged, factor)


def load_trajectory(path: str = TRAJECTORY) -> list[dict]:
    lines = []
    with open(path, encoding="utf-8") as fh:
        for i, raw in enumerate(fh, 1):
            raw = raw.strip()
            if not raw:
                continue
            try:
                lines.append(json.loads(raw))
            except json.JSONDecodeError as e:
                raise SystemExit(
                    f"{os.path.basename(path)}:{i}: unparseable banked "
                    f"line ({e}) — the baseline record is corrupt"
                ) from None
    if not lines:
        raise SystemExit(f"{path}: no banked bench lines")
    return lines


def selftest(factor: float) -> None:
    """The comparator must fire on a clear regression and stay quiet
    within the factor — run on synthetic lines so the check cannot rot."""
    mk = lambda ops, p99: {  # noqa: E731
        "sizes": "full", "backend": "cpu", "pr": "synthetic",
        "configs": {"synth": {"ops_per_sec": ops, "p99_round_ms": p99,
                              "batch": 8, "capacity_log2": 10}},
    }
    regs, n = compare_latest(
        extract_series([mk(100.0, 50.0),
                        mk(100.0 / (factor * 2.0),
                           50.0 * factor * 2.0)]),
        factor,
    )
    assert n == 2 and len(regs) == 2, (
        f"sentinel self-test: past-factor regression not flagged ({regs})"
    )
    drift = 1.0 + (factor - 1.0) * 0.5  # halfway inside the factor
    regs, n = compare_latest(
        extract_series([mk(100.0, 50.0),
                        mk(100.0 / drift, 50.0 * drift)]), factor)
    assert n == 2 and not regs, (
        f"sentinel self-test: within-factor drift flagged ({regs})"
    )
    # geometry guard: same metric at a different batch is NOT compared
    a = mk(100.0, 50.0)
    b = mk(1.0, 5000.0)
    b["configs"]["synth"]["batch"] = 2048
    regs, n = compare_latest(extract_series([a, b]), factor)
    assert n == 0 and not regs, (
        "sentinel self-test: mismatched geometry was compared"
    )
    # the capacity metric path (PR 9, bench load_scenarios): the knee
    # and per-scenario throughput/latency nest two dicts deep — the
    # flattener must produce comparable series for them, fire past the
    # factor, and skip the honesty/calibration fields. knee_target_ms
    # VARIES between the two synthetic lines on purpose: it is
    # perf_counter-calibrated in real runs, and were it geometry (or a
    # gated metric) every run would mint a fresh series and the
    # capacity numbers would never be compared at all.
    mk_cap = lambda knee, p99, tgt: {  # noqa: E731
        "sizes": "full", "backend": "cpu", "pr": "synthetic",
        "configs": {"load_scenarios": {
            "batch": 16, "capacity_log2": 14, "knee_target_ms": tgt,
            "knee_ops_per_sec": knee,
            "scenarios": {"steady": {
                "achieved_ops_per_sec": knee * 0.5,
                "p99_commit_ms": p99,
                "dispatch_skew_p99_ms": p99 * 100.0,  # must NOT gate
                "leakaudit": "PASS",
            }},
        }},
    }
    regs, n = compare_latest(
        extract_series([mk_cap(200.0, 40.0, 3250.7),
                        mk_cap(200.0 / (factor * 2.0),
                               40.0 * factor * 2.0, 2871.3)]),
        factor,
    )
    assert n == 3 and len(regs) == 3, (
        f"sentinel self-test: capacity series not gated ({n=}, {regs}) "
        "— a calibration-varying field fragmented the series keys?"
    )
    assert not any("dispatch_skew" in r or "knee_target" in r
                   for r in regs), (
        "sentinel self-test: an honesty/calibration field was gated"
    )
    regs, n = compare_latest(
        extract_series([mk_cap(200.0, 40.0, 3250.7),
                        mk_cap(200.0, 40.0, 2871.3)]), factor)
    assert n == 3 and not regs, (
        f"sentinel self-test: steady capacity series flagged ({regs})"
    )
    # pipeline_depth is GEOMETRY (PR 10): an explicit-depth rerun keys
    # its own series — a depth-2 knee must never be graded against the
    # auto/depth-1 baseline (they measure different programs), and the
    # auto runs (no key at all) must stay one continuous series
    a = mk_cap(200.0, 40.0, 3250.7)
    b = mk_cap(200.0 / (factor * 4.0), 40.0 * factor * 4.0, 3250.7)
    b["configs"]["load_scenarios"]["pipeline_depth"] = 2
    regs, n = compare_latest(extract_series([a, b]), factor)
    assert n == 0 and not regs, (
        "sentinel self-test: a depth-keyed capacity line was compared "
        "against the auto-depth baseline"
    )
    # shard_count is GEOMETRY (PR 16, bench fleet_loopback): an N=2
    # fleet capacity line sums two shard knees over two engines — a
    # different deployment shape whose numbers must never grade against
    # the N=1 (monolithic) series, in either direction; same-N fleet
    # lines must still gate each other.
    a = mk_cap(200.0, 40.0, 3250.7)
    b = mk_cap(200.0 * factor * 4.0, 40.0 / (factor * 4.0), 3250.7)
    b["configs"]["load_scenarios"]["shard_count"] = 2
    regs, n = compare_latest(extract_series([a, b]), factor)
    assert n == 0 and not regs, (
        "sentinel self-test: a shard_count-keyed fleet line was "
        "compared against the single-process baseline"
    )
    e = mk_cap(200.0, 40.0, 3250.7)
    f = mk_cap(200.0 / (factor * 4.0), 40.0 * factor * 4.0, 3250.7)
    e["configs"]["load_scenarios"]["shard_count"] = 2
    f["configs"]["load_scenarios"]["shard_count"] = 2
    regs, n = compare_latest(extract_series([e, f]), factor)
    assert n == 3 and len(regs) == 3, (
        f"sentinel self-test: same-shard-count series not gated "
        f"({n=}, {regs})"
    )
    # tail_frames is GEOMETRY (ISSUE 19, bench failover_ab): the
    # measured failover RTO scales with the durable tail the promotion
    # replays, so a line banked at a different checkpoint interval is
    # a different experiment — never graded against another interval's
    # baseline, in either direction; same-interval lines must still
    # gate each other (an RTO regression at a FIXED tail is real).
    a = mk_cap(200.0, 40.0, 3250.7)
    b = mk_cap(200.0 * factor * 4.0, 40.0 / (factor * 4.0), 3250.7)
    b["configs"]["load_scenarios"]["tail_frames"] = 64
    regs, n = compare_latest(extract_series([a, b]), factor)
    assert n == 0 and not regs, (
        "sentinel self-test: a tail_frames-keyed failover line was "
        "compared against a different-interval baseline"
    )
    g = mk_cap(200.0, 40.0, 3250.7)
    h = mk_cap(200.0 / (factor * 4.0), 40.0 * factor * 4.0, 3250.7)
    g["configs"]["load_scenarios"]["tail_frames"] = 64
    h["configs"]["load_scenarios"]["tail_frames"] = 64
    regs, n = compare_latest(extract_series([g, h]), factor)
    assert n == 3 and len(regs) == 3, (
        f"sentinel self-test: same-tail-frames series not gated "
        f"({n=}, {regs})"
    )
    # worker_count is GEOMETRY (ISSUE 20, bench host_pipeline_ab): a
    # W-worker multiprocess frontend runs a different host program
    # (fan-out + IPC) than the in-process path — its numbers key their
    # own series in either direction; same-W lines must still gate.
    a = mk_cap(200.0, 40.0, 3250.7)
    b = mk_cap(200.0 * factor * 4.0, 40.0 / (factor * 4.0), 3250.7)
    b["configs"]["load_scenarios"]["worker_count"] = 2
    regs, n = compare_latest(extract_series([a, b]), factor)
    assert n == 0 and not regs, (
        "sentinel self-test: a worker_count-keyed host-pipeline line "
        "was compared against the in-process baseline"
    )
    i = mk_cap(200.0, 40.0, 3250.7)
    j = mk_cap(200.0 / (factor * 4.0), 40.0 * factor * 4.0, 3250.7)
    i["configs"]["load_scenarios"]["worker_count"] = 2
    j["configs"]["load_scenarios"]["worker_count"] = 2
    regs, n = compare_latest(extract_series([i, j]), factor)
    assert n == 3 and len(regs) == 3, (
        f"sentinel self-test: same-worker-count series not gated "
        f"({n=}, {regs})"
    )
    # adaptive_batch is GEOMETRY (ISSUE 20): the SLO-adaptive window
    # trades latency against occupancy per-round — a run with the
    # policy on measures a different collection discipline than the
    # static window and must never grade against it.
    a = mk_cap(200.0, 40.0, 3250.7)
    b = mk_cap(200.0 * factor * 4.0, 40.0 / (factor * 4.0), 3250.7)
    b["configs"]["load_scenarios"]["adaptive_batch"] = True
    regs, n = compare_latest(extract_series([a, b]), factor)
    assert n == 0 and not regs, (
        "sentinel self-test: an adaptive-batch line was compared "
        "against the static-window baseline"
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tier-1 mode: validate the banked baseline + "
                    "comparator self-test; no bench run")
    ap.add_argument("--fresh", metavar="FILE",
                    help="fresh bench.py JSON line to compare against "
                    "the banked baselines ('-' = stdin)")
    ap.add_argument("--run", action="store_true",
                    help="run bench.py --smoke and compare its output")
    ap.add_argument("--factor", type=float, default=2.0,
                    help="multiple of the banked median beyond which a "
                    "regression fails (default 2.0 — see the noise "
                    "rationale above; tighten on quiet hardware)")
    ap.add_argument("--trajectory", default=TRAJECTORY)
    args = ap.parse_args(argv)
    if args.factor <= 1.0:
        raise SystemExit("--factor must be > 1")

    selftest(args.factor)
    banked = load_trajectory(args.trajectory)
    series = extract_series(banked)
    if not series:
        raise SystemExit(
            "no comparable series in the trajectory — every banked line "
            "is skipped/errored or carries no directional metrics"
        )

    if args.fresh or args.run:
        if args.run:
            import subprocess

            out = subprocess.run(
                [sys.executable, os.path.join(REPO, "bench.py"), "--smoke"],
                capture_output=True, text=True, timeout=1800, cwd=REPO,
            )
            candidates = [ln for ln in out.stdout.splitlines()
                          if ln.strip().startswith("{")]
            if out.returncode != 0 or not candidates:
                raise SystemExit(
                    f"bench run failed (rc={out.returncode}): "
                    f"{out.stderr[-300:]}"
                )
            fresh_line = json.loads(candidates[-1])
        elif args.fresh == "-":
            fresh_line = json.loads(sys.stdin.read())
        else:
            with open(args.fresh, encoding="utf-8") as fh:
                fresh_line = json.loads(fh.read())
        regs, n = compare_fresh(fresh_line, banked, args.factor)
        scope = "fresh-vs-banked-median"
    else:
        regs, n = compare_latest(series, args.factor)
        scope = "banked-latest-vs-median"

    for r in regs:
        print(f"PERF REGRESSION: {r}", file=sys.stderr)
    print(
        f"perf sentinel: self-test ok; {len(banked)} banked lines, "
        f"{len(series)} series, {n} compared ({scope}, factor "
        f"{args.factor:g}x); {'FAILED' if regs else 'clean'}"
    )
    return 1 if regs else 0


if __name__ == "__main__":
    raise SystemExit(main())
