#!/usr/bin/env python3
"""Capture a JAX profiler trace of the engine round on real TPU.

PERF.md lever 1: replace the analytic ~5-10 ms/round cost model with a
trace-backed attribution. Run on a machine with a TPU, as the one
process that uses it:

    python profile_tpu.py [--impl jnp|pallas]
                          [--cap-log2 20] [--batch 2048] [--rounds 8]
                          [--outdir chiprun_out/grapevine-trace]

Prints one JSON line with per-round wall time and writes a perfetto/
tensorboard trace directory. View: tensorboard --logdir <outdir>, or
upload trace.json.gz to ui.perfetto.dev.

Deliberately NOT part of bench.py: the profiler adds overhead and the
trace directory is an artifact to inspect, not a scoreboard number.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--impl", default="jnp", choices=["jnp", "pallas"])
    ap.add_argument("--cap-log2", type=int, default=20)
    ap.add_argument("--batch", type=int, default=2048)
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--outdir", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "chiprun_out", "grapevine-trace"))
    args = ap.parse_args()

    import jax

    from grapevine_tpu.config import setup_compile_cache

    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(json.dumps({"error": f"needs a TPU, have {platform!r}"}))
        return 1
    setup_compile_cache()

    import bench

    cap = 1 << args.cap_log2
    cfg, ecfg, state, step = bench._mk_engine(
        cap, 1 << 12, args.batch, cipher_impl=args.impl
    )
    batches = bench.make_batches(4, args.batch)
    # compile + settle outside the trace window
    state, resp, _ = step(ecfg, state, batches[0])
    jax.block_until_ready(resp)

    times = []
    with jax.profiler.trace(args.outdir):
        for i in range(args.rounds):
            t0 = time.perf_counter()
            state, resp, _ = step(ecfg, state, batches[i % 4])
            jax.block_until_ready(resp)
            times.append(time.perf_counter() - t0)
    per_round_ms = statistics.median(times) * 1e3
    print(json.dumps({
        "impl": args.impl,
        "capacity_log2": args.cap_log2,
        "batch": args.batch,
        "median_round_ms": round(per_round_ms, 3),
        "ops_per_sec_blocking": round(args.batch / (per_round_ms / 1e3), 1),
        "trace_dir": args.outdir,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
