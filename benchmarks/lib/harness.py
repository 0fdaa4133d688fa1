"""One run of one cell: build the served bus from the configuration,
warm it, let the traffic's driver load it for the window, then replay
every round and every expiry sweep on the oracle and reduce what was
observed to metrics.

``run.py`` looks for the chip and then calls :func:`run_cell`; the
tests call it directly on the CPU at a toy geometry.
"""

from __future__ import annotations

import contextlib
import gc
import glob
import json
import os
import shutil
import statistics
import threading
import time
import types

from . import compare, gcwatch, round_bytes
from . import wire as W
from ..readers import xplane_busy
from .manifest import HERE, Benchmark, load_kind
from .roundlog import RoundLog, SubmitLog
from .stats import percentile


def say(**kv) -> None:
    print(json.dumps(kv), flush=True)


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def chips_missing(cell: dict) -> str | None:
    """Why this machine cannot run ``cell``, or None: JAX's first
    device must be a TPU and the machine must hold exactly the cell's
    chips. No platform override is set or read."""
    dev = device_info()
    if dev["platform"] == "tpu" and dev["count"] == cell["chips"]:
        return None
    return (f"{cell['name']} needs {cell['chips']} TPU chip(s); JAX reports "
            f"{dev['count']} x {dev['platform']} ({dev['kind']})")


def prepare_process() -> str:
    """The compile cache where the program keeps it
    (``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``)
    and the native session library, built on first import. Returns the
    cache directory."""
    from grapevine_tpu import native
    from grapevine_tpu.config import setup_compile_cache

    cache = setup_compile_cache()
    if native.lib is None:
        raise RuntimeError("the native session library did not build: "
                           f"{native.load_error}")
    return cache


@contextlib.contextmanager
def scratch_dir(name: str):
    """``benchmarks/.scratch/<name>``: inside the checkout, emptied
    before the run and removed after it."""
    path = os.path.join(HERE, ".scratch", name)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    try:
        yield path
    finally:
        shutil.rmtree(path, ignore_errors=True)


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def build_server(config: dict, seed: int):
    """The ``GrapevineServer`` the CLI builds (``server/cli.py``: every
    option it does not pass stays at its default), from the
    configuration file's ``grapevine_config``."""
    from grapevine_tpu.config import GrapevineConfig
    from grapevine_tpu.obs.slo import SloConfig
    from grapevine_tpu.server.service import GrapevineServer

    cfg = GrapevineConfig(**config["grapevine_config"])
    server = GrapevineServer(
        cfg, seed=seed % (1 << 31), slo=SloConfig(enforce=False),
        **config.get("server", {}))
    return cfg, server


def check_guarantees(cfg, config: dict) -> None:
    """The configuration file states the guarantees; the server built
    from it must be built to them."""
    g = config["guarantees"]
    built = {"max_messages": cfg.max_messages,
             "max_recipients": cfg.max_recipients,
             "mailbox_cap": cfg.mailbox_cap}
    for k, v in built.items():
        if g[k] != v:
            raise ValueError(f"guarantee {k}: the file says {g[k]}, the "
                             f"server is built with {v}")


def shard_layout_faults(engine, shards: int) -> int:
    """On a mesh: the number of trees whose value plane is NOT held in
    equal parts, one per device."""
    if shards <= 1:
        return 0
    faults = 0
    for tree in (engine.state.rec, engine.state.mb):
        parts = tree.tree_val.addressable_shards
        even = (len(parts) == shards
                and len({str(s.device) for s in parts}) == shards
                and all(s.data.shape[0] * shards == tree.tree_val.shape[0]
                        for s in parts))
        faults += not even
    return faults


def warm_round(server, idents) -> float:
    """One signed op through the scheduler: the round program compiles
    or loads here, in set-up. It is the only shape a cell uses."""
    from grapevine_tpu.session import get_signature_scheme
    from grapevine_tpu.wire import records

    scheme = get_signature_scheme("schnorrkel")
    sk, pub = idents[0]
    challenge = b"\x07" * W.CHALLENGE_SIZE
    sig = scheme.sign(sk, W.SIGNING_CONTEXT, challenge)
    req = records.QueryRequest(
        request_type=W.READ, auth_identity=pub, auth_signature=sig,
        record=records.RequestRecord())
    t0 = time.perf_counter()
    server.scheduler.submit_nowait(
        req, (pub, W.SIGNING_CONTEXT, challenge, sig)).result(timeout=3000)
    return time.perf_counter() - t0


class Tracing:
    """The profiler around a few seconds in the middle of the window."""

    def __init__(self, out_dir: str, t_open: float, seconds: float,
                 params: dict):
        self.dir = out_dir
        length = min(params.get("trace_seconds", 4.0), seconds / 2)
        self.start_at = t_open + (seconds - length) / 2
        self.length = length
        self.t_start = self.t_stop = None
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        import jax

        # the Python tracer would record every call of the served bus
        # (1.5 M events in 4 s) and slow the rounds it is there to time
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        time.sleep(max(0.0, self.start_at - time.perf_counter()))
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.t_start = time.perf_counter()
        time.sleep(self.length)
        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()

    def result(self):
        """(plain trace, t_start, t_stop), once the profiler has
        stopped and written its file."""
        from . import xplane

        self._thread.join()
        files = glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb"))
        if not files:
            return None, self.t_start, self.t_stop
        return (xplane.read(max(files, key=os.path.getmtime)),
                self.t_start, self.t_stop)


class Cell:
    """One served bus built from a cell's files, with the benchmark's
    observers attached and its round program warm. ``drive()`` loads it
    for one window; the control drives several seeds through one."""

    def __init__(self, bench: Benchmark, cell_name: str, seed: int,
                 scratch: str):
        import jax

        self.bench, self.name, self.scratch = bench, cell_name, scratch
        cell = bench.cell(cell_name)
        self.config = bench.config(cell["config"])
        self.traffic = bench.traffic(cell["traffic"])
        self.driver = load_kind("drivers", self.traffic["kind"])
        self.dev = device_info()
        t0 = time.perf_counter()
        self.cfg, self.server = build_server(self.config, seed)
        check_guarantees(self.cfg, self.config)
        engine = self.engine = self.server.engine
        jax.block_until_ready(engine.state)
        self.geometry = round_bytes.round_geometry(engine.ecfg,
                                                   self.cfg.shards)
        say(phase="init", init_s=time.perf_counter() - t0,
            geometry=self.geometry,
            resolved={"vphases_impl": engine.ecfg.vphases_impl,
                      "sort_impl": engine.ecfg.sort_impl,
                      "tree_top_cache_levels":
                          engine.ecfg.tree_top_cache_levels,
                      "evict_every": engine.ecfg.evict_every,
                      "pipeline_depth": engine.pipeline_depth,
                      "bucket_cipher_impl": engine.ecfg.rec.cipher_impl,
                      "scheduler_max_wait_ms":
                          self.server.scheduler.max_wait * 1e3,
                      "scheduler_idle_gap_ms":
                          self.server.scheduler.idle_gap * 1e3},
            state_bytes=sum(x.nbytes for x in jax.tree.leaves(engine.state)))
        self.log = RoundLog(engine)
        # only a driver that reads enqueue -> settle waits pays for them:
        # the wrapper costs every op a callback on the collector thread
        self.waits = (SubmitLog(self.server.scheduler)
                      if getattr(self.driver, "SUBMIT_LOG", False) else None)
        self.warm = False

    def drive(self, seed: int, seconds: float, trace: bool,
              t_process_start: float | None = None,
              ident_seed: int | None = None) -> dict:
        """Set-up for one seed (data, clients, the first round), then the
        window, then the drain. Returns what was observed. The control
        keeps one set of identities (``ident_seed``) over its seeds: a
        mailbox keeps its recipient slot, and there are 2^12 of them."""
        # what a driver gets: the server and its observers, the cell's
        # files, the seeds and the window's length
        ctx = types.SimpleNamespace(
            server=self.server, engine=self.engine, cfg=self.cfg,
            config=self.config, traffic=self.traffic, seed=seed,
            ident_seed=seed if ident_seed is None else ident_seed,
            seconds=seconds, log=self.log, say=say, scratch=self.scratch)
        driver = self.driver
        state = None
        try:
            t0 = time.perf_counter()
            state = driver.prepare(ctx)
            say(phase="prepared", prepare_s=time.perf_counter() - t0)
            if not self.warm:
                first = warm_round(self.server, state["idents"])
                self.warm = True
                say(phase="first_round", compile_or_load_and_run_s=first)
            if hasattr(driver, "ready"):
                # what the driver started in prepare() beside the first
                # round (signatures in worker processes) is collected here
                t0 = time.perf_counter()
                driver.ready(ctx, state)
                say(phase="ready", ready_s=time.perf_counter() - t0)
            first_entry = len(self.log.entries)
            gc.collect()
            gc.freeze()  # set-up's objects leave the collector's sight
            watch = gcwatch.GcWatch()
            t_open = time.perf_counter()
            tracing = (Tracing(os.path.join(self.scratch, "trace"), t_open,
                               seconds, self.traffic) if trace else None)
            watch.start()
            try:
                t_end = driver.run(ctx, state, t_open)
            finally:
                gc_seen = watch.stop()
            peak = memory_peak_bytes()
            plain_trace, tr0, tr1 = (tracing.result() if tracing
                                     else (None, None, None))
            observed = driver.finish(ctx, state)
        finally:
            gc.unfreeze()
            if state is not None:
                driver.stop(ctx, state)
        rounds = self.log.rounds(first_entry)
        return {"ctx": ctx, "observed": observed, "all_rounds": rounds,
                "rounds": [e for e in rounds if e["t_resolved"] is not None
                           and t_open <= e["t_resolved"] <= t_end],
                # the expiry sweeps since the window opened, in a cell
                # whose driver sweeps
                "sweeps": self.log.sweeps(first_entry),
                # the driver closes its window: at ``seconds``, or at
                # the first answers to arrive after that
                "window": (t_open, t_end), "seconds": seconds,
                "setup_s": (t_open - t_process_start
                            if t_process_start is not None else None),
                "batch_size": self.cfg.batch_size, "shards": self.cfg.shards,
                "geometry": self.geometry, "device_kind": self.dev["kind"],
                "submit_waits": self.waits.waits if self.waits else [],
                "collections": gc_seen, "memory_peak_bytes": peak,
                "trace": plain_trace, "trace_window": (tr0, tr1)}

    def judge(self, obs: dict) -> tuple[bool, int, dict]:
        """The oracle over every round and sweep since the server
        started, and the verdict on this window: (correct, failed ops,
        replay)."""
        t0 = time.perf_counter()
        rep = compare.replay(self.log.entries, self.config["guarantees"])
        health = self.engine.health()
        observed = obs["observed"]
        wrong = sum(e["ok"].count(False) for e in obs["all_rounds"])
        numbers = {
            "ops_wrong": rep["ops_wrong"],
            "ops_unanswered": rep["ops_unresolved"] + observed["unanswered"],
            "client_answers_differing": observed.get("client_mismatch", 0),
            "stash_overflow": int(health["stash_overflow"]),
            "message_count_gap":
                abs(health["messages"] - rep["oracle_messages"]),
            "recipient_count_gap":
                abs(health["recipients"] - rep["oracle_recipients"]),
            "trees_not_in_equal_shards":
                shard_layout_faults(self.engine, self.cfg.shards),
            "sweep_evicted_gap": rep["sweep_evicted_gap"],
        }
        correct, lines = compare.verdict(numbers)
        #: each number compared beside its limit, for the result line
        self.compared = {x["compared"]: {"value": x["value"],
                                         "limit": x["limit"]} for x in lines}
        for line in lines:
            say(phase="compare", **line)
        say(phase="oracle", replay_s=time.perf_counter() - t0,
            rounds=len(self.log.entries) - rep["sweeps"],
            sweeps=rep["sweeps"], first_wrong=rep["first_wrong"],
            status_counts=rep["status_counts"],
            oracle_messages=rep["oracle_messages"],
            oracle_recipients=rep["oracle_recipients"],
            stash_occupancy=health["stash_occupancy"])
        failed = (wrong + rep["ops_unresolved"] + observed["unanswered"]
                  + observed.get("client_mismatch", 0))
        return correct, int(failed), rep

    def close(self) -> None:
        if self.waits is not None:
            self.waits.fail_pending()
        self.server.stop()


def period_spread_ms(rounds: list) -> dict | None:
    """How the time between consecutive rounds' answers was spread over
    the window: a few long rounds (a stalled host) and a slower host
    read alike in a rate and apart here."""
    ts = [e["t_resolved"] for e in rounds]
    steps = [1e3 * (b - a) for a, b in zip(ts, ts[1:])]
    if len(steps) < 10:
        return None
    return {"p10": percentile(steps, 10), "p50": percentile(steps, 50),
            "p90": percentile(steps, 90), "max": max(steps)}


def run_cell(bench: Benchmark, cell_name: str, seed: int, seconds: float,
             trace: bool, t_process_start: float, scratch: str) -> dict:
    """One run: the result line as a dict (``correct``, ``attempted``,
    ``failed``, ``metrics``, ``device``, traced ``breakdown``, and last
    ``compared``: each number compared beside its limit)."""
    import jax

    layer_metrics = bench.per_layer(cell_name) if trace else []
    readers = {f["reader"]: load_kind("readers", f["reader"])
               for _, f in layer_metrics}
    say(phase="start", workload=cell_name, seed=seed, seconds=seconds,
        trace=trace, device=device_info(), jax=jax.__version__)
    cell = Cell(bench, cell_name, seed, scratch)
    try:
        obs = cell.drive(seed, seconds, trace, t_process_start)
    finally:
        cell.close()
    # the oracle runs after the window and outside set-up
    correct, failed, _ = cell.judge(obs)
    obs["ledger"] = cell.server.tracer.chrome_trace()["traceEvents"]
    observed = obs["observed"]
    values = cell.driver.end_to_end(obs["ctx"], obs)
    values["setup_s"] = obs["setup_s"]
    say(phase="samples", rounds_in_window=len(obs["rounds"]),
        ops_in_window=sum(len(e["reqs"]) for e in obs["rounds"]),
        window_s=obs["window"][1] - obs["window"][0],
        round_fill_mean=(statistics.fmean(
            len(e["reqs"]) for e in obs["rounds"]) / cell.cfg.batch_size
            if obs["rounds"] else None),
        round_period_ms=period_spread_ms(obs["rounds"]),
        memory_peak_bytes=obs["memory_peak_bytes"], end_to_end=values,
        **obs["collections"], **observed.get("summary", {}))
    device = {**cell.dev, "memory_peak_bytes": obs["memory_peak_bytes"]}
    result = {"correct": bool(correct), "attempted": observed["attempted"],
              "failed": failed, "metrics": {}, "device": device}
    if not trace:
        for m in bench.end_to_end(cell_name):
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
        result["compared"] = cell.compared
        return result
    for entry, spec in layer_metrics:
        value = readers[spec["reader"]].read(spec.get("params", {}), obs)
        if value is not None:
            result["metrics"][entry["name"]] = {"value": value,
                                                "unit": entry["unit"]}
    busy = xplane_busy.device_busy(obs)
    if busy is not None:
        device.update(busy_s=busy["busy_s"], window_s=busy["window_s"])
        result["breakdown"] = busy["breakdown"]
        say(phase="trace", **busy["summary"])
    result["compared"] = cell.compared
    return result
