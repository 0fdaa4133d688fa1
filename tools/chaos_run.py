#!/usr/bin/env python
"""Crash-recovery chaos harness (the PR-4 acceptance gate).

Runs a deterministic engine workload in a child process with durability
on, SIGKILLs the child at a randomized point — either an armed
fault-injection site inside the journal/checkpoint protocol
(testing/faults.py) or a random wall-clock timer — restarts it until the
workload completes, and asserts the run is **bit-identical** to an
uninterrupted oracle:

- every per-round response hash the (possibly many) child incarnations
  recorded matches the oracle's hash for that round;
- the final recovered engine state equals the oracle's final state,
  byte for byte (engine/checkpoint.py's canonical serialization);
- the leak monitor verdict stays PASS on the recovered engine
  (obliviousness survives recovery);
- no run ever half-loads a torn checkpoint or journal file (a child
  incarnation failing with anything but SIGKILL fails the trial).

Usage::

    JAX_PLATFORMS=cpu python tools/chaos_run.py --trials 50
    JAX_PLATFORMS=cpu python tools/chaos_run.py --points   # one trial
                                                           # per fault site
    JAX_PLATFORMS=cpu python tools/chaos_run.py --standby --points
        # hot-standby mode: SIGKILL the primary once at every fault
        # site and verify the promoted replica instead of a restart

The child re-enters this file with ``--child``; parent and children
share JAX's persistent compilation cache at its one fixed place
(``grapevine_tpu.config.setup_compile_cache``), so relaunches do not
re-pay the compile. A SIGKILLed child can at worst leave a torn entry
there, which jax detects on read and recompiles (see that helper).

A CPU tool: the parent (oracle, in-process standby replica) and every
child run JAX at the same time, which only the CPU platform allows, so
``main`` pins it for all of them.
"""

from __future__ import annotations

import argparse
import hashlib
import os
import random
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

NOW0 = 1_700_000_000
ENGINE_SEED = 3
SWEEP_PERIOD = 10_000
MAX_RESTARTS = 60


def _config(posmap_impl: str | None = None,
            tree_top_cache_levels: int | None = None,
            pipeline_depth: int | None = None,
            shards: int | None = None):
    from grapevine_tpu.config import GrapevineConfig

    return GrapevineConfig(
        max_messages=64, max_recipients=8, mailbox_cap=4,
        batch_size=4, stash_size=64, bucket_cipher_rounds=0,
        posmap_impl=posmap_impl,
        tree_top_cache_levels=tree_top_cache_levels,
        pipeline_depth=pipeline_depth,
        shards=shards or 1,
    )


def _key(n: int) -> bytes:
    return bytes([n & 0xFF, (n >> 8) & 0xFF, n ^ 0x5A]) + b"\x01" * 29


def build_schedule(seed: int, n_events: int):
    """Deterministic event list; event i carries journal seq i+1.

    Requests avoid response-derived inputs (zero-id READ/DELETE pops
    instead of id lookups) so the schedule is a pure function of the
    seed — any incarnation of the child reconstructs it identically."""
    from grapevine_tpu.wire import constants as C
    from grapevine_tpu.wire.records import QueryRequest, RequestRecord

    rng = random.Random(seed)
    events = []
    for i in range(n_events):
        if i % 7 == 5:
            events.append(("sweep", NOW0 + i, SWEEP_PERIOD))
            continue
        reqs = []
        for _ in range(rng.randrange(1, 5)):
            c = rng.random()
            if c < 0.6:
                rt, rcp = C.REQUEST_TYPE_CREATE, _key(rng.randrange(1, 6))
            elif c < 0.9:
                rt, rcp = C.REQUEST_TYPE_READ, C.ZERO_PUBKEY
            else:
                rt, rcp = C.REQUEST_TYPE_DELETE, C.ZERO_PUBKEY
            reqs.append(QueryRequest(
                request_type=rt,
                auth_identity=_key(rng.randrange(1, 6)),
                auth_signature=b"\x01" * C.SIGNATURE_SIZE,
                record=RequestRecord(
                    msg_id=C.ZERO_MSG_ID,
                    recipient=rcp,
                    payload=bytes([rng.randrange(256)]) * C.PAYLOAD_SIZE,
                ),
            ))
        events.append(("round", NOW0 + i, reqs))
    return events


def _resp_hash(resps) -> str:
    return hashlib.sha256(b"".join(r.pack() for r in resps)).hexdigest()


def _run_events(engine, events, start: int, progress=None):
    """Drive ``events[start:]``; append ``seq hash`` progress lines.

    Pipelined per the engine's resolved ``pipeline_depth``: up to depth
    rounds stay dispatched-but-unresolved ACROSS events (the engine's
    async path with a bounded ledger — the scheduler's discipline), so
    the journal/dispatch crash sites fire while earlier rounds are
    genuinely mid-flight on the device. Rounds resolve oldest-first (=
    dispatch = journal order); a crash loses only the progress lines of
    rounds that never resolved, whose recovery the final-state hash
    still fully covers. Depth 1 keeps the ledger empty at every event
    boundary — the serial pre-PR-10 program, bit for bit."""
    depth = max(1, getattr(engine, "pipeline_depth", 1))
    ledger: list = []  # (event seq, PendingRound) in dispatch order

    def settle_one():
        seq, pending = ledger.pop(0)
        h = _resp_hash(pending.resolve())
        if progress is not None:
            progress.write(f"{seq} {h}\n")
            progress.flush()

    for i in range(start, len(events)):
        ev = events[i]
        # the pipeline bound: at depth d, dispatch (or sweep — it runs
        # synchronously under the same engine lock) with at most d-1
        # rounds already in flight
        while len(ledger) > depth - 1:
            settle_one()
        if ev[0] == "round":
            ledger.append(
                (i + 1, engine.handle_queries_async(ev[2], ev[1]))
            )
        else:
            engine.expire(ev[1], period=ev[2])
            if progress is not None:
                progress.write(f"{i + 1} sweep\n")
                progress.flush()
    while ledger:
        settle_one()


def run_child(args) -> int:
    from grapevine_tpu.config import DurabilityConfig, setup_compile_cache
    from grapevine_tpu.engine.batcher import GrapevineEngine
    from grapevine_tpu.engine.checkpoint import state_to_bytes
    from grapevine_tpu.obs.leakmon import EngineLeakMonitor, LeakMonitorConfig

    setup_compile_cache()
    dcfg = DurabilityConfig(
        state_dir=args.state_dir,
        checkpoint_every_rounds=args.checkpoint_every,
        journal_fsync_every=1,
    )
    engine = GrapevineEngine(
        _config(args.posmap_impl, args.tree_top_cache_levels,
                args.pipeline_depth, args.shards),
        seed=ENGINE_SEED, durability=dcfg,
    )
    shipper = None
    if args.replicate_to:
        # hot-standby chaos (--standby): this child is the PRIMARY,
        # streaming every sealed frame to the parent's replica until
        # the armed fault SIGKILLs it mid-protocol
        from grapevine_tpu.engine.replication import JournalShipper

        shipper = JournalShipper(engine, args.replicate_to)
        shipper.start()
    monitor = EngineLeakMonitor.for_engine(
        engine, LeakMonitorConfig(window_rounds=64)
    )
    engine.attach_leakmon(monitor)
    if shipper is not None:
        monitor.attach_shipper(shipper)
    # the PR-6 observability stack rides every chaos incarnation (as it
    # does in serving): tracing/SLO must never perturb recovery
    # bit-equality, and the tracer's schema check runs on real
    # journal/checkpoint-bearing ledgers here
    from grapevine_tpu.obs.slo import SloTracker
    from grapevine_tpu.obs.tracer import RoundTracer

    engine.attach_tracer(
        RoundTracer(capacity=64, registry=engine.metrics.registry)
    )
    engine.attach_slo(SloTracker(registry=engine.metrics.registry))
    events = build_schedule(args.schedule_seed, args.events)
    # events[:start] are already durable: one journal frame per event
    start = engine.durability.seq
    with open(args.progress, "a") as pf:
        _run_events(engine, events, start, pf)
        monitor.close()  # drain the detector queue before the verdict
        verdict = monitor.verdict()["verdict"]
        final = hashlib.sha256(
            state_to_bytes(engine.ecfg, engine.state)
        ).hexdigest()
        pf.write(f"leakmon {verdict}\n")
        pf.write(f"final {final}\n")
        pf.flush()
    if shipper is not None:
        shipper.close()
    engine.close()
    return 0


def oracle(schedule_seed: int, n_events: int, posmap_impl: str | None = None,
           tree_top_cache_levels: int | None = None):
    """Uninterrupted in-process run: per-seq hashes + final state hash.

    Always serial (pipeline_depth=1) and single-chip (shards=1): the
    oracle is the pre-PR-10 resolve-before-next-dispatch program on one
    device, so a ``--pipeline-depth 2`` or ``--shards N`` chaos run
    proves the pipelined / mesh-sharded child recovers bit-identical to
    the SERIAL SINGLE-CHIP ground truth — composition equivalence and
    crash equivalence in one gate."""
    from grapevine_tpu.engine.batcher import GrapevineEngine
    from grapevine_tpu.engine.checkpoint import state_to_bytes

    engine = GrapevineEngine(
        _config(posmap_impl, tree_top_cache_levels, pipeline_depth=1),
        seed=ENGINE_SEED,
    )
    events = build_schedule(schedule_seed, n_events)
    hashes: dict[int, str] = {}
    for i, ev in enumerate(events):
        if ev[0] == "round":
            hashes[i + 1] = _resp_hash(engine.handle_queries(ev[2], ev[1]))
        else:
            engine.expire(ev[1], period=ev[2])
            hashes[i + 1] = "sweep"
    final = hashlib.sha256(
        state_to_bytes(engine.ecfg, engine.state)
    ).hexdigest()
    return hashes, final


def _parse_progress(path: str):
    seq_hashes: dict[int, str] = {}
    finals, leakmons = [], []
    try:
        with open(path) as fh:
            lines = fh.read().splitlines()
    except FileNotFoundError:
        return seq_hashes, finals, leakmons
    for line in lines:
        parts = line.split()
        if len(parts) != 2:
            continue  # torn progress line from a mid-write kill
        tag, val = parts
        if tag == "final":
            finals.append(val)
        elif tag == "leakmon":
            leakmons.append(val)
        elif tag.isdigit():
            seq_hashes[int(tag)] = val
    return seq_hashes, finals, leakmons


def run_trial(trial: int, mode: str, rng: random.Random, args,
              oracle_hashes, oracle_final) -> list[str]:
    """One kill-recover-verify trial; returns a list of failure strings."""
    errors: list[str] = []
    with tempfile.TemporaryDirectory(prefix=f"chaos{trial}-") as state_dir:
        progress = os.path.join(state_dir, "progress.log")
        child_cmd = [
            sys.executable, os.path.abspath(__file__), "--child",
            "--state-dir", state_dir, "--progress", progress,
            "--events", str(args.events),
            "--schedule-seed", str(args.schedule_seed),
            "--checkpoint-every", str(args.checkpoint_every),
        ]
        if args.posmap_impl:
            child_cmd += ["--posmap-impl", args.posmap_impl]
        if args.tree_top_cache_levels is not None:
            child_cmd += ["--tree-top-cache-levels",
                          str(args.tree_top_cache_levels)]
        if args.pipeline_depth is not None:
            child_cmd += ["--pipeline-depth", str(args.pipeline_depth)]
        if args.shards is not None:
            child_cmd += ["--shards", str(args.shards)]
        base_env = dict(os.environ, JAX_PLATFORMS="cpu")
        base_env.pop("GRAPEVINE_FAULTS", None)
        if (args.shards or 1) > 1:
            # the child needs a mesh: force the virtual CPU device
            # count (before its jax init) unless the caller already set
            # one — the ORACLE stays single-chip in this process
            flags = base_env.get("XLA_FLAGS", "")
            if "xla_force_host_platform_device_count" not in flags:
                base_env["XLA_FLAGS"] = (
                    f"{flags} --xla_force_host_platform_device_count="
                    f"{args.shards}"
                ).strip()
        kills = 0
        launch = 0
        while True:
            env = dict(base_env)
            timer_kill = None
            if launch == 0:
                if mode == "timer":
                    timer_kill = rng.uniform(1.0, args.timer_max_s)
                else:
                    # checkpoint sites fire once per --checkpoint-every
                    # records, append sites once per record — scale the
                    # trigger count so the fault actually lands mid-run
                    if mode.startswith("checkpoint."):
                        cap = max(2, args.events // args.checkpoint_every)
                    else:
                        cap = max(2, args.events // 2)
                    env["GRAPEVINE_FAULTS"] = f"{mode}={rng.randrange(1, cap)}"
            proc = subprocess.Popen(
                child_cmd, env=env, cwd=REPO,
                stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
            )
            if timer_kill is not None:
                try:
                    proc.wait(timeout=timer_kill)
                except subprocess.TimeoutExpired:
                    proc.send_signal(signal.SIGKILL)
            _, err = proc.communicate()
            rc = proc.returncode
            if rc == 0:
                break
            if rc != -signal.SIGKILL:
                errors.append(
                    f"trial {trial} [{mode}]: child exited rc={rc} "
                    f"(want clean or SIGKILL): {err.decode()[-2000:]}"
                )
                return errors
            kills += 1
            launch += 1
            if launch > MAX_RESTARTS:
                errors.append(
                    f"trial {trial} [{mode}]: no clean run after "
                    f"{MAX_RESTARTS} restarts"
                )
                return errors
        seq_hashes, finals, leakmons = _parse_progress(progress)
        for seq, h in sorted(seq_hashes.items()):
            if oracle_hashes.get(seq) != h:
                errors.append(
                    f"trial {trial} [{mode}]: responses for round {seq} "
                    f"diverge from the uninterrupted run"
                )
        if not finals or finals[-1] != oracle_final:
            errors.append(
                f"trial {trial} [{mode}]: final recovered state is not "
                f"bit-identical to the uninterrupted run"
            )
        if not leakmons or leakmons[-1] != "PASS":
            errors.append(
                f"trial {trial} [{mode}]: leak monitor verdict "
                f"{leakmons[-1] if leakmons else 'missing'} (want PASS)"
            )
        if not errors:
            print(
                f"trial {trial:3d} [{mode:>26s}]: PASS "
                f"({kills} kill{'s' if kills != 1 else ''}, "
                f"{len(seq_hashes)}/{len(oracle_hashes)} rounds recorded)",
                flush=True,
            )
    return errors


def run_standby_trial(trial: int, mode: str, rng: random.Random, args,
                      oracle_hashes, oracle_final) -> list[str]:
    """One kill-the-primary takeover trial (--standby).

    The parent process hosts a live :class:`StandbyReplica` (same
    geometry as the oracle: serial, single-chip — so its jitted
    programs are already warm from the oracle run, which is the hot
    part of "hot standby"). The child is the PRIMARY: it runs the
    schedule with ``--replicate-to`` pointed at the replica and is
    SIGKILLed ONCE at the armed fault site — including mid-fsync —
    with no restart. The parent then promotes the replica
    (fencing the dead primary's state dir, draining its durable tail
    off disk), drives the REMAINING schedule on the promoted engine,
    and holds the whole run to the uninterrupted serial oracle:
    per-round response hashes, final state bit-identity, leakmon PASS.
    RPO 0 for durable frames and RTO = the measured promote() wall
    time, printed per trial."""
    errors: list[str] = []
    from grapevine_tpu.config import DurabilityConfig
    from grapevine_tpu.engine.checkpoint import state_to_bytes
    from grapevine_tpu.engine.journal import BatchJournal, JournalError
    from grapevine_tpu.engine.replication import StandbyReplica
    from grapevine_tpu.obs.leakmon import EngineLeakMonitor, LeakMonitorConfig

    events = build_schedule(args.schedule_seed, args.events)
    with tempfile.TemporaryDirectory(prefix=f"chaos{trial}-") as root:
        primary_dir = os.path.join(root, "primary")
        standby_dir = os.path.join(root, "standby")
        os.makedirs(primary_dir)
        os.makedirs(standby_dir)
        progress = os.path.join(root, "progress.log")
        # replication's standing requirement (engine/replication.py,
        # OPERATIONS.md §23): primary and standby share the root seal
        # key — a standby with its own key cannot unseal a single
        # shipped frame. Provision one key into both dirs up front,
        # exactly what a production secret mount does.
        key = bytes(rng.randrange(256) for _ in range(32))
        for d in (primary_dir, standby_dir):
            kp = os.path.join(d, "root.key")
            with open(kp, "wb") as fh:
                fh.write(key)
            os.chmod(kp, 0o600)
        replica = StandbyReplica(
            _config(args.posmap_impl, args.tree_top_cache_levels,
                    pipeline_depth=1, shards=1),
            seed=ENGINE_SEED,
            durability=DurabilityConfig(
                state_dir=standby_dir,
                checkpoint_every_rounds=args.checkpoint_every,
                journal_fsync_every=1,
            ),
        )
        try:
            port = replica.listen()
            child_cmd = [
                sys.executable, os.path.abspath(__file__), "--child",
                "--state-dir", primary_dir, "--progress", progress,
                "--events", str(args.events),
                "--schedule-seed", str(args.schedule_seed),
                "--checkpoint-every", str(args.checkpoint_every),
                "--replicate-to", f"127.0.0.1:{port}",
            ]
            if args.posmap_impl:
                child_cmd += ["--posmap-impl", args.posmap_impl]
            if args.tree_top_cache_levels is not None:
                child_cmd += ["--tree-top-cache-levels",
                              str(args.tree_top_cache_levels)]
            if args.pipeline_depth is not None:
                child_cmd += ["--pipeline-depth", str(args.pipeline_depth)]
            if args.shards is not None:
                child_cmd += ["--shards", str(args.shards)]
            env = dict(os.environ, JAX_PLATFORMS="cpu")
            env.pop("GRAPEVINE_FAULTS", None)
            if (args.shards or 1) > 1:
                flags = env.get("XLA_FLAGS", "")
                if "xla_force_host_platform_device_count" not in flags:
                    env["XLA_FLAGS"] = (
                        f"{flags} --xla_force_host_platform_device_count="
                        f"{args.shards}"
                    ).strip()
            timer_kill = None
            if mode == "timer":
                timer_kill = rng.uniform(1.0, args.timer_max_s)
            else:
                if mode.startswith("checkpoint."):
                    cap = max(2, args.events // args.checkpoint_every)
                else:
                    cap = max(2, args.events // 2)
                env["GRAPEVINE_FAULTS"] = f"{mode}={rng.randrange(1, cap)}"
            proc = subprocess.Popen(
                child_cmd, env=env, cwd=REPO,
                stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
            )
            if timer_kill is not None:
                try:
                    proc.wait(timeout=timer_kill)
                except subprocess.TimeoutExpired:
                    proc.send_signal(signal.SIGKILL)
            _, err = proc.communicate()
            rc = proc.returncode
            if rc not in (0, -signal.SIGKILL):
                errors.append(
                    f"trial {trial} [standby:{mode}]: primary exited "
                    f"rc={rc} (want clean or SIGKILL): "
                    f"{err.decode()[-2000:]}"
                )
                return errors
            killed = rc == -signal.SIGKILL
            # fenced takeover: plant the epoch fence in the dead
            # primary's dir, drain its durable tail — the measured RTO
            info = replica.promote(primary_state_dir=primary_dir)
            eng = replica.engine
            monitor = EngineLeakMonitor.for_engine(
                eng, LeakMonitorConfig(window_rounds=64)
            )
            eng.attach_leakmon(monitor)
            start = eng.durability.seq
            with open(progress, "a") as pf:
                _run_events(eng, events, start, pf)
                monitor.close()
                verdict = monitor.verdict()["verdict"]
                final = hashlib.sha256(
                    state_to_bytes(eng.ecfg, eng.state)
                ).hexdigest()
                pf.write(f"leakmon {verdict}\n")
                pf.write(f"final {final}\n")
                pf.flush()
            # split-brain guard, live: a revived incarnation of the
            # killed primary must be refused at journal-open time
            try:
                stale = BatchJournal(primary_dir, replica.dm.root_key,
                                     replica.dm.ecfg)
                for _rec in stale.replay():
                    pass
                stale.open_for_append()
            except JournalError:
                pass
            else:
                errors.append(
                    f"trial {trial} [standby:{mode}]: revived stale "
                    "primary was NOT refused by the epoch fence"
                )
        finally:
            replica.close()
        seq_hashes, finals, leakmons = _parse_progress(progress)
        for seq, h in sorted(seq_hashes.items()):
            if oracle_hashes.get(seq) != h:
                errors.append(
                    f"trial {trial} [standby:{mode}]: responses for "
                    f"round {seq} diverge from the uninterrupted run"
                )
        if not finals or finals[-1] != oracle_final:
            errors.append(
                f"trial {trial} [standby:{mode}]: promoted final state "
                "is not bit-identical to the uninterrupted run"
            )
        if not leakmons or leakmons[-1] != "PASS":
            errors.append(
                f"trial {trial} [standby:{mode}]: leak monitor verdict "
                f"{leakmons[-1] if leakmons else 'missing'} (want PASS)"
            )
        if not errors:
            print(
                f"trial {trial:3d} [{mode:>26s}]: PASS "
                f"({'killed' if killed else 'clean'}, promoted epoch "
                f"{info['epoch']}, drained {info['drained_frames']} "
                f"durable frames, rto {info['rto_seconds'] * 1e3:.0f}ms, "
                f"{len(seq_hashes)}/{len(oracle_hashes)} rounds recorded)",
                flush=True,
            )
    return errors


def run_trials(n_trials: int, args=None, modes=None) -> list[str]:
    """Run ``n_trials`` randomized trials (or one per entry of
    ``modes``); returns accumulated failures. Importable by the slow
    chaos test (tests/test_chaos_recovery.py)."""
    from grapevine_tpu.testing.faults import ALL_POINTS

    args = args or parse_args([])
    rng = random.Random(args.seed)
    from grapevine_tpu.config import setup_compile_cache

    setup_compile_cache()  # the oracle's and the standby's compiles
    t0 = time.monotonic()
    oracle_hashes, oracle_final = oracle(
        args.schedule_seed, args.events, args.posmap_impl,
        args.tree_top_cache_levels,
    )
    print(f"oracle: {len(oracle_hashes)} events in "
          f"{time.monotonic() - t0:.1f}s", flush=True)
    if modes is None:
        modes = [
            rng.choice(list(ALL_POINTS) + ["timer"]) for _ in range(n_trials)
        ]
    failures: list[str] = []
    trial_fn = run_standby_trial if args.standby else run_trial
    for trial, mode in enumerate(modes):
        failures.extend(
            trial_fn(trial, mode, rng, args, oracle_hashes, oracle_final)
        )
    return failures


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--child", action="store_true")
    p.add_argument("--state-dir")
    p.add_argument("--progress")
    p.add_argument("--trials", type=int, default=50)
    p.add_argument("--points", action="store_true",
                   help="one trial per fault-injection site instead of "
                   "randomized trials")
    p.add_argument("--events", type=int, default=24)
    p.add_argument("--schedule-seed", type=int, default=11)
    p.add_argument("--checkpoint-every", type=int, default=5)
    p.add_argument("--seed", type=int, default=1234)
    p.add_argument("--timer-max-s", type=float, default=12.0)
    p.add_argument("--standby", action="store_true",
                   help="hot-standby takeover trials instead of "
                   "restart-in-place: the child primary ships its "
                   "journal to an in-parent StandbyReplica "
                   "(engine/replication.py) and is SIGKILLed ONCE at "
                   "the armed site with no restart; the parent "
                   "promotes (fenced), drives the remaining schedule "
                   "on the promoted engine, and holds the whole run "
                   "to the serial oracle bit-for-bit with leakmon "
                   "PASS. Prints the measured RTO per trial")
    p.add_argument("--replicate-to", default=None,
                   help="(child) ship the journal to this host:port "
                   "while running — set by --standby trials")
    p.add_argument("--posmap-impl", default=None,
                   choices=["flat", "recursive"],
                   help="position-map implementation under test "
                   "(oram/posmap.py); default = the engine auto (flat)")
    p.add_argument("--tree-top-cache-levels", type=int, default=None,
                   help="tree-top cache depth under test "
                   "(oram/path_oram.py); default = the engine auto")
    p.add_argument("--shards", type=int, default=None,
                   help="bucket-axis shard count under test (parallel/"
                   "mesh.py via engine/batcher.py): the child runs the "
                   "sharded step on a virtual CPU mesh "
                   "(the parent exports the device-count XLA flag), "
                   "while the ORACLE stays single-chip — so every "
                   "trial proves crash recovery AND sharded<->single-"
                   "chip bit-equivalence in one gate (the pipeline-"
                   "depth discipline). Default = engine auto (1)")
    p.add_argument("--pipeline-depth", type=int, default=None,
                   choices=[1, 2],
                   help="round-pipeline depth under test (engine/"
                   "batcher.py): 2 keeps a round mid-flight on the "
                   "device while the next one journals + fsyncs — the "
                   "crash windows PR 10 opened; the oracle always runs "
                   "serial (depth 1), so the trial also proves depth "
                   "bit-equivalence. Default = the engine auto")
    return p.parse_args(argv)


def main(argv=None) -> int:
    os.environ["JAX_PLATFORMS"] = "cpu"  # before anything loads jax
    args = parse_args(argv if argv is not None else sys.argv[1:])
    if args.child:
        return run_child(args)
    from grapevine_tpu.testing.faults import ALL_POINTS

    modes = list(ALL_POINTS) + ["timer"] if args.points else None
    failures = run_trials(args.trials, args, modes=modes)
    for f in failures:
        print(f"CHAOS FAILURE: {f}", file=sys.stderr)
    n = len(modes) if modes else args.trials
    print(f"chaos: {n} trials, {len(failures)} failure(s)")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
