"""Fleet observatory (obs/fleet.py + leakmon.FleetUniformityMonitor +
load sharding): merge/degrade semantics, the shard label policy, the
cross-shard discrimination drill, replication-lag gauges, and the live
2-member fleet boot (ISSUE 16).

The discrimination drill mirrors test_leakmon.py's shape: honest
uniformly-scheduled N-shard soaks must PASS under every arrival shape
(the false-positive gate — at fleet grain, client traffic shape is
allowed to be anything), while the seeded skewed-scheduler mutant (a
shard dispatches a round only when its own queue is hot) must flip the
fleet verdict to SUSPECT within a bounded number of ticks.
"""

from __future__ import annotations

import http.server
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from grapevine_tpu.config import DurabilityConfig, GrapevineConfig
from grapevine_tpu.engine.checkpoint import DurabilityManager
from grapevine_tpu.engine.state import EngineConfig, init_engine
from grapevine_tpu.load.capacity import analyze_ramp, fleet_capacity
from grapevine_tpu.load.generators import (
    CREATE,
    partition_schedule,
    ramp_to_saturation,
    steady_poisson,
)
from grapevine_tpu.load.harness import ShardedScenarioRunner, ShardRoundDriver
from grapevine_tpu.obs.exporter import render_prometheus
from grapevine_tpu.obs.fleet import (
    FleetAggregator,
    FleetConfig,
    parse_exposition,
)
from grapevine_tpu.obs.leakmon import FleetUniformityMonitor
from grapevine_tpu.obs.registry import TelemetryLeakError, TelemetryRegistry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- helpers ------------------------------------------------------------


def member_text(rounds, qdepth=0, durable=None, applied=None,
                fill_mean=0.5):
    """A minimal member /metrics body with the families the fleet
    consumes."""
    lines = [
        "# HELP grapevine_rounds_total oblivious rounds committed",
        "# TYPE grapevine_rounds_total counter",
        f"grapevine_rounds_total {rounds}",
        "# TYPE grapevine_queue_depth gauge",
        f"grapevine_queue_depth {qdepth}",
        "# TYPE grapevine_load_batch_fill histogram",
        f'grapevine_load_batch_fill_bucket{{le="+Inf"}} {rounds}',
        f"grapevine_load_batch_fill_sum {rounds * fill_mean}",
        f"grapevine_load_batch_fill_count {rounds}",
    ]
    if durable is not None:
        lines += ["# TYPE grapevine_last_durable_seq gauge",
                  f"grapevine_last_durable_seq {durable}"]
    if applied is not None:
        lines += ["# TYPE grapevine_journal_applied_seq gauge",
                  f"grapevine_journal_applied_seq {applied}"]
    return "\n".join(lines) + "\n"


class FakeFleet:
    """Dict-driven fetch injection: members[addr][path] is a str/dict
    body or an Exception to raise."""

    def __init__(self, members: dict):
        self.members = members

    def __call__(self, url: str, timeout_s: float) -> bytes:
        addr, _, path = url.split("//")[1].partition("/")
        doc = self.members[addr].get("/" + path)
        if doc is None:
            return b""
        if isinstance(doc, Exception):
            raise doc
        if isinstance(doc, dict):
            return json.dumps(doc).encode()
        return doc.encode()


# -- exposition parser --------------------------------------------------


def test_parse_exposition_families_and_labels():
    fams = parse_exposition(
        "# HELP m one\n# TYPE m counter\n"
        'm{phase="a b",q="x\\"y"} 3\nm{phase="c"} 4.5\n'
        "# TYPE h histogram\n"
        'h_bucket{le="+Inf"} 2\nh_sum 1.5\nh_count 2\n'
    )
    assert fams["m"]["kind"] == "counter" and fams["m"]["help"] == "one"
    assert fams["m"]["samples"][0] == (
        "m", (("phase", "a b"), ("q", 'x"y')), 3.0)
    # histogram suffixes fold into one family
    assert {s[0] for s in fams["h"]["samples"]} == {
        "h_bucket", "h_sum", "h_count"}


@pytest.mark.parametrize("body", [
    "grapevine_rounds_total",                 # no value (cut mid-line)
    "grapevine_rounds_total 1.2e",            # torn float
    'm{phase="a} 1',                          # unterminated label string
    "m{phase=a} 1",                           # unquoted label value
    "not a metric line at all!",
])
def test_parse_exposition_rejects_malformed_whole(body):
    """Strictness is the degraded-view guard: any malformed line rejects
    the WHOLE scrape (last-good retained) — never a half-merged family."""
    with pytest.raises(ValueError):
        parse_exposition("# TYPE m counter\nm 1\n" + body)


# -- shard label policy (ISSUE 16 satellite 1) --------------------------


def test_shard_label_values_must_be_integer_indices():
    r = TelemetryRegistry()
    r.gauge("grapevine_fleet_ok", "x", labels={"shard": ("0", "1", "2")})
    for bad in ("engine-a.internal", "10.0.0.7:9464", "shard-0", "-1",
                "١"):  # non-ASCII digit must not sneak past isdigit()
        with pytest.raises(TelemetryLeakError):
            TelemetryRegistry().gauge(
                "grapevine_fleet_bad", "x", labels={"shard": (bad,)})


def test_member_label_key_rejected():
    with pytest.raises(TelemetryLeakError):
        TelemetryRegistry().gauge(
            "grapevine_fleet_bad", "x", labels={"member": ("0",)})


# -- merged views -------------------------------------------------------


def _fresh_agg(n=2, interval=1.0, members=None):
    fake = FakeFleet(members or {})
    t = [0.0]
    cfg = FleetConfig(
        members=tuple(f"m{i}:1" for i in range(n)),
        scrape_interval_s=interval,
    )
    agg = FleetAggregator(cfg, clock=lambda: t[0], fetch=fake)
    return agg, fake, t


def test_merged_metrics_inject_shard_label():
    agg, fake, t = _fresh_agg()
    fake.members["m0:1"] = {"/metrics": member_text(8, qdepth=3)}
    fake.members["m1:1"] = {"/metrics": member_text(5, qdepth=1)}
    agg.scrape_once()
    merged = agg.render_merged()
    assert 'grapevine_rounds_total{shard="0"} 8' in merged
    assert 'grapevine_rounds_total{shard="1"} 5' in merged
    # existing labels survive with shard appended
    assert 'grapevine_load_batch_fill_bucket{le="+Inf",shard="0"} 8' in merged
    # HELP/TYPE once per family, not per member
    assert merged.count("# TYPE grapevine_rounds_total counter") == 1
    # the fleet's own registry rides along
    assert 'grapevine_fleet_member_up{shard="0"} 1' in merged
    # a member's own stray shard label is dropped, never re-exported
    fake.members["m0:1"] = {
        "/metrics": '# TYPE x gauge\nx{shard="9"} 1\n'}
    agg.scrape_once()
    assert 'x{shard="0"} 1' in agg.render_merged()


def test_healthz_folds_members_burn_rates_and_uniformity():
    agg, fake, t = _fresh_agg()
    for i, addr in enumerate(("m0:1", "m1:1")):
        fake.members[addr] = {
            "/metrics": member_text(4),
            "/healthz": {"healthy": True, "role": "engine",
                         "slo": {"fast_burn_rate": 0.5 + i,
                                 "slow_burn_rate": 0.25}},
            "/leakaudit": {"verdict": "PASS"},
        }
    agg.scrape_once()
    healthy, detail = agg.healthz()
    assert healthy
    assert detail["role"] == "fleet" and detail["n_members"] == 2
    # merged burn rate = worst member (budgets do not average away)
    assert detail["slo_fast_burn_rate"] == 1.5
    assert [m["shard"] for m in detail["members"]] == [0, 1]
    # one member unhealthy -> fleet unhealthy
    fake.members["m1:1"]["/healthz"] = {"healthy": False, "role": "engine"}
    agg.scrape_once()
    healthy, _ = agg.healthz()
    assert not healthy


def test_standby_fold_counts_roles_and_sums_promotions():
    """_update_standbys (ISSUE 19): ``grapevine_fleet_standbys`` counts
    live un-promoted role=standby members by their /healthz tag (a fed
    standby exports no round counter, so nothing else in the merge
    distinguishes it from a dead shard), ``grapevine_fleet_promotions``
    sums the members' promotion counters, and the fleet /healthz entry
    carries the DR surface an operator pages on."""
    agg, fake, t = _fresh_agg()
    fake.members["m0:1"] = {
        "/metrics": member_text(4),
        "/healthz": {"healthy": True, "role": "engine"},
    }
    standby_metrics = (
        "# TYPE grapevine_replication_promotions_total counter\n"
        "grapevine_replication_promotions_total 0\n")
    fake.members["m1:1"] = {
        "/metrics": standby_metrics,
        "/healthz": {"healthy": True, "role": "standby",
                     "promoted": False, "replication_connected": True,
                     "journal_epoch": 0},
    }
    agg.scrape_once()

    def fleet_gauge(name):
        fams = parse_exposition(agg.render_merged())
        ((_, _, val),) = fams[name]["samples"]
        return val

    assert fleet_gauge("grapevine_fleet_standbys") == 1.0
    assert fleet_gauge("grapevine_fleet_promotions") == 0.0
    healthy, detail = agg.healthz()
    assert healthy and detail["n_standbys"] == 1
    (sb,) = [m for m in detail["members"] if m.get("role") == "standby"]
    assert sb["promoted"] is False
    assert sb["replication_connected"] is True
    assert sb["journal_epoch"] == 0
    # the DR keys are the standby's surface alone
    (eng,) = [m for m in detail["members"] if m.get("role") == "engine"]
    assert "promoted" not in eng and "replication_connected" not in eng

    # promotion flips the member out of the standby count and into the
    # promotions sum — the fleet sees the takeover, not a dead shard
    fake.members["m1:1"]["/metrics"] = standby_metrics.replace(
        "total 0", "total 1")
    fake.members["m1:1"]["/healthz"] = {
        "healthy": True, "role": "standby", "promoted": True,
        "replication_connected": False, "journal_epoch": 1}
    t[0] += 2.0
    agg.scrape_once()
    assert fleet_gauge("grapevine_fleet_standbys") == 0.0
    assert fleet_gauge("grapevine_fleet_promotions") == 1.0
    _, detail = agg.healthz()
    assert detail["n_standbys"] == 0
    (sb,) = [m for m in detail["members"] if m.get("role") == "standby"]
    assert sb["promoted"] is True and sb["journal_epoch"] == 1


def test_leakaudit_folds_member_verdicts():
    agg, fake, t = _fresh_agg()
    fake.members["m0:1"] = {"/metrics": member_text(4),
                            "/leakaudit": {"verdict": "PASS"}}
    fake.members["m1:1"] = {"/metrics": member_text(4),
                            "/leakaudit": {"verdict": "PASS"}}
    agg.scrape_once()
    assert agg.leakaudit()["verdict"] == "PASS"
    fake.members["m1:1"]["/leakaudit"] = {"verdict": "SUSPECT"}
    agg.scrape_once()
    v = agg.leakaudit()
    assert v["verdict"] == "SUSPECT"
    assert v["members"][1]["verdict"] == "SUSPECT"
    # fleet detectors ride the same body
    assert {d["name"] for d in v["fleet_detectors"]} == {
        "cadence_ratio", "fill_load_correlation"}


def test_scrape_attempts_are_traffic_independent():
    """Every member is attempted every cycle in declared order — a down
    or 'boring' member is scraped exactly as often as a hot one (the
    cadence-leak argument, OPERATIONS.md §20)."""
    agg, fake, t = _fresh_agg()
    fake.members["m0:1"] = {"/metrics": member_text(1000, qdepth=99)}
    fake.members["m1:1"] = {"/metrics": ConnectionRefusedError("down")}
    for k in range(7):
        t[0] = float(k)
        agg.scrape_once()
    text = render_prometheus(agg.registry)
    assert 'grapevine_fleet_scrapes_total{shard="0"} 7' in text
    assert 'grapevine_fleet_scrapes_total{shard="1"} 7' in text
    assert 'grapevine_fleet_scrape_failures_total{shard="1"} 7' in text


# -- degraded-scrape edge (ISSUE 16 satellite 3) ------------------------


class _FakeMemberHTTP:
    """A real HTTP member whose behavior is switchable mid-test:
    'ok' serves a valid exposition, 'truncated' a torn body, 'sleep'
    times the client out."""

    def __init__(self):
        self.mode = "ok"
        outer = self

        class Handler(http.server.BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_GET(self):
                if outer.mode == "sleep":
                    time.sleep(1.0)
                    return
                body = member_text(7, qdepth=2).encode()
                if outer.mode == "truncated":
                    # a torn write: headers promise more than arrives,
                    # and the last line is cut mid-sample
                    body = body[: len(body) - 12]
                self.send_response(200)
                self.send_header("Content-Type", "text/plain")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self.httpd = http.server.ThreadingHTTPServer(
            ("127.0.0.1", 0), Handler)
        self.httpd.daemon_threads = True
        self.port = self.httpd.server_address[1]
        threading.Thread(
            target=self.httpd.serve_forever, daemon=True).start()

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()


def test_flapping_member_degrades_without_tearing_merged_view():
    members = [_FakeMemberHTTP(), _FakeMemberHTTP(), _FakeMemberHTTP()]
    try:
        t = [100.0]
        cfg = FleetConfig(
            members=tuple(f"127.0.0.1:{m.port}" for m in members),
            scrape_interval_s=1.0, scrape_timeout_s=0.25,
        )
        agg = FleetAggregator(cfg, clock=lambda: t[0])
        agg.scrape_once()
        assert all(st.up for st in agg._members)
        # member 1 flaps to a truncated body, member 2 to a timeout
        members[1].mode = "truncated"
        members[2].mode = "sleep"
        t[0] = 103.0
        agg.scrape_once()
        ups = [st.up for st in agg._members]
        assert ups == [True, False, False]
        merged = agg.render_merged()
        # last-good families still serve for the down members...
        for shard in (0, 1, 2):
            assert f'grapevine_rounds_total{{shard="{shard}"}} 7' in merged
        # ...with up=0 and a truthful stale age, and healthz degrades
        assert 'grapevine_fleet_member_up{shard="1"} 0' in merged
        assert 'grapevine_fleet_member_up{shard="2"} 0' in merged
        assert 'grapevine_fleet_member_stale_age_seconds{shard="1"} 3' \
            in merged
        healthy, detail = agg.healthz()
        assert not healthy
        assert [m["up"] for m in detail["members"]] == [True, False, False]
        # recovery: the flapper comes back, the view heals
        members[1].mode = "ok"
        t[0] = 104.0
        agg.scrape_once()
        assert agg._members[1].up
    finally:
        for m in members:
            m.close()


# -- cross-shard uniformity drill (satellite 4, fast tier) --------------


def _bursty_arrivals(seed, n=3):
    """Shard 0 breathes hot/cold; the others trickle — the load shape
    most likely to fool a cadence detector."""
    rng = np.random.default_rng(seed)

    def f(k):
        out = []
        for i in range(n):
            out.append(12 if (k // 8) % 2 == 0 else 0) if i == 0 \
                else out.append(int(rng.poisson(2)))
        return out

    return f


def _steady_arrivals(seed, n=3):
    rng = np.random.default_rng(seed)
    return lambda k: [int(rng.poisson(3)) for _ in range(n)]


@pytest.mark.parametrize("shape", ["bursty", "steady"])
def test_honest_uniform_scheduler_passes(shape):
    mon = FleetUniformityMonitor(3)
    drv = ShardRoundDriver(3, mon, policy="uniform")
    arr = (_bursty_arrivals if shape == "bursty" else _steady_arrivals)(11)
    v = drv.run(arr, 200)
    assert v["verdict"] == "PASS", v
    for det in v["detectors"]:
        assert det["verdict"] == "PASS", det


@pytest.mark.parametrize("shape", ["bursty", "steady"])
def test_skewed_scheduler_mutant_suspects_within_64_ticks(shape):
    """The seeded mutant: a shard dispatches only when its own queue is
    hot — per-shard load reaches per-shard cadence, the exact leak the
    fleet detectors exist to flag. Bounded detection: <= 64 ticks."""
    mon = FleetUniformityMonitor(3)
    drv = ShardRoundDriver(3, mon, policy="skewed")
    arr = (_bursty_arrivals if shape == "bursty" else _steady_arrivals)(13)
    v = drv.run(arr, 64, stop_on="SUSPECT")
    assert v["verdict"] == "SUSPECT", v
    assert v["ticks"] <= 64
    tripped = [d for d in v["detectors"] if d["verdict"] == "SUSPECT"]
    assert tripped, v


def test_insufficient_evidence_is_pass():
    """min-samples stance (the PR-2 rule): a young window grades PASS,
    never SUSPECT-by-default."""
    mon = FleetUniformityMonitor(2)
    drv = ShardRoundDriver(2, mon, policy="skewed")
    v = drv.run(_steady_arrivals(7, n=2), 4)
    assert v["verdict"] == "PASS"
    assert all(d["samples"] < d["min_samples"] or d["verdict"] == "PASS"
               for d in v["detectors"])


def test_monitor_tolerates_missing_members_and_counter_resets():
    mon = FleetUniformityMonitor(2)
    base = lambda r: {"rounds_total": float(r),  # noqa: E731
                      "fill_sum": 0.0, "fill_count": 0.0,
                      "queue_depth": 0.0}
    mon.observe_tick([base(1), base(1)])
    mon.observe_tick([base(2), None])        # partial scrape: no evidence
    mon.observe_tick([base(3), base(0)])     # member 1 restarted (reset)
    mon.observe_tick([base(4), base(1)])
    assert mon.verdict()["verdict"] == "PASS"
    with pytest.raises(ValueError):
        mon.observe_tick([base(5)])          # wrong shard count
    with pytest.raises(ValueError):
        FleetUniformityMonitor(1)            # a fleet of one has no pairs


# -- per-shard scenario replay (load/) ----------------------------------


def test_partition_schedule_routes_and_preserves():
    sched = steady_poisson(rate=500.0, duration_s=1.0, seed=3)
    parts = partition_schedule(sched, 3)
    assert sum(p.n_ops for p in parts) == sched.n_ops
    for i, p in enumerate(parts):
        assert p.meta["shard"] == i and p.meta["n_shards"] == 3
        creates = p.kind == CREATE
        assert np.all(p.recipient[creates] % 3 == i)
        assert np.all(p.auth[~creates] % 3 == i)
        # still a valid sorted schedule
        assert np.all(np.diff(p.t_s) >= 0)
    # deterministic: same split twice
    again = partition_schedule(sched, 3)
    assert [p.fingerprint() for p in parts] == \
        [p.fingerprint() for p in again]
    with pytest.raises(ValueError):
        partition_schedule(sched, 0)


class _StubScheduler:
    """submit_nowait -> already-settled future (status SUCCESS)."""

    def __init__(self):
        from concurrent.futures import Future

        from grapevine_tpu.wire import constants as C

        self.n = 0
        self._mk = Future
        self._status = C.STATUS_CODE_SUCCESS

    def submit_nowait(self, req):
        import types

        self.n += 1
        fut = self._mk()
        fut.set_result(types.SimpleNamespace(status_code=self._status))
        return fut


def test_sharded_runner_replays_partition_and_folds_capacity():
    sched = ramp_to_saturation(rate0=400.0, factor=2.0, n_steps=3,
                               step_s=0.08, seed=5)
    stubs = [_StubScheduler(), _StubScheduler()]
    runner = ShardedScenarioRunner(stubs, time_scale=1.0,
                                   settle_timeout_s=5.0)
    results = runner.run(sched)
    assert len(results) == 2
    assert sum(s.n for s in stubs) == sched.n_ops
    analyses = [
        analyze_ramp(r.schedule, r, target_ms=250.0) for r in results
    ]
    fleet = fleet_capacity(analyses)
    assert fleet["shard_count"] == 2
    assert fleet["fleet_knee_ops_per_sec"] == pytest.approx(
        sum(a["knee_ops_per_sec"] for a in analyses))
    assert [s["shard"] for s in fleet["shards"]] == [0, 1]


# -- replication-lag gauges (ISSUE 16 third leg) ------------------------


def test_journal_lag_tracks_follower_through_checkpoint_cycle(tmp_path):
    """Primary journals + checkpoints; a follower recovers from shipped
    copies of the state dir; the fleet lag gauges must read the gap and
    its closure — the hot-standby RPO as a number (ROADMAP item 4)."""
    ecfg = EngineConfig.from_config(GrapevineConfig(
        max_messages=64, max_recipients=8, mailbox_cap=4,
        batch_size=4, stash_size=64, bucket_cipher_rounds=0,
    ))
    state = init_engine(ecfg, seed=5)
    pdir, fdir = str(tmp_path / "primary"), str(tmp_path / "follower")

    reg_p = TelemetryRegistry()
    mgr_p = DurabilityManager(
        DurabilityConfig(state_dir=pdir, checkpoint_every_rounds=4),
        ecfg, registry=reg_p)
    mgr_p.recover(state, lambda s, rec: s)
    for _ in range(3):
        mgr_p.append_sweep(now=1, now_hi=0, period=1)
    assert mgr_p.applied_seq == 3 and mgr_p.status()["applied_seq"] == 3

    def ship_and_recover():
        """Journal shipping, crudely: rsync the sealed state dir and
        replay it on the follower side."""
        if os.path.isdir(fdir):
            shutil.rmtree(fdir)
        shutil.copytree(pdir, fdir)
        reg_f = TelemetryRegistry()
        mgr_f = DurabilityManager(
            DurabilityConfig(state_dir=fdir, checkpoint_every_rounds=4),
            ecfg, registry=reg_f)
        mgr_f.recover(state, lambda s, rec: s)
        mgr_f.close()
        return reg_f

    reg_f = ship_and_recover()  # follower caught up at seq 3

    # primary advances THROUGH a checkpoint cycle: 3 more records trip
    # checkpoint_every_rounds=4, sealing at seq 6 and rolling the journal
    for _ in range(3):
        mgr_p.append_sweep(now=2, now_hi=0, period=1)
    assert mgr_p.should_checkpoint()
    mgr_p.checkpoint(state)
    assert mgr_p.ckpt_seq == 6 and mgr_p.applied_seq == 6

    t = [50.0]
    agg = FleetAggregator(
        FleetConfig(members=("p:1", "f:1")),
        clock=lambda: t[0],
        fetch=FakeFleet({
            "p:1": {"/metrics": render_prometheus(reg_p)},
            "f:1": {"/metrics": render_prometheus(reg_f)},
        }),
    )
    agg.scrape_once()
    own = render_prometheus(agg.registry)
    assert 'grapevine_fleet_journal_lag_seq{shard="0"} 0' in own
    assert 'grapevine_fleet_journal_lag_seq{shard="1"} 3' in own

    # the follower re-ships past the checkpoint: recovery loads the
    # sealed checkpoint (seq 6) and the lag closes
    reg_f2 = ship_and_recover()
    t[0] = 55.0
    agg._fetch = FakeFleet({
        "p:1": {"/metrics": render_prometheus(reg_p)},
        "f:1": {"/metrics": render_prometheus(reg_f2)},
    })
    agg.scrape_once()
    own = render_prometheus(agg.registry)
    assert 'grapevine_fleet_journal_lag_seq{shard="1"} 0' in own
    assert 'grapevine_fleet_journal_lag_seconds{shard="1"} 0' in own
    mgr_p.close()


def test_journal_follow_is_read_only(tmp_path):
    from grapevine_tpu.engine.journal import BatchJournal

    ecfg = EngineConfig.from_config(GrapevineConfig(
        max_messages=64, max_recipients=8, mailbox_cap=4,
        batch_size=4, stash_size=64, bucket_cipher_rounds=0,
    ))
    root = bytes(range(32))
    j = BatchJournal(str(tmp_path), root, ecfg)
    list(j.replay())
    j.open_for_append()
    j.append_sweep(1, 0, 1)
    j.append_sweep(2, 0, 1)
    with pytest.raises(RuntimeError, match="read-only"):
        list(j.follow())  # open for append: not a follower
    f = BatchJournal(str(tmp_path), root, ecfg)
    assert [r.seq for r in f.follow()] == [1, 2]
    j.append_sweep(3, 0, 1)
    # a later follow picks up newly shipped frames
    assert [r.seq for r in f.follow(after_seq=2)] == [3]
    j.close()


# -- live 2-member fleet (satellite 2 + acceptance) ---------------------


def _wait_port_line(proc, needle, timeout=120.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            if proc.poll() is not None:
                raise AssertionError(
                    f"process died rc={proc.returncode}: "
                    f"{proc.stderr.read()[-2000:]}")
            time.sleep(0.05)
            continue
        if needle in line:
            return line
    raise AssertionError(f"no {needle!r} line within {timeout}s")


def _get(url, timeout=5.0):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return resp.status, resp.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_live_two_member_fleet_boots_merges_and_drains():
    """Two engine-role processes + the fleet role, end to end: merged
    /metrics with shard-labeled families, merged /healthz, fleet
    /leakaudit, then SIGTERM-drain to exit 0 for all three."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    engine_argv = [
        sys.executable, "-m", "grapevine_tpu.server.cli",
        "--role", "engine", "--engine-listen", "127.0.0.1:0",
        "--msg-capacity", "64", "--recipient-capacity", "8",
        "--batch-size", "4", "--metrics-port", "0",
    ]
    procs = []
    try:
        for seed in ("0", "1"):
            procs.append(subprocess.Popen(
                engine_argv + ["--seed", seed], cwd=REPO, env=env,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
        mports = []
        for p in procs:
            _wait_port_line(p, "engine tier listening")
            line = _wait_port_line(p, "metrics endpoint on port")
            mports.append(int(line.rsplit(" ", 1)[1]))
        fport = _free_port()
        fleet = subprocess.Popen(
            [sys.executable, "-m", "grapevine_tpu.server.cli",
             "--role", "fleet",
             "--fleet-members",
             ",".join(f"127.0.0.1:{mp}" for mp in mports),
             "--fleet-scrape-interval", "0.2",
             "--fleet-port", str(fport)],
            cwd=REPO, env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        procs.append(fleet)
        _wait_port_line(fleet, "fleet aggregator on port", timeout=60)
        deadline = time.monotonic() + 30
        merged = ""
        while time.monotonic() < deadline:
            _, merged = _get(f"http://127.0.0.1:{fport}/metrics")
            if ('grapevine_rounds_total{shard="0"}' in merged
                    and 'grapevine_rounds_total{shard="1"}' in merged):
                break
            time.sleep(0.3)
        assert 'grapevine_rounds_total{shard="0"}' in merged, merged[:800]
        assert 'grapevine_rounds_total{shard="1"}' in merged
        assert 'grapevine_fleet_member_up{shard="0"} 1' in merged
        assert 'grapevine_fleet_member_up{shard="1"} 1' in merged
        code, body = _get(f"http://127.0.0.1:{fport}/healthz")
        hz = json.loads(body)
        assert code == 200 and hz["healthy"] and hz["role"] == "fleet"
        assert [m["up"] for m in hz["members"]] == [True, True]
        code, body = _get(f"http://127.0.0.1:{fport}/leakaudit")
        assert code == 200 and json.loads(body)["verdict"] == "PASS"
        # SIGTERM-drain: all three exit 0
        for p in reversed(procs):
            p.send_signal(signal.SIGTERM)
        for p in procs:
            assert p.wait(timeout=60) == 0, p.stderr.read()[-2000:]
        procs = []
    finally:
        for p in procs:
            p.kill()
            p.wait(timeout=10)
