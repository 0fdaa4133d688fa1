"""Role/flag validation of the server CLI (server/cli.py): misapplied
flags fail loudly by argv-token presence, even at default values."""

from __future__ import annotations

import pytest

from grapevine_tpu.server import cli


def _check(argv):
    parser = cli.build_parser()
    args = parser.parse_args(argv)
    cli._reject_misapplied_flags(parser, args, argv)
    return args


@pytest.mark.parametrize("argv", [
    ["--role", "engine", "--identity-seed", "ab" * 32],
    ["--role", "engine", "--tls-cert", "c.pem"],
    # explicitly supplied WITH the default value still rejects
    ["--role", "engine", "--listen", "insecure-grapevine://0.0.0.0:3229"],
    ["--role", "frontend", "--seed", "0"],
    ["--role", "frontend", "--expiry-period", "60"],
    ["--role", "mono", "--engine", "x:1"],
    ["--role", "mono", "--engine-listen", "127.0.0.1:0"],
    # observability flags observe the device round: frontend rejects
    # them even at their default values (ISSUE 6 satellite)
    ["--role", "frontend", "--trace-ring-size", "512"],
    ["--role", "frontend", "--slo-commit-p99-ms", "250.0"],
    ["--role", "frontend", "--profile-enable"],
    # engine geometry lives with the device: a frontend supplying
    # --posmap-impl would silently configure nothing (ISSUE 7 satellite)
    ["--role", "frontend", "--posmap-impl", "recursive"],
    ["--role", "frontend", "--posmap-impl", "flat"],
    # same for the tree-top cache depth (ISSUE 8 satellite) — rejected
    # even at the explicit "off" value
    ["--role", "frontend", "--tree-top-cache-levels", "4"],
    ["--role", "frontend", "--tree-top-cache-levels", "0"],
    # the round pipeline runs on the device-owning role (ISSUE 10
    # satellite) — rejected even at the explicit serial value
    ["--role", "frontend", "--pipeline-depth", "2"],
    ["--role", "frontend", "--pipeline-depth", "1"],
    # the bucket-tree shard count is engine geometry (ISSUE 18): a
    # frontend supplying it would silently shard nothing — rejected
    # even at the explicit single-chip value, and on the fleet role
    ["--role", "frontend", "--shards", "2"],
    ["--role", "frontend", "--shards", "1"],
    ["--role", "fleet", "--fleet-members", "h0:1", "--shards", "2"],
    # fleet topology/cadence belongs to the fleet role alone (ISSUE 16
    # satellite): any other role supplying --fleet-* would silently
    # aggregate nothing — rejected even at default values
    ["--role", "mono", "--fleet-members", "h0:1,h1:1"],
    ["--role", "engine", "--fleet-members", "h0:1"],
    ["--role", "frontend", "--fleet-members", "h0:1"],
    ["--role", "mono", "--fleet-scrape-interval", "1.0"],
    ["--role", "engine", "--fleet-scrape-interval", "0.5"],
    ["--role", "frontend", "--fleet-port", "0"],
    ["--role", "engine", "--fleet-port", "9500"],
    # ...and the fleet role owns no device, listener, or sessions: it
    # rejects engine/frontend/mono flags, even at default values
    ["--role", "fleet", "--fleet-members", "h0:1", "--batch-size", "8"],
    ["--role", "fleet", "--fleet-members", "h0:1", "--listen",
     "insecure-grapevine://0.0.0.0:3229"],
    ["--role", "fleet", "--fleet-members", "h0:1", "--state-dir", "/x"],
    ["--role", "fleet", "--fleet-members", "h0:1", "--leakmon"],
    ["--role", "fleet", "--fleet-members", "h0:1", "--engine", "x:1"],
    ["--role", "fleet", "--fleet-members", "h0:1",
     "--metrics-port", "9464"],
    ["--role", "fleet", "--fleet-members", "h0:1", "--seed", "0"],
    # journal shipping needs the journal in-process (ISSUE 19): a
    # frontend supplying --replicate-to would silently replicate
    # nothing (its journal lives in the engine tier) — rejected even
    # at the --ship-every default; the fleet owns no journal either
    ["--role", "frontend", "--replicate-to", "127.0.0.1:4100"],
    ["--role", "frontend", "--ship-every", "1"],
    ["--role", "fleet", "--fleet-members", "h0:1",
     "--replicate-to", "127.0.0.1:4100"],
    # ...and the standby's own surface belongs to the standby role
    # alone: any other role supplying --standby-listen or
    # --promote-from would silently stand nothing by — rejected even
    # at default values
    ["--role", "mono", "--standby-listen", "127.0.0.1:0"],
    ["--role", "engine", "--standby-listen", "127.0.0.1:0"],
    ["--role", "frontend", "--standby-listen", "127.0.0.1:0"],
    ["--role", "mono", "--promote-from", "/var/lib/grapevine"],
    ["--role", "engine", "--promote-from", "/var/lib/grapevine"],
    # the standby is the replication TARGET: it takes no client-facing
    # listener, no --replicate-to chain, no fleet topology
    ["--role", "standby", "--state-dir", "/x",
     "--replicate-to", "127.0.0.1:4100"],
    ["--role", "standby", "--state-dir", "/x", "--listen",
     "insecure-grapevine://0.0.0.0:3229"],
    ["--role", "standby", "--state-dir", "/x", "--identity-seed",
     "ab" * 32],
    ["--role", "standby", "--state-dir", "/x",
     "--fleet-members", "h0:1"],
    # the host pipeline terminates sessions (mono, frontend) or
    # verifies rounds (engine); the fleet aggregator and the
    # pre-promotion standby touch neither (ISSUE 20)
    ["--role", "fleet", "--fleet-members", "h0:1", "--host-workers", "2"],
    ["--role", "standby", "--state-dir", "/x", "--host-workers", "2"],
    # adaptive collection shapes the device round window — a frontend
    # supplying it would silently shape nothing (its rounds are
    # collected in the engine tier)
    ["--role", "frontend", "--engine", "h:1", "--adaptive-batch"],
    ["--role", "fleet", "--fleet-members", "h0:1", "--adaptive-batch"],
])
def test_misapplied_flags_rejected(argv):
    with pytest.raises(SystemExit, match="does not take"):
        _check(argv)


@pytest.mark.parametrize("argv", [
    [],
    ["--role", "mono", "--listen", "insecure-grapevine://0.0.0.0:1",
     "--identity-seed", "ab" * 32, "--expiry-period", "60"],
    ["--role", "engine", "--engine-listen", "127.0.0.1:0",
     "--msg-capacity", "512", "--batch-size", "16", "--seed", "3"],
    ["--role", "frontend", "--engine", "127.0.0.1:4000",
     "--listen", "insecure-grapevine://0.0.0.0:1", "--batch-size", "16"],
    # the metrics endpoint is a per-process concern: every role takes it
    ["--metrics-port", "9464"],
    ["--role", "engine", "--engine-listen", "127.0.0.1:0",
     "--metrics-port", "9464"],
    ["--role", "frontend", "--engine", "127.0.0.1:4000",
     "--metrics-port", "0"],
    # device-owning roles take the tracer/SLO/profiler flags
    ["--role", "mono", "--trace-ring-size", "1024",
     "--slo-commit-p99-ms", "100", "--profile-enable"],
    ["--role", "engine", "--engine-listen", "127.0.0.1:0",
     "--trace-ring-size", "64", "--slo-commit-p99-ms", "500.5",
     "--profile-enable"],
    # device-owning roles take the position-map knob (ISSUE 7)
    ["--role", "mono", "--posmap-impl", "recursive"],
    ["--role", "engine", "--engine-listen", "127.0.0.1:0",
     "--posmap-impl", "flat"],
    # …and the tree-top cache depth (ISSUE 8)
    ["--role", "mono", "--tree-top-cache-levels", "4"],
    ["--role", "engine", "--engine-listen", "127.0.0.1:0",
     "--tree-top-cache-levels", "0"],
    # …and the round-pipeline depth (ISSUE 10)
    ["--role", "mono", "--pipeline-depth", "2"],
    ["--role", "engine", "--engine-listen", "127.0.0.1:0",
     "--pipeline-depth", "1"],
    # …and the bucket-tree shard count (ISSUE 18)
    ["--role", "mono", "--shards", "2"],
    ["--role", "engine", "--engine-listen", "127.0.0.1:0",
     "--shards", "4"],
    ["--role", "mono", "--shards", "1"],
    # the fleet role takes its topology/cadence flags + the bind
    # interface (ISSUE 16)
    ["--role", "fleet", "--fleet-members", "127.0.0.1:9464,127.0.0.1:9465"],
    ["--role", "fleet", "--fleet-members", "h0:1,h1:1",
     "--fleet-scrape-interval", "0.25", "--fleet-port", "0"],
    ["--role", "fleet", "--fleet-members", "h0:1",
     "--metrics-host", "127.0.0.1", "-v"],
    # device-owning roles ship their journal to a standby (ISSUE 19),
    # alone and with the shipping cadence knob
    ["--role", "mono", "--state-dir", "/x",
     "--replicate-to", "127.0.0.1:4100"],
    ["--role", "engine", "--engine-listen", "127.0.0.1:0",
     "--state-dir", "/x", "--replicate-to", "127.0.0.1:4100",
     "--ship-every", "4"],
    # the standby role: its feed listener, the primary dir it fences
    # at promotion, durability + geometry (it replays into a real
    # engine), and the engine listener it serves on after promotion
    ["--role", "standby", "--state-dir", "/x"],
    ["--role", "standby", "--state-dir", "/x",
     "--standby-listen", "127.0.0.1:0",
     "--promote-from", "/var/lib/grapevine",
     "--engine-listen", "127.0.0.1:0"],
    ["--role", "standby", "--state-dir", "/x",
     "--pipeline-depth", "1", "--tree-top-cache-levels", "0",
     "--metrics-port", "0"],
    # the host pipeline + adaptive knob (ISSUE 20): every
    # session-terminating or round-verifying role takes --host-workers;
    # the frontend also takes --worker-restart (hostpipe crash policy,
    # no durability implied); adaptive windows belong to roles owning
    # a BatchScheduler over an in-process engine (mono/engine/standby)
    ["--role", "mono", "--host-workers", "2", "--adaptive-batch"],
    ["--role", "engine", "--engine-listen", "127.0.0.1:0",
     "--host-workers", "2", "--adaptive-batch"],
    ["--role", "frontend", "--engine", "127.0.0.1:4000",
     "--host-workers", "2", "--worker-restart"],
    ["--role", "standby", "--state-dir", "/x", "--adaptive-batch"],
])
def test_valid_role_flag_combinations_accepted(argv):
    _check(argv)  # must not raise


def test_abbreviated_options_rejected():
    """allow_abbrev=False: the presence scan matches exact tokens, so
    abbreviations must not parse at all."""
    with pytest.raises(SystemExit):
        cli.build_parser().parse_args(["--rol", "engine"])


def test_unclaimed_parser_flag_fails_loudly(monkeypatch):
    """A flag added to build_parser but missing from every role's set
    must error at validation time (and not via a strippable assert)."""
    trimmed = {k: v - {"seed"} for k, v in cli._ROLE_FLAGS.items()}
    monkeypatch.setattr(cli, "_ROLE_FLAGS", trimmed)
    with pytest.raises(SystemExit, match="missing from _ROLE_FLAGS"):
        _check([])
