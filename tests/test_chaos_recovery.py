"""Kill-at-every-phase chaos loop: SIGKILL the engine process at
every instrumented fault site in the journal/checkpoint protocol — plus
randomized wall-clock kills — restart, and assert recovery is
bit-identical to an uninterrupted run with the leak monitor PASS
throughout.

Drives tools/chaos_run.py (the standalone ≥50-trial acceptance harness:
``python tools/chaos_run.py --trials 50``) at a phase-exhaustive trial
count. Each trial spawns child processes; durability is a guarantee, so
the file rides tier-1 (it is the longest file there: ``--dist
loadfile`` keeps it on one worker).
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_chaos():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import chaos_run

    return chaos_run


def test_kill_at_every_fault_point_recovers_bit_identical():
    """One trial per crash site (testing/faults.py ALL_POINTS) plus one
    timer-kill trial: recovered state and every recorded response hash
    must match the uninterrupted oracle, and leakmon must report PASS on
    the recovered engine."""
    chaos = _load_chaos()
    from grapevine_tpu.testing.faults import ALL_POINTS

    args = chaos.parse_args(["--events", "18"])
    failures = chaos.run_trials(0, args, modes=list(ALL_POINTS) + ["timer"])
    assert not failures, "\n".join(failures)


def test_randomized_kill_trials_recover_bit_identical():
    """A handful of randomized trials (site and trigger count drawn per
    trial) on top of the exhaustive pass — the shape the standalone
    50-trial acceptance run uses."""
    chaos = _load_chaos()

    args = chaos.parse_args(["--events", "18", "--seed", "77"])
    failures = chaos.run_trials(6, args)
    assert not failures, "\n".join(failures)


def test_pipelined_kill_trials_recover_bit_identical():
    """PR-10 chaos coverage: ``--pipeline-depth 2`` keeps a round
    mid-flight on the device while the next one journals + fsyncs, and
    kills land (a) between journal-fsync(k+1) and dispatch(k+1)
    (``round.pre_dispatch``), (b) mid-flight of round k with k+1
    dispatched behind it (``round.post_dispatch``), (c) at the fsync
    barrier itself, the torn-frame window, and a randomized wall-clock
    point. The oracle is the SERIAL depth-1 program, so recovery being
    bit-identical proves both the crash contract (replay order = journal
    order, never completion order) and depth bit-equivalence at once,
    with leakmon PASS on the recovered engine."""
    chaos = _load_chaos()

    args = chaos.parse_args(
        ["--events", "18", "--seed", "99", "--pipeline-depth", "2"]
    )
    failures = chaos.run_trials(0, args, modes=[
        "round.pre_dispatch", "round.post_dispatch",
        "journal.append.post_fsync", "journal.append.torn", "timer",
    ])
    assert not failures, "\n".join(failures)


def test_standby_kill_at_every_fault_point_promotes_bit_identical():
    """ISSUE-19 chaos acceptance: the hot-standby drill at every
    instrumented fault site (plus a timer kill) at
    ``--pipeline-depth 2``. Each trial streams the primary's sealed
    frames to an in-parent StandbyReplica, SIGKILLs the primary at the
    armed site — including the torn-frame window, which lands a
    half-written frame at the tail the promote-time drain must treat as
    not-yet-durable — then
    promotes, finishes the event schedule on the replica, and requires
    the final state to match the serial oracle bit-identically with
    leakmon (including the ship-cadence book) PASS, and the fenced
    primary dir to refuse a revived stale writer."""
    chaos = _load_chaos()
    from grapevine_tpu.testing.faults import ALL_POINTS

    args = chaos.parse_args(
        ["--standby", "--events", "16",
         "--pipeline-depth", "2", "--checkpoint-every", "5",
         "--seed", "43"]
    )
    failures = chaos.run_trials(0, args, modes=list(ALL_POINTS) + ["timer"])
    assert not failures, "\n".join(failures)


# -- live flip drill: CLI processes, SIGKILL + SIGUSR1, zero dropped ----


def _wait_line(proc, needle, timeout=120.0):
    import time as _t

    deadline = _t.monotonic() + timeout
    while _t.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            raise AssertionError(
                f"process exited before {needle!r}: "
                f"{proc.stderr.read()[-2000:]}"
            )
        if needle in line:
            return line
    raise AssertionError(f"no {needle!r} line within {timeout}s")


def _signed_req(scheme, seed_byte, rt, recipient, payload_byte, challenge):
    from grapevine_tpu.wire import constants as C
    from grapevine_tpu.wire.records import QueryRequest, RequestRecord

    sk, pub = scheme.keygen(bytes([seed_byte]) * 32)
    sig = scheme.sign(
        sk, C.GRAPEVINE_CHALLENGE_SIGNING_CONTEXT, challenge
    )
    req = QueryRequest(
        request_type=rt, auth_identity=pub, auth_signature=sig,
        record=RequestRecord(
            msg_id=C.ZERO_MSG_ID, recipient=recipient,
            payload=bytes([payload_byte]) * C.PAYLOAD_SIZE,
        ),
    )
    return req, (pub, C.GRAPEVINE_CHALLENGE_SIGNING_CONTEXT, challenge, sig)


def test_live_flip_drill_zero_dropped_ops(tmp_path):
    """The operational runbook (OPERATIONS.md §23) over real processes:
    an engine-role primary shipping to a standby-role process, clients
    acknowledged over gRPC, SIGKILL the primary, SIGUSR1 the standby,
    and every acknowledged write is readable from the promoted engine
    port — zero dropped ops across the flip."""
    import json
    import signal
    import subprocess
    import time as _t
    import urllib.request

    import grpc  # noqa: F401 - engine stub transport

    from grapevine_tpu.server.tier import _EngineStub
    from grapevine_tpu.session import get_signature_scheme
    from grapevine_tpu.wire import constants as C

    scheme = get_signature_scheme("schnorrkel")
    pdir, sdir = str(tmp_path / "primary"), str(tmp_path / "standby")
    for d in (pdir, sdir):
        os.makedirs(d)
        with open(os.path.join(d, "root.key"), "wb") as fh:
            fh.write(bytes(range(32)))
        os.chmod(os.path.join(d, "root.key"), 0o600)

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    geometry = [
        "--msg-capacity", "64", "--recipient-capacity", "8",
        "--batch-size", "4",
        "--tree-top-cache-levels", "0", "--pipeline-depth", "1",
        "--batch-wait-ms", "30",
    ]
    procs = []
    try:
        standby = subprocess.Popen(
            [sys.executable, "-m", "grapevine_tpu.server.cli",
             "--role", "standby", "--state-dir", sdir,
             "--standby-listen", "127.0.0.1:0",
             "--promote-from", pdir,
             "--engine-listen", "127.0.0.1:0",
             "--metrics-port", "0"] + geometry,
            cwd=REPO, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True)
        procs.append(standby)
        line = _wait_line(standby, "standby replica on port")
        feed_port = int(line.rsplit(" ", 1)[1])
        line = _wait_line(standby, "metrics endpoint on port")
        mport = int(line.rsplit(" ", 1)[1])

        primary = subprocess.Popen(
            [sys.executable, "-m", "grapevine_tpu.server.cli",
             "--role", "engine", "--engine-listen", "127.0.0.1:0",
             "--state-dir", pdir,
             "--replicate-to", f"127.0.0.1:{feed_port}"] + geometry,
            cwd=REPO, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True)
        procs.append(primary)
        line = _wait_line(primary, "engine tier listening on port")
        eport = int(line.rsplit(" ", 1)[1])

        # acknowledged writes: 3 messages into mailbox X + filler ops
        stub = _EngineStub(f"127.0.0.1:{eport}", deadline_s=60.0)
        _, x_pub = scheme.keygen(b"\x07" * 32)
        for i in range(8):
            challenge = bytes([i + 1]) * C.CHALLENGE_SIZE
            req, auth = _signed_req(
                scheme, seed_byte=i + 10, rt=C.REQUEST_TYPE_CREATE,
                recipient=x_pub if i < 3 else bytes([i + 40]) * 32,
                payload_byte=0x70 + i, challenge=challenge)
            resp = stub.submit(req, auth=auth)
            assert resp.status_code == C.STATUS_CODE_SUCCESS, i
        stub.close()

        # wait for the live feed to have applied the acked tail (the
        # drill's "hot" claim: promotion replays no cold backlog)
        deadline = _t.monotonic() + 60
        while _t.monotonic() < deadline:
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{mport}/healthz",
                        timeout=5) as r:
                    hz = json.loads(r.read().decode())
            except urllib.error.HTTPError as e:
                hz = json.loads(e.read().decode())
            if (hz.get("replication_connected")
                    and hz["durability"]["applied_seq"] >= 8):
                break
            _t.sleep(0.2)
        else:
            raise AssertionError(f"standby never caught up: {hz}")

        # kill-the-primary, promote-the-standby
        primary.send_signal(signal.SIGKILL)
        primary.wait(timeout=30)
        standby.send_signal(signal.SIGUSR1)
        _wait_line(standby, "standby promoted: epoch")
        line = _wait_line(standby, "promoted engine tier listening on port")
        pport = int(line.rsplit(" ", 1)[1])

        # zero dropped: every pre-kill write survives the flip — pops
        # from mailbox X return the exact acknowledged payloads
        stub = _EngineStub(f"127.0.0.1:{pport}", deadline_s=60.0)
        x_sk, x_pub2 = scheme.keygen(b"\x07" * 32)
        assert x_pub2 == x_pub
        popped = []
        for i in range(3):
            challenge = bytes([0x80 + i]) * C.CHALLENGE_SIZE
            sig = scheme.sign(
                x_sk, C.GRAPEVINE_CHALLENGE_SIGNING_CONTEXT, challenge)
            from grapevine_tpu.wire.records import (
                QueryRequest,
                RequestRecord,
            )

            req = QueryRequest(
                request_type=C.REQUEST_TYPE_DELETE, auth_identity=x_pub,
                auth_signature=sig,
                record=RequestRecord(
                    msg_id=C.ZERO_MSG_ID, recipient=C.ZERO_PUBKEY,
                    payload=b"\x00" * C.PAYLOAD_SIZE))
            resp = stub.submit(
                req, auth=(x_pub, C.GRAPEVINE_CHALLENGE_SIGNING_CONTEXT,
                           challenge, sig))
            assert resp.status_code == C.STATUS_CODE_SUCCESS
            popped.append(resp.record.payload[0])
        assert popped == [0x70, 0x71, 0x72], popped
        # ...and the promoted engine keeps taking new writes
        challenge = b"\xaa" * C.CHALLENGE_SIZE
        req, auth = _signed_req(
            scheme, seed_byte=99, rt=C.REQUEST_TYPE_CREATE,
            recipient=b"\x63" * 32, payload_byte=0x63,
            challenge=challenge)
        assert stub.submit(req, auth=auth).status_code == \
            C.STATUS_CODE_SUCCESS
        stub.close()

        standby.send_signal(signal.SIGTERM)
        assert standby.wait(timeout=120) == 0, standby.stderr.read()[-2000:]
        procs = []
    finally:
        for p in procs:
            p.kill()
            p.wait(timeout=10)
