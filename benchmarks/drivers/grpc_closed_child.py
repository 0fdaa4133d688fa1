"""One client process of the closed-loop gRPC driver. JAX-free: it uses
the program's client library (``grapevine_tpu.server.client``) the way
a service that holds its sessions open does, and nothing else of the
program.

Reads one JSON job from stdin, authenticates its sessions (each against
the frontend the job gives it), prints ``ready``, waits for
``go <t_start> <seconds>`` (``t_start`` a ``time.monotonic`` instant,
system-wide on Linux). From then on every session keeps exactly one op
outstanding with no think time: on its own thread it builds the next op,
signs it against the fresh challenge, seals it and sends it when the
last answer is opened, until the window's end; the op in flight then is
still awaited and reported. Prints one JSON line: ``records``
(``[answered, digest]`` per answered op, ``time.monotonic`` seconds and
a BLAKE2 digest of the packed answer), ``errors`` (one text per failed
op), and ``cpu_s``, this process's CPU seconds (all threads) between the
go line and the window's end.
"""

from __future__ import annotations

import hashlib
import json
import random
import sys
import threading
import time


def digest(packed: bytes) -> str:
    return hashlib.blake2b(packed, digest_size=16).hexdigest()


def main() -> int:
    job = json.loads(sys.stdin.readline())
    sys.path.insert(0, job["root"])
    from benchmarks.lib import opmix
    from benchmarks.lib import wire as W
    from benchmarks.lib.identities import identity_seed
    from grapevine_tpu.server.client import GrapevineClient

    pubs = [bytes.fromhex(p) for p in job["pubs"]]
    edges = opmix.mix_edges(job["mix"])
    zipf = opmix.Zipf(len(pubs), job["recipient_zipf"])
    sessions = []
    for k, ident, frontend in job["sessions"]:
        uri, static = job["frontends"][frontend]
        cl = GrapevineClient(uri, identity_seed(job["ident_seed"], ident),
                             server_static=bytes.fromhex(static))
        cl.auth()
        sessions.append((k, cl))
    records: list = []
    errors: list = []
    lock = threading.Lock()
    window = {}
    go = threading.Event()

    def session_loop(k: int, cl) -> None:
        """``mine`` holds (msg_id, recipient index) of the ids this
        session created itself: it names no others."""
        draws = random.Random(f"{job['seed']}-session-{k}")
        mine: list = []
        done: list = []
        failed: list = []
        go.wait()
        t_end = window["t_end"]
        while time.monotonic() < t_end:
            kind = opmix.kind_of(draws.random(), edges)
            if kind not in ("create", "read_next", "pop_next") and not mine:
                kind = "create"  # nothing of its own to name yet
            payload = draws.randbytes(W.PAYLOAD_SIZE)
            try:
                if kind == "create":
                    rcp = zipf.draw(draws.random())
                    r = cl.create(pubs[rcp], payload)
                    if r.status_code == W.SUCCESS:
                        mine.append((r.record.msg_id, rcp))
                elif kind == "read_id":
                    r = cl.read(draws.choice(mine)[0])
                elif kind == "read_next":
                    r = cl.read()
                elif kind == "update":
                    mid, rcp = draws.choice(mine)
                    r = cl.update(mid, pubs[rcp], payload)
                elif kind == "delete_id":
                    mid, rcp = mine.pop(draws.randrange(len(mine)))
                    r = cl.delete(mid, pubs[rcp])
                else:
                    r = cl.delete()
                done.append([time.monotonic(), digest(r.pack())])
            except Exception as exc:  # noqa: BLE001 — counted as a failed op
                failed.append(repr(exc)[:200])
                break  # the session's lockstep is gone with the op
        with lock:
            records.extend(done)
            errors.extend(failed)

    threads = [threading.Thread(target=session_loop, args=s, daemon=True)
               for s in sessions]
    for th in threads:
        th.start()
    print("ready", flush=True)
    _, t_start, seconds = sys.stdin.readline().split()
    window["t_end"] = float(t_start) + float(seconds)
    cpu0 = time.process_time()
    go.set()
    time.sleep(max(0.0, window["t_end"] - time.monotonic()))
    cpu_s = time.process_time() - cpu0
    deadline = time.monotonic() + job["drain_s"]
    for th in threads:
        th.join(timeout=max(0.0, deadline - time.monotonic()))
    stuck = sum(th.is_alive() for th in threads)
    for _, cl in sessions:
        cl.close()
    with lock:
        out = {"child": job["child"], "records": list(records),
               "errors": list(errors) + ["never answered"] * stuck,
               "cpu_s": cpu_s}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
