"""One client process of the open-loop gRPC driver. JAX-free: it uses
the program's client library (``grapevine_tpu.server.client``) the way
a wallet does and nothing else of the program.

Reads one JSON job from stdin, authenticates its sessions, prints
``ready``, waits for ``go <t_start>`` (a ``time.monotonic`` instant,
system-wide on Linux), replays its arrivals, waits for every answer and
prints one JSON line of per-op records (``[due, sent, answered, digest,
error, handed]``: ``handed`` is when a free session took the op). An arrival is handed to a free
session; when none is free it waits, and since latency counts from the
due time the wait shows.
"""

from __future__ import annotations

import hashlib
import json
import queue
import random
import sys
import threading
import time


def main() -> int:
    job = json.loads(sys.stdin.readline())
    sys.path.insert(0, job["root"])
    from benchmarks.lib import opmix
    from benchmarks.lib import wire as W
    from benchmarks.lib.identities import identity_seed
    from grapevine_tpu.server.client import GrapevineClient

    pubs = [bytes.fromhex(p) for p in job["pubs"]]
    static = bytes.fromhex(job["server_static"])
    edges = opmix.mix_edges(job["mix"])
    zipf = opmix.Zipf(len(pubs), job["recipient_zipf"])
    rng = random.Random(f"{job['seed']}-child-{job['child']}")
    sessions = []
    for ident in job["session_identities"]:
        cl = GrapevineClient(job["uri"], identity_seed(job["ident_seed"], ident),
                             server_static=static)
        cl.auth()
        sessions.append({"client": cl, "ident": ident, "mine": []})
    free: queue.SimpleQueue = queue.SimpleQueue()
    for s in sessions:
        free.put(s)
    records: list = []
    lock = threading.Lock()

    def one_op(s, due, handed, u, draws):
        """Runs on the session's own thread: build, sign, seal, send,
        open. ``mine`` holds (msg_id, recipient index) of the ids this
        session created itself."""
        cl, mine = s["client"], s["mine"]
        kind = opmix.kind_of(u, edges)
        if kind not in ("create", "read_next", "pop_next") and not mine:
            kind = "create"  # nothing of its own to name yet
        payload = draws.randbytes(W.PAYLOAD_SIZE)
        t_send = time.monotonic()
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
            t_done = time.monotonic()
            digest = hashlib.sha256(r.pack()).hexdigest()
            err = None
        except Exception as exc:  # noqa: BLE001 — counted as a failed op
            t_done, digest, err = time.monotonic(), None, repr(exc)[:200]
        with lock:
            records.append([due, t_send, t_done, digest, err, handed])
        free.put(s)

    threads: list[threading.Thread] = []
    print("ready", flush=True)
    go = sys.stdin.readline().split()
    t_start = float(go[1])
    for off, u in zip(job["t_s"], job["u"]):
        due = t_start + off
        delay = due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        s = free.get()
        th = threading.Thread(
            target=one_op,
            args=(s, due, time.monotonic(), u,
                  random.Random(rng.getrandbits(64))))
        th.start()
        threads.append(th)
    for th in threads:
        th.join(timeout=job["drain_s"])
    for s in sessions:
        s["client"].close()
    with lock:
        out = list(records)
    print(json.dumps({"child": job["child"], "due": len(job["t_s"]),
                      "records": out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
