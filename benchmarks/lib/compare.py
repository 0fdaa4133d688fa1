"""The comparison that decides ``correct``.

Every round the engine ran, in the engine's own order and slot
composition, is replayed on the plain oracle (lib/oracle.py) and each
answer must be equal: status, id, sender, recipient, timestamp and
payload. Every expiry sweep is replayed where the engine ran it among
the rounds, and must have removed as many records as the oracle's.
Every comparison is exact, so every limit is 0.
"""

from __future__ import annotations

from . import wire as W
from .oracle import Oracle


def answers_equal(resp, ans) -> bool:
    """The engine's response object against the oracle's Answer."""
    rec = resp.record
    return (resp.status_code == ans.status
            and rec.msg_id == ans.msg_id
            and rec.sender == ans.sender
            and rec.recipient == ans.recipient
            and rec.timestamp == ans.timestamp
            and rec.payload == ans.payload)


def replay(entries, guarantees: dict, answered=None) -> dict:
    """Replay ``entries`` (RoundLog's: a round has ``reqs``, ``now``,
    ``resps``; a sweep ``kind`` ``"sweep"``, ``now``, ``period``,
    ``evicted``) on a fresh oracle, in their order. Marks each round
    with ``ok`` (per-slot booleans) and returns the counts the verdict
    needs. ``answered`` lets a control put another oracle in the
    engine's place: called with each entry, it gives that oracle's
    answers to a round and its count of records removed by a sweep."""
    oracle = Oracle(guarantees["max_messages"], guarantees["max_recipients"],
                    guarantees["mailbox_cap"])
    compared = wrong = unresolved = sweeps = evicted_gap = 0
    statuses: dict[int, int] = {}
    first_wrong = None
    for i, e in enumerate(entries):
        if e.get("kind") == "sweep":
            evicted = e["evicted"] if answered is None else answered(e)
            sweeps += 1
            evicted_gap += abs(evicted - oracle.expire(e["now"], e["period"]))
            continue
        resps = e["resps"] if answered is None else answered(e)
        if resps is None:
            # never resolved: its requests still happened as far as the
            # engine's state goes, but there is nothing to compare
            unresolved += len(e["reqs"])
            e["ok"] = [False] * len(e["reqs"])
            continue
        forced = [
            _id_of(d) if r.request_type == W.CREATE
            and _status_of(d) == W.SUCCESS else None
            for r, d in zip(e["reqs"], resps)
        ]
        forced += [None] * (len(e["reqs"]) - len(forced))  # lost answers
        ora = oracle.handle_batch(e["reqs"], e["now"], forced)
        if answered is None:
            ok = [answers_equal(d, o) for d, o in zip(resps, ora)]
        else:
            ok = [d == o for d, o in zip(resps, ora)]
        # a round shorter than its requests lost answers
        ok += [False] * (len(e["reqs"]) - len(ok))
        e["ok"] = ok
        compared += len(ok)
        bad = ok.count(False)
        if bad and first_wrong is None:
            j = ok.index(False)
            first_wrong = {"round": i, "slot": j,
                           "request_type": e["reqs"][j].request_type,
                           "oracle_status": ora[j].status if j < len(ora)
                           else None}
        wrong += bad
        for o in ora:
            statuses[o.status] = statuses.get(o.status, 0) + 1
    return {"ops_compared": compared, "ops_wrong": wrong,
            "ops_unresolved": unresolved, "first_wrong": first_wrong,
            "sweeps": sweeps, "sweep_evicted_gap": evicted_gap,
            "status_counts": {str(k): v for k, v in sorted(statuses.items())},
            "oracle_messages": len(oracle.records),
            "oracle_recipients": len(oracle.mailboxes)}


def _status_of(d) -> int:
    return d.status_code if hasattr(d, "status_code") else d.status


def _id_of(d) -> bytes:
    return d.record.msg_id if hasattr(d, "record") else d.msg_id


def verdict(numbers: dict) -> tuple[bool, list[dict]]:
    """``numbers``: name -> measured value. Every number is an exact
    count with the limit 0; ``correct`` is all of them at 0. Returns the
    lines to print: each number beside its limit."""
    lines = [{"compared": k, "value": v, "limit": 0, "ok": v == 0}
             for k, v in numbers.items()]
    return all(x["ok"] for x in lines), lines
