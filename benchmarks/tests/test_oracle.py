"""The benchmark's own oracle against the program's reference on seeded
batches, with and without expiry sweeps among them (they must agree op
for op and record for record), and the controls: the same oracle with
the mailbox cap not enforced, or with an ``expire`` that does nothing,
must be caught; so must a sweep replayed one round late."""

import random

import pytest

from benchmarks.lib import compare, opmix
from benchmarks.lib import wire as W
from benchmarks.lib.oracle import Oracle

TRAFFIC = {"mix": {"create": 0.40, "read_id": 0.15, "read_next": 0.10,
                   "update": 0.13, "delete_id": 0.12, "pop_next": 0.10},
           "identities": 12, "recipient_zipf": 0.99}


#: flatter recipients and more pops than creates can feed: mailboxes
#: drain, and a table of 24 slots meets 40 identities
DRAINING = {"mix": {"create": 0.30, "read_id": 0.10, "read_next": 0.10,
                    "update": 0.10, "delete_id": 0.15, "pop_next": 0.25},
            "identities": 40, "recipient_zipf": 0.3}

#: a TTL short enough that records come due among 80 rounds a second apart
PERIOD = 12


def _rounds(seed, n_rounds=80, batch=24, sweep_every=0, period=PERIOD,
            traffic=TRAFFIC, max_recipients=8):
    """Rounds as RoundLog records them, answered by the program's own
    reference (its plain-dict engine), ids assigned by it; with
    ``sweep_every``, a sweep of the reference after every so many
    rounds, as RoundLog records a sweep."""
    from grapevine_tpu.config import GrapevineConfig
    from grapevine_tpu.testing.reference import ReferenceEngine
    from grapevine_tpu.wire import records as R

    cfg = GrapevineConfig(max_messages=4096, max_recipients=max_recipients)
    ref = ReferenceEngine(config=cfg, rng=random.Random(seed))
    pubs = [bytes([i + 1]) * 32 for i in range(traffic["identities"])]
    known = opmix.KnownIds(pubs)
    payloads = opmix.Payloads(seed, 256)
    script = opmix.script(seed, n_rounds * batch, traffic)
    rounds = []
    for k in range(n_rounds):
        reqs = [opmix.build_request(
            e, j, (pubs[e[1]], b"", b"", b"\x00" * 64), known, pubs,
            payloads, R)
            for j, e in enumerate(script[k * batch:(k + 1) * batch],
                                  k * batch)]
        resps = ref.handle_batch(reqs, 1000 + k)
        known.learn(reqs, resps)
        rounds.append({"reqs": reqs, "now": 1000 + k, "resps": resps})
        if sweep_every and (k + 1) % sweep_every == 0:
            rounds.append({"kind": "sweep", "now": 1000 + k,
                           "period": period,
                           "evicted": ref.expire(1000 + k, period)})
    return rounds, {"max_messages": 4096, "max_recipients": max_recipients,
                    "mailbox_cap": 62}


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_oracle_agrees_with_the_programs_reference(seed):
    rounds, guarantees = _rounds(seed)
    rep = compare.replay(rounds, guarantees)
    assert rep["ops_compared"] == 80 * 24 and rep["ops_wrong"] == 0
    seen = set(rep["status_counts"])
    # the traffic reaches the cap, the recipient table's end and misses
    assert {str(W.SUCCESS), str(W.NOT_FOUND),
            str(W.TOO_MANY_MESSAGES_FOR_RECIPIENT),
            str(W.TOO_MANY_RECIPIENTS)} <= seen


def _swept(seed, period=PERIOD, max_recipients=24):
    return _rounds(seed, sweep_every=5, period=period, traffic=DRAINING,
                   max_recipients=max_recipients)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_oracle_with_sweeps_agrees_with_the_programs_reference(seed):
    entries, guarantees = _swept(seed)
    rep = compare.replay(entries, guarantees)
    assert rep["ops_compared"] == 80 * 24 and rep["ops_wrong"] == 0
    assert rep["sweeps"] == 16 and rep["sweep_evicted_gap"] == 0
    # records came due, and reads of them were answered as gone
    assert sum(e["evicted"] for e in entries if "evicted" in e) > 10


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_a_sweep_replayed_one_round_late_is_caught(seed):
    """What a log appended outside the engine's order would hold."""
    entries, guarantees = _swept(seed)
    caught = moved = 0
    for i, e in enumerate(entries[:-1]):
        if e.get("kind") == "sweep" and e["evicted"]:
            late = list(entries)
            late[i], late[i + 1] = late[i + 1], late[i]
            rep = compare.replay(late, guarantees)
            moved += 1
            caught += rep["ops_wrong"] + rep["sweep_evicted_gap"] > 0
    assert moved >= 5 and caught == moved


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_no_reclaim_control_is_caught(seed):
    """The oracle whose expire does nothing, in the program's place."""
    from benchmarks.control import no_reclaim_numbers

    entries, guarantees = _swept(seed)
    numbers = no_reclaim_numbers(entries, guarantees)
    assert numbers["recipient_count_gap"] > 0
    assert numbers["sweep_evicted_gap"] > 0 and numbers["ops_wrong"] > 0
    assert not compare.verdict(numbers)[0]
    # with a TTL of a day nothing comes due, and in a table that never
    # fills no answer differs: the slots alone tell (the chip's cell)
    entries, guarantees = _swept(seed, period=86400, max_recipients=64)
    numbers = no_reclaim_numbers(entries, guarantees)
    assert numbers["recipient_count_gap"] > 0
    del numbers["recipient_count_gap"]
    assert compare.verdict(numbers)[0]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_control_is_caught(seed):
    """The oracle with the cap unenforced, in the program's place."""
    from benchmarks.control import control_in_place

    rounds, guarantees = _rounds(seed)
    rep = compare.replay(rounds, guarantees,
                         answered=control_in_place(guarantees))
    assert rep["ops_wrong"] > 0
    correct, _ = compare.verdict({"ops_wrong": rep["ops_wrong"]})
    assert not correct


def test_a_short_round_and_a_lost_round_count_as_wrong():
    rounds, guarantees = _rounds(4, n_rounds=3)
    rounds[1]["resps"] = rounds[1]["resps"][:-1]
    rounds[2]["resps"] = None
    rep = compare.replay(rounds, guarantees)
    assert rep["ops_wrong"] == 1 and rep["ops_unresolved"] == 24
    assert rounds[1]["ok"][-1] is False


class Rec:
    """A request's record as the oracle reads it, for the tests by hand."""

    def __init__(self, **kw):
        self.msg_id, self.recipient = W.ZERO_MSG_ID, W.ZERO_PUBKEY
        self.payload = b"\x00" * W.PAYLOAD_SIZE
        self.__dict__.update(kw)


class Req:
    def __init__(self, rt, who, **kw):
        self.request_type, self.auth_identity = rt, who
        self.record = Rec(**kw)


A, B, C, D = (bytes([i]) * 32 for i in (1, 2, 3, 4))


def test_oracle_semantics_by_hand():
    a, b, c = (bytes([i]) * 32 for i in (1, 2, 3))
    o = Oracle(8, 8, mailbox_cap=2)
    ids = [bytes([i]) * 16 for i in (1, 2, 3)]
    out = o.handle_batch([Req(W.CREATE, a, recipient=b) for _ in ids],
                         5, list(ids))
    assert [x.status for x in out] == [
        W.SUCCESS, W.SUCCESS, W.TOO_MANY_MESSAGES_FOR_RECIPIENT]
    out = o.handle_batch(
        [Req(W.READ, c, msg_id=ids[0]), Req(W.READ, b),
         Req(W.DELETE, b), Req(W.READ, b),
         Req(W.UPDATE, a, msg_id=ids[1], recipient=c),
         Req(W.DELETE, a, msg_id=ids[1], recipient=b)], 6, [None] * 6)
    assert [x.status for x in out] == [
        W.NOT_FOUND, W.SUCCESS, W.SUCCESS, W.SUCCESS,
        W.INVALID_RECIPIENT, W.SUCCESS]
    # phase-major: both zero-id reads saw the mailbox as phase A left it
    assert out[1].msg_id == ids[0] and out[2].msg_id == ids[0]
    assert out[3].msg_id == ids[1]
    assert not o.records and o.mailboxes == {b: []}


def test_expiry_by_hand():
    a, b, c, d = A, B, C, D
    ids = [bytes([i]) * 16 for i in (1, 2, 3, 4)]
    o = Oracle(8, 2, mailbox_cap=62)
    o.handle_batch([Req(W.CREATE, a, recipient=b)], 100, ids[:1])
    o.handle_batch([Req(W.CREATE, a, recipient=b),
                    Req(W.CREATE, a, recipient=c)], 101, ids[1:3])
    # no TTL: nothing happens, whatever the clock
    assert o.expire(10**9, 0) == 0 and len(o.records) == 3
    # a record exactly ``period`` old stays; one a second older goes
    assert o.expire(110, 10) == 0 and len(o.records) == 3
    assert o.expire(111, 10) == 1 and set(o.records) == set(ids[1:3])
    assert o.mailboxes == {b: [ids[1]], c: [ids[2]]}
    # an UPDATE stamps the record anew
    o.handle_batch([Req(W.UPDATE, a, msg_id=ids[1], recipient=b)], 111,
                   [None])
    assert o.expire(112, 10) == 1 and set(o.records) == {ids[1]}
    # c's mailbox went with its last record: its slot is free
    assert o.mailboxes == {b: [ids[1]]}
    # a clock behind a record's stamp never expires it
    assert o.expire(5, 10) == 0 and len(o.records) == 1


def test_a_drained_mailbox_keeps_its_slot_until_a_sweep():
    a, b, c, d = A, B, C, D
    ids = [bytes([i]) * 16 for i in (1, 2, 3, 4)]
    o = Oracle(8, 2, mailbox_cap=62)
    o.handle_batch([Req(W.CREATE, a, recipient=b),
                    Req(W.CREATE, a, recipient=c)], 100, ids[:2])
    o.handle_batch([Req(W.DELETE, b)], 101, [None])  # b pops its only one
    assert o.mailboxes == {b: [], c: [ids[1]]}
    # the table of two is full: a third recipient is refused ...
    out = o.handle_batch([Req(W.CREATE, a, recipient=d)], 102, [ids[2]])
    assert out[0].status == W.TOO_MANY_RECIPIENTS
    # ... until a sweep, which removes no record, frees b's slot
    assert o.expire(103, 86400) == 0
    assert o.mailboxes == {c: [ids[1]]}
    out = o.handle_batch([Req(W.CREATE, a, recipient=d)], 104, [ids[2]])
    assert out[0].status == W.SUCCESS and set(o.mailboxes) == {c, d}


def test_a_misplaced_sweep_by_hand():
    """A sweep appended behind the round that was dispatched after it:
    the replay expires a record the engine no longer had."""
    a, b, c, d = A, B, C, D
    mid = bytes([9]) * 16
    g = {"max_messages": 8, "max_recipients": 2, "mailbox_cap": 62}
    engine = Oracle(8, 2, 62)  # stands where the engine would
    log = []
    for now, reqs, forced in [(100, [Req(W.CREATE, a, recipient=b)], [mid]),
                              (112, [Req(W.READ, b)], [None])]:
        if now == 112:
            log.append({"kind": "sweep", "now": 111, "period": 10,
                        "evicted": engine.expire(111, 10)})
        log.append({"kind": "round", "reqs": reqs, "now": now,
                    "resps": engine.handle_batch(reqs, now, list(forced))})
    assert log[1]["evicted"] == 1 and log[2]["resps"][0].status == W.NOT_FOUND
    def logged(e):  # the stand-in's own answers and counts, as logged
        return e["evicted"] if e["kind"] == "sweep" else e["resps"]

    rep = compare.replay(log, g, answered=logged)
    assert rep["ops_wrong"] == 0 and rep["sweep_evicted_gap"] == 0
    late = [log[0], log[2], log[1]]
    rep = compare.replay(late, g, answered=logged)
    assert rep["ops_wrong"] == 1 and rep["sweep_evicted_gap"] == 0
