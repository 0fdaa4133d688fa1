"""The benchmark's own oracle against the program's reference on seeded
batches (they must agree op for op), and the control: the same oracle
with the mailbox cap not enforced must be caught."""

import random

import pytest

from benchmarks.lib import compare, opmix
from benchmarks.lib import wire as W
from benchmarks.lib.oracle import Oracle

TRAFFIC = {"mix": {"create": 0.40, "read_id": 0.15, "read_next": 0.10,
                   "update": 0.13, "delete_id": 0.12, "pop_next": 0.10},
           "identities": 12, "recipient_zipf": 0.99}


def _rounds(seed, n_rounds=80, batch=24):
    """Rounds as RoundLog records them, answered by the program's own
    reference (its plain-dict engine), ids assigned by it."""
    from grapevine_tpu.config import GrapevineConfig
    from grapevine_tpu.testing.reference import ReferenceEngine
    from grapevine_tpu.wire import records as R

    cfg = GrapevineConfig(max_messages=4096, max_recipients=8)
    ref = ReferenceEngine(config=cfg, rng=random.Random(seed))
    pubs = [bytes([i + 1]) * 32 for i in range(TRAFFIC["identities"])]
    known = opmix.KnownIds(pubs)
    payloads = opmix.Payloads(seed, 256)
    script = opmix.script(seed, n_rounds * batch, TRAFFIC)
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
    return rounds, {"max_messages": 4096, "max_recipients": 8,
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


def test_oracle_semantics_by_hand():
    class Rec:
        def __init__(self, **kw):
            self.msg_id, self.recipient = W.ZERO_MSG_ID, W.ZERO_PUBKEY
            self.payload = b"\x00" * W.PAYLOAD_SIZE
            self.__dict__.update(kw)

    class Req:
        def __init__(self, rt, who, **kw):
            self.request_type, self.auth_identity = rt, who
            self.record = Rec(**kw)

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
