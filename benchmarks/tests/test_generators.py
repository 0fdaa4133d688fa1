"""The same seed gives the same schedule and the same op script; another
seed gives the same amount of work in another order."""

import collections
import json
import os

import pytest

from benchmarks.lib import opmix, schedules
from benchmarks.lib import wire as W
from benchmarks.lib.manifest import HERE

TRICKLE = json.load(open(os.path.join(HERE, "traffic", "trickle-grpc.json")))
BACKLOG = json.load(open(os.path.join(HERE, "traffic", "backlog-mixed.json")))


def _schedule(seed, seconds=30.0):
    t = TRICKLE
    return schedules.onoff_schedule(
        t["mean_rate_ops_per_s"], t["on_factor"], t["off_factor"], t["duty"],
        t["period_s"], seconds, seed)


def test_schedule_fingerprint_repeats_and_differs():
    a, b, c = _schedule(7), _schedule(7), _schedule(8)
    fp = lambda s: schedules.fingerprint(s["t_s"], s["u"])  # noqa: E731
    assert fp(a) == fp(b) != fp(c)
    # the seed decides the instants and which op each arrival is
    assert schedules.fingerprint(a["t_s"]) != schedules.fingerprint(c["t_s"])
    assert schedules.fingerprint(a["u"]) != schedules.fingerprint(c["u"])


@pytest.mark.parametrize("seed", [1, 2**31 + 3, 99])
def test_every_seed_offers_the_same_count_per_phase(seed):
    s = _schedule(seed)
    t = TRICKLE
    assert len(s["t_s"]) == len(_schedule(5)["t_s"])
    assert abs(len(s["t_s"]) - t["mean_rate_ops_per_s"] * 30.0) <= 1
    assert all(a <= b for a, b in zip(s["t_s"], s["t_s"][1:]))
    half = t["period_s"] * t["duty"]
    on = sum(1 for x in s["t_s"] if (x % t["period_s"]) < half)
    want = t["on_factor"] * t["duty"] / (
        t["on_factor"] * t["duty"] + t["off_factor"] * (1 - t["duty"]))
    assert abs(on / len(s["t_s"]) - want) < 0.01


def test_script_repeats_and_keeps_its_mix():
    a = opmix.script(11, 20000, BACKLOG)
    assert a == opmix.script(11, 20000, BACKLOG)
    assert a != opmix.script(12, 20000, BACKLOG)
    share = collections.Counter(e[0] for e in a)
    for kind, want in BACKLOG["mix"].items():
        assert abs(share[kind] / len(a) - want) < 0.015
    hot = collections.Counter(e[2] for e in a if e[0] == "create")
    assert hot.most_common(1)[0][0] == 0, "identity 0 is the hottest mailbox"
    assert hot[0] / sum(hot.values()) > 0.08  # Zipf 0.99 over 2048


def test_a_mix_must_name_the_six_kinds_and_sum_to_one():
    with pytest.raises(ValueError):
        opmix.mix_edges({"create": 1.0})
    with pytest.raises(ValueError):
        opmix.mix_edges(dict(BACKLOG["mix"], create=0.5))
    edges = opmix.mix_edges(BACKLOG["mix"])
    assert opmix.kind_of(0.0, edges) == "create"
    assert opmix.kind_of(0.999999, edges) == "pop_next"


def test_known_ids_learn_and_forget():
    from grapevine_tpu.wire import records as R

    pubs = [bytes([i + 1]) * 32 for i in range(4)]
    known = opmix.KnownIds(pubs)
    mid = b"\x05" * 16
    create = R.QueryRequest(request_type=1, auth_identity=pubs[1])
    made = R.QueryResponse(status_code=1, record=R.Record(
        msg_id=mid, sender=pubs[1], recipient=pubs[2], timestamp=9))
    known.learn([create], [made])
    assert known.pick(1, 0.1)[0] == mid and known.pick(2, 0.5)[0] == mid
    assert known.pick(3, 0.1)[0] == mid  # foreign: any live id
    delete = R.QueryRequest(request_type=4, auth_identity=pubs[2])
    known.learn([delete], [made])
    assert len(known.live) == 0 and len(known.mine[1]) == 0
    assert known.pick(3, 0.99)[0] == mid  # deleted a moment ago


def test_payloads_come_from_a_seeded_pool_and_spread_over_it():
    a, b = opmix.Payloads(5, 64), opmix.Payloads(5, 64)
    assert a.pool == b.pool != opmix.Payloads(6, 64).pool
    assert all(len(p) == W.PAYLOAD_SIZE for p in a.pool)
    # any 64 ops in a row carry 64 different payloads
    assert len({a.of(j) for j in range(1000, 1064)}) == 64
    assert a.of(7) is a.of(7 + 64)  # shared, not copied


def test_requests_that_name_no_id_are_built_without_answers():
    from grapevine_tpu.wire import records as R

    pubs = [i.to_bytes(2, "big") * 16 for i in range(BACKLOG["identities"])]
    script = opmix.script(3, 400, BACKLOG)
    payloads = opmix.Payloads(3, 64)
    early = [e for e in script if not opmix.needs_answers(e)]
    assert {e[0] for e in early} == {"create", "read_next", "pop_next"}
    assert 0.5 < len(early) / len(script) < 0.7  # 60 % of the mix
    item = (pubs[0], b"", b"", b"\x00" * 64)
    known = opmix.KnownIds(pubs)
    for j, e in enumerate(script):
        if opmix.needs_answers(e):
            with pytest.raises(AttributeError):  # it would ask ``known``
                opmix.build_request(e, j, item, None, pubs, payloads, R)
            req = opmix.build_request(e, j, item, known, pubs, payloads, R)
            # nothing known yet: a seeded id that names no record
            assert req.record.msg_id == payloads.of(j)[:W.MSG_ID_SIZE]
        else:
            req = opmix.build_request(e, j, item, None, pubs, payloads, R)
            assert req.validate() is req
            if e[0] == "create":
                assert req.record.payload is payloads.of(j)
