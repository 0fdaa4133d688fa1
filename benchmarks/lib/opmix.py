"""The seeded op mix: which op each arrival is, who asks, whom it names.

A traffic file gives the mix as fractions of six op kinds (the mix of
``chip_smoke.py``'s ``SchedulerTraffic.wave``), the number of
identities and the Zipf exponent of recipients. ``script()`` draws, from
the seed alone, each op's kind, its asker and its draws — so every seed
offers the same amount of every kind of work, and challenge signatures
and every request that names no message id can be made before the
window. Which message id a by-id op names is decided when the op is
built, from what the engine has answered so far (``KnownIds``): ids are
engine-private and exist only once a CREATE has succeeded.
"""

from __future__ import annotations

import bisect
import collections
import random

from . import wire as W

#: op kinds, in the order a mix lists them
KINDS = ("create", "read_id", "read_next", "update", "delete_id", "pop_next")
_ZERO_ID_KINDS = ("read_next", "pop_next")


def mix_edges(mix: dict) -> list[float]:
    """Cumulative fractions over KINDS; the mix must name exactly those
    kinds and sum to 1."""
    if set(mix) != set(KINDS):
        raise ValueError(f"a mix names exactly {KINDS}, not {sorted(mix)}")
    total = sum(mix.values())
    if abs(total - 1.0) > 1e-9:
        raise ValueError(f"mix fractions sum to {total}, not 1")
    edges, acc = [], 0.0
    for k in KINDS:
        acc += mix[k]
        edges.append(acc)
    edges[-1] = 1.0
    return edges


def kind_of(u: float, edges: list[float]) -> str:
    return KINDS[bisect.bisect_right(edges, u)] if u < 1.0 else KINDS[-1]


class Zipf:
    """Identity ``i`` drawn with weight ``1 / (i + 1) ** s``."""

    def __init__(self, n: int, s: float):
        acc, self.cum = 0.0, []
        for i in range(n):
            acc += 1.0 / (i + 1) ** s
            self.cum.append(acc)
        self.total = acc

    def draw(self, u: float) -> int:
        return min(bisect.bisect_right(self.cum, u * self.total),
                   len(self.cum) - 1)


def script(seed: int, n_ops: int, traffic: dict) -> list[tuple]:
    """``n_ops`` entries ``(kind, asker, recipient_draw, pick_draw)``.
    CREATE senders and by-id askers are uniform over the identities;
    zero-id askers follow the recipients' Zipf (a busy mailbox is polled
    more), so hot mailboxes are both filled to the cap and drained."""
    rng = random.Random(f"{seed}-script")
    edges = mix_edges(traffic["mix"])
    n_id = traffic["identities"]
    zipf = Zipf(n_id, traffic["recipient_zipf"])
    out = []
    for _ in range(n_ops):
        kind = kind_of(rng.random(), edges)
        if kind in _ZERO_ID_KINDS:
            asker = zipf.draw(rng.random())
        else:
            asker = rng.randrange(n_id)
        out.append((kind, asker, zipf.draw(rng.random()), rng.random()))
    return out


class _Bag:
    """A set with O(1) add, remove and seeded choice."""

    def __init__(self):
        self.items: list = []
        self.pos: dict = {}

    def add(self, x) -> None:
        if x not in self.pos:
            self.pos[x] = len(self.items)
            self.items.append(x)

    def discard(self, x) -> None:
        i = self.pos.pop(x, None)
        if i is None:
            return
        last = self.items.pop()
        if i < len(self.items):
            self.items[i] = last
            self.pos[last] = i

    def pick(self, u: float):
        return self.items[min(int(u * len(self.items)), len(self.items) - 1)]

    def __len__(self):
        return len(self.items)


class KnownIds:
    """What the generator has learned from answers: live message ids by
    the identities that may see them, and the last few deleted ones."""

    def __init__(self, pubs: list[bytes]):
        self.index = {p: i for i, p in enumerate(pubs)}
        self.live = _Bag()  # (msg_id, sender, recipient)
        self.mine: dict[int, _Bag] = collections.defaultdict(_Bag)
        self.dead: collections.deque = collections.deque(maxlen=64)
        self._by_id: dict[bytes, tuple] = {}

    def learn(self, reqs, resps) -> None:
        for req, resp in zip(reqs, resps):
            if resp.status_code != W.SUCCESS:
                continue
            rec = resp.record
            if req.request_type == W.CREATE:
                e = (rec.msg_id, self.index[rec.sender],
                     self.index[rec.recipient])
                self._by_id[rec.msg_id] = e
                self.live.add(e)
                self.mine[e[1]].add(e)
                self.mine[e[2]].add(e)
            elif req.request_type == W.DELETE:
                e = self._by_id.pop(rec.msg_id, None)
                if e is not None:
                    self.live.discard(e)
                    self.mine[e[1]].discard(e)
                    self.mine[e[2]].discard(e)
                    self.dead.append(e)

    def pick(self, asker: int, u: float):
        """An id for ``asker`` to name: two times in three one it sent or
        received, else any live id (mostly foreign: NOT_FOUND is the
        correct answer), now and then one deleted a moment ago. None
        while nothing is known."""
        v = (u * 3.0) % 1.0
        if u < 2 / 3 and len(self.mine[asker]):
            return self.mine[asker].pick(v)
        if u > 0.97 and self.dead:
            return self.dead[min(int(v * len(self.dead)), len(self.dead) - 1)]
        if len(self.live):
            return self.live.pick(v)
        return None


class Payloads:
    """A seeded pool of payloads: op ``j`` of a script carries payload
    ``j * K mod n`` (``K`` odd, ``n`` a power of two, so ``n`` ops in a
    row carry ``n`` different ones). A million scripted ops share 15 MB
    of payloads, and none is drawn inside the window."""

    K = 2654435761

    def __init__(self, seed: int, n: int = 1 << 14):
        rng = random.Random(f"{seed}-payloads")
        self.pool = [rng.randbytes(W.PAYLOAD_SIZE) for _ in range(n)]

    def of(self, j: int) -> bytes:
        return self.pool[(j * self.K) % len(self.pool)]


def needs_answers(entry) -> bool:
    """Whether the op names a message id, which exists only once a
    CREATE has been answered: such an op is built inside the window,
    every other one before it."""
    return entry[0] not in ("create",) + _ZERO_ID_KINDS


def build_request(entry, j: int, auth_item, known: KnownIds | None, pubs,
                  payloads: Payloads, records):
    """The program's ``QueryRequest`` for entry ``j`` of a script,
    signed by ``auth_item``. ``records`` is the program's wire-record
    module (``grapevine_tpu.wire.records``): the benchmark builds the
    objects the scheduler takes and nothing else of it. ``known`` is
    read only where ``needs_answers(entry)``."""
    kind, asker, rcp_draw, u = entry
    pub, _, _, sig = auth_item
    rec = {}
    if kind == "create":
        rt = W.CREATE
        rec = {"recipient": pubs[rcp_draw], "payload": payloads.of(j)}
    elif kind == "read_next":
        rt = W.READ
    elif kind == "pop_next":
        rt = W.DELETE
    else:
        named = known.pick(asker, u)
        if named is None:
            # nothing known yet: a seeded id that names no record
            named = (payloads.of(j)[:W.MSG_ID_SIZE], asker, rcp_draw)
        mid, _snd, rcp = named
        if kind == "read_id":
            rt, rec = W.READ, {"msg_id": mid}
        elif kind == "update":
            rt = W.UPDATE
            rec = {"msg_id": mid, "recipient": pubs[rcp],
                   "payload": payloads.of(j)}
        else:
            rt, rec = W.DELETE, {"msg_id": mid, "recipient": pubs[rcp]}
    return records.QueryRequest(
        request_type=rt, auth_identity=pub, auth_signature=sig,
        record=records.RequestRecord(**rec))
