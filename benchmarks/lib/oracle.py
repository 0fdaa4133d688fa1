"""The plain reference: the bus's CRUD semantics on ordinary dicts.

A copy of the arithmetic of ``grapevine_tpu/testing/reference.py``
``ReferenceEngine.handle_batch`` (phase-major batch commit), kept here
so that no later PR can move the yardstick. It imports nothing of the
program: requests are read by attribute (``request_type``,
``auth_identity``, ``record.msg_id`` / ``.recipient`` / ``.payload``),
answers are plain :class:`Answer` tuples, and the capacities and the
mailbox cap come from the configuration file's ``guarantees``.

``expire`` is the TTL sweep as the upstream README gives it (its lines
86-98, SURVEY.md section 3.4: ``expired = now - ts > expiry_period``): every
record older than the period leaves, with its mailbox entry, and then
every mailbox that is empty releases its recipient slot. It is the one
place a slot is released.

Message ids are engine-private PRP outputs, so the oracle is handed the
id the engine returned for each successful CREATE; everything else —
which record an id names, who may see it, zero-id order, the cap — is
decided here.
"""

from __future__ import annotations

from typing import NamedTuple

from . import wire as W


class Answer(NamedTuple):
    status: int
    msg_id: bytes = W.ZERO_MSG_ID
    sender: bytes = W.ZERO_PUBKEY
    recipient: bytes = W.ZERO_PUBKEY
    timestamp: int = 0
    payload: bytes = b"\x00" * W.PAYLOAD_SIZE


def _refusal(now: int, status: int) -> Answer:
    # a failure carries a zero record and the server's (nonzero) clock
    return Answer(status, timestamp=max(1, now))


class Oracle:
    def __init__(self, max_messages: int, max_recipients: int,
                 mailbox_cap: int):
        self.max_messages = max_messages
        self.max_recipients = max_recipients
        self.mailbox_cap = mailbox_cap
        #: msg_id -> [sender, recipient, timestamp, payload]
        self.records: dict[bytes, list] = {}
        #: recipient -> msg_ids, oldest first ("next message" = index 0)
        self.mailboxes: dict[bytes, list[bytes]] = {}

    def _ok(self, mid: bytes) -> Answer:
        snd, rcp, ts, payload = self.records[mid]
        return Answer(W.SUCCESS, mid, snd, rcp, ts, payload)

    def _visible(self, mid, auth):
        rec = self.records.get(mid) if mid is not None else None
        if rec is None or auth not in (rec[0], rec[1]):
            return None  # absence and refusal are the same answer
        return rec

    def _unlist(self, recipient: bytes, mid: bytes) -> None:
        box = self.mailboxes.get(recipient)
        if box is not None and mid in box:
            # a drained mailbox keeps its recipient slot until a sweep
            box.remove(mid)

    def expire(self, now: int, period: int) -> int:
        """One sweep at the clock ``now``: a record leaves, with its
        mailbox entry, when ``now - timestamp > period`` (one exactly
        ``period`` old stays); then every empty mailbox releases its
        recipient slot. ``period <= 0`` is a bus without a TTL: nothing
        happens. Returns the records removed."""
        if period <= 0:
            return 0
        now = int(now)
        due = [mid for mid, rec in self.records.items()
               if now - rec[2] > period]
        for mid in due:
            self._unlist(self.records.pop(mid)[1], mid)
        self.mailboxes = {rcp: box for rcp, box in self.mailboxes.items()
                          if box}
        return len(due)

    def handle_batch(self, reqs, now: int, forced_ids) -> list[Answer]:
        """One round: mailbox effects for the whole batch (A), then
        record effects (B), then by-id deletes leave their mailbox (C),
        each in slot order."""
        now = int(now)
        n = len(reqs)
        status_a = [None] * n
        selected = [None] * n
        new_id = [None] * n
        free = self.max_messages - len(self.records)
        for i, req in enumerate(reqs):
            rt, rec = req.request_type, req.record
            if rt == W.CREATE:
                box = self.mailboxes.get(rec.recipient)
                if rec.recipient == W.ZERO_PUBKEY:
                    status_a[i] = W.INVALID_RECIPIENT
                elif free <= 0:
                    status_a[i] = W.TOO_MANY_MESSAGES
                elif box is None and len(self.mailboxes) >= self.max_recipients:
                    status_a[i] = W.TOO_MANY_RECIPIENTS
                elif box is not None and len(box) >= self.mailbox_cap:
                    status_a[i] = W.TOO_MANY_MESSAGES_FOR_RECIPIENT
                else:
                    if forced_ids[i] is None:
                        # the engine refused a CREATE the oracle accepts:
                        # a nonzero id no request can name keeps the
                        # two states apart for the comparison to see
                        forced_ids[i] = b"\xff" * 8 + i.to_bytes(8, "little")
                    free -= 1
                    new_id[i] = forced_ids[i]
                    self.mailboxes.setdefault(rec.recipient, []).append(new_id[i])
                    status_a[i] = W.SUCCESS
            elif rec.msg_id == W.ZERO_MSG_ID:
                box = self.mailboxes.get(req.auth_identity)
                selected[i] = box[0] if box else None
                if rt == W.DELETE and selected[i] is not None:
                    box.pop(0)

        out: list = [None] * n
        leave = []
        for i, req in enumerate(reqs):
            rt, rec = req.request_type, req.record
            if rt == W.CREATE:
                if new_id[i] is None:
                    out[i] = _refusal(now, status_a[i])
                else:
                    self.records[new_id[i]] = [
                        req.auth_identity, rec.recipient, now, rec.payload]
                    out[i] = self._ok(new_id[i])
                continue
            by_zero = rec.msg_id == W.ZERO_MSG_ID
            mid = selected[i] if by_zero else rec.msg_id
            stored = self._visible(mid, req.auth_identity)
            if stored is None:
                out[i] = _refusal(now, W.NOT_FOUND)
            elif rt == W.READ:
                out[i] = self._ok(mid)
            elif not by_zero and rec.recipient != stored[1]:
                out[i] = _refusal(now, W.INVALID_RECIPIENT)
            elif rt == W.UPDATE:
                stored[2], stored[3] = now, rec.payload
                out[i] = self._ok(mid)
            else:  # DELETE
                out[i] = self._ok(mid)
                del self.records[mid]
                if not by_zero:
                    leave.append((stored[1], mid))
        for recipient, mid in leave:
            self._unlist(recipient, mid)
        return out
