#!/usr/bin/env python
"""Checkpoint/journal seal gate (CI; invoked by a tier-1 test).

Drives a fixture engine with durability on — every request carrying a
loud plaintext marker in its payload, recipient, and auth identity —
then scans every file the durability subsystem wrote and asserts none
of them contains:

- the payload marker bytes (message content must be sealed);
- any fixture recipient/auth identity bytes (metadata must be sealed);
- the 32-byte root seal key (key material must never leak into data
  files; the key lives only in its own 0600 key file, which the scan
  skips — it IS the key).

The files are the ones the real write path leaves: a checkpoint
written as a stream (engine/checkpoint.py ``write_checkpoint``; the
fixture cuts its blocks to 4 KiB, so the state crosses some dozens of
block boundaries and a slip of the running block counter would repeat
keystream over marker-laden plaintext), and a journal whose frames were
sealed by the native stream (``stream_xor``) behind that checkpoint.

This is the durability analog of tools/check_telemetry_policy.py: the
property OPERATIONS.md §11 promises ("sealed files are ciphertext —
a stolen state volume without the key reveals sizes and cadence only"),
enforced against the real write path rather than trusted by review.

Run directly::

    JAX_PLATFORMS=cpu python tools/check_checkpoint_seal.py
"""

from __future__ import annotations

import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: recognizable, high-redundancy plaintext: a sealing slip of even a
#: few bytes of keystream reuse would still contain a full marker copy
PAYLOAD_MARKER = b"GRAPEVINE-SEAL-CHECK-PLAINTEXT-MARKER/"


def _ident(n: int) -> bytes:
    base = b"SEALCHECK-IDENT-%02d/" % n
    return (base + b"\xaa" * 32)[:32]


def run_fixture(state_dir: str) -> dict:
    """Rounds + a sweep + checkpoints against ``state_dir``; returns the
    byte patterns that must NOT appear in any sealed file."""
    from grapevine_tpu.config import DurabilityConfig, GrapevineConfig
    from grapevine_tpu.engine import checkpoint
    from grapevine_tpu.engine.batcher import GrapevineEngine
    from grapevine_tpu.wire import constants as C
    from grapevine_tpu.wire.records import QueryRequest, RequestRecord

    cfg = GrapevineConfig(
        max_messages=64, max_recipients=8, mailbox_cap=4,
        batch_size=4, stash_size=64, bucket_cipher_rounds=0,
    )
    dcfg = DurabilityConfig(state_dir=state_dir, checkpoint_every_rounds=3)
    engine = GrapevineEngine(cfg, seed=9, durability=dcfg)
    reps = C.PAYLOAD_SIZE // len(PAYLOAD_MARKER) + 1
    payload = (PAYLOAD_MARKER * reps)[: C.PAYLOAD_SIZE]
    now = 1_700_000_000

    def round_(i: int) -> None:
        reqs = [
            QueryRequest(
                request_type=C.REQUEST_TYPE_CREATE,
                auth_identity=_ident(i % 4),
                auth_signature=b"\x01" * C.SIGNATURE_SIZE,
                record=RequestRecord(
                    msg_id=C.ZERO_MSG_ID,
                    recipient=_ident((i + 1) % 4),
                    payload=payload,
                ),
            )
            for _ in range(3)
        ]
        engine.handle_queries(reqs, now + i)

    stream_block = checkpoint.STREAM_BLOCK_BYTES
    checkpoint.STREAM_BLOCK_BYTES = 4096
    try:
        for i in range(6):
            round_(i)
        engine.expire(now + 10, period=10_000)
        engine.checkpoint_now()
    finally:
        checkpoint.STREAM_BLOCK_BYTES = stream_block
    # the checkpoint rolled the journal: two rounds more, so that the
    # segment scanned holds sealed frames
    for i in (7, 8):
        round_(i)
    root_key = engine.durability.root_key
    engine.close()
    return {
        "payload marker": PAYLOAD_MARKER,
        "recipient/auth identity": _ident(0)[:16],
        "root seal key": root_key,
    }


def scan(state_dir: str, patterns: dict) -> list[str]:
    violations = []
    for name in sorted(os.listdir(state_dir)):
        if name == "root.key":
            continue  # the key file is the key; everything else is data
        path = os.path.join(state_dir, name)
        if not os.path.isfile(path):
            continue
        with open(path, "rb") as fh:
            blob = fh.read()
        for label, pattern in patterns.items():
            if pattern in blob:
                violations.append(
                    f"{name}: contains plaintext {label} "
                    f"({len(pattern)} marker bytes found in a sealed file)"
                )
    return violations


def main() -> int:
    with tempfile.TemporaryDirectory(prefix="sealcheck-") as state_dir:
        patterns = run_fixture(state_dir)
        files = sorted(
            n for n in os.listdir(state_dir)
            if os.path.isfile(os.path.join(state_dir, n))
        )
        if not any(n.startswith("ckpt-") for n in files) or not any(
            n.startswith("journal-")
            and os.path.getsize(os.path.join(state_dir, n))
            for n in files
        ):
            print(
                f"SEAL GATE BROKEN: fixture wrote no checkpoint/journal "
                f"files to scan (saw {files})", file=sys.stderr,
            )
            return 1
        violations = scan(state_dir, patterns)
    for v in violations:
        print(f"CHECKPOINT SEAL VIOLATION: {v}", file=sys.stderr)
    if not violations:
        print(
            f"checkpoint seal: clean — {len(files)} state file(s) hold "
            "no plaintext payload, identity, or key material"
        )
    return 1 if violations else 0


if __name__ == "__main__":
    raise SystemExit(main())
