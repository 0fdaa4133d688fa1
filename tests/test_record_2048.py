"""The 2048-byte record compile-time option (reference README.md:138-139).

The reference offers record size as a compile-time constant (1024
default, 2048 optional). The analog here is a process-wide constant
fixed before import (``GRAPEVINE_RECORD_SIZE``); this test launches a
subprocess in 2048 mode and drives wire-layer constant-size checks plus
an engine CRUD round — proving every derived layout (wire codec, device
block geometry, codecs) follows the option."""

import os
import subprocess
import sys

_CHILD = r"""
import os
assert os.environ["GRAPEVINE_RECORD_SIZE"] == "2048"
import jax
jax.config.update("jax_platforms", "cpu")
from grapevine_tpu.wire import constants as C
from grapevine_tpu.wire.records import QueryRequest, QueryResponse, Record, RequestRecord

assert C.RECORD_SIZE == 2048 and C.PAYLOAD_SIZE == 1960
# constant-size property holds at the new geometry (the reference's
# signature test idea, api/tests/grapevine_types.rs:21-31)
sizes = set()
for fill in (b"\x00", b"\xaa", b"\xff"):
    req = QueryRequest(
        request_type=C.REQUEST_TYPE_CREATE,
        auth_identity=fill * 32,
        auth_signature=fill * 64,
        record=RequestRecord(
            msg_id=fill * 16, recipient=fill * 32,
            payload=fill * C.PAYLOAD_SIZE,
        ),
    )
    sizes.add(len(req.pack()))
    assert RequestRecord.unpack(req.pack()[4 + 32 + 64:]).payload == fill * C.PAYLOAD_SIZE
assert sizes == {C.QUERY_REQUEST_WIRE_SIZE}
resp = QueryResponse(record=Record(payload=b"\x07" * C.PAYLOAD_SIZE),
                     status_code=C.STATUS_CODE_SUCCESS)
assert len(resp.pack()) == C.QUERY_RESPONSE_WIRE_SIZE == 2052

# device engine at the 2048-byte block geometry (512-word blocks)
from grapevine_tpu.engine.state import PAYLOAD_WORDS, REC_WORDS
assert (PAYLOAD_WORDS, REC_WORDS) == (490, 512)
from grapevine_tpu.config import GrapevineConfig
from grapevine_tpu.engine.batcher import GrapevineEngine
cfg = GrapevineConfig(bucket_cipher_rounds=8, max_messages=64,
                      max_recipients=8, mailbox_cap=4, batch_size=4,
                      stash_size=64)
e = GrapevineEngine(cfg, seed=1)
a, b = b"\x11" * 32, b"\x22" * 32
r = e.handle_queries([QueryRequest(
    request_type=C.REQUEST_TYPE_CREATE, auth_identity=a,
    record=RequestRecord(recipient=b, payload=b"\x09" * C.PAYLOAD_SIZE))],
    1_700_000_000)[0]
assert r.status_code == C.STATUS_CODE_SUCCESS
r2 = e.handle_queries([QueryRequest(
    request_type=C.REQUEST_TYPE_READ, auth_identity=b,
    record=RequestRecord(msg_id=C.ZERO_MSG_ID))], 1_700_000_001)[0]
assert r2.status_code == C.STATUS_CODE_SUCCESS
assert r2.record.payload == b"\x09" * C.PAYLOAD_SIZE
print("RECORD2048_OK")
"""


def test_2048_byte_record_mode():
    env = dict(os.environ)
    env["GRAPEVINE_RECORD_SIZE"] = "2048"
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", _CHILD], env=env, capture_output=True,
        text=True, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert "RECORD2048_OK" in out.stdout, out.stderr[-2000:]


def test_invalid_record_size_rejected():
    env = dict(os.environ)
    env["GRAPEVINE_RECORD_SIZE"] = "1536"
    out = subprocess.run(
        [sys.executable, "-c", "from grapevine_tpu.wire import constants"],
        env=env, capture_output=True, text=True, timeout=120,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode != 0 and "1024 or 2048" in out.stderr
