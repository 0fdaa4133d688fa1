"""grapevine_service_seconds_total{phase=open|wait|wake|seal} and
grapevine_service_queries_total (server/service.py): sums over every
Query served through a real GrapevineServer, never a per-op series."""

import threading
import time

import pytest

from grapevine_tpu.config import GrapevineConfig
from grapevine_tpu.server.client import GrapevineClient
from grapevine_tpu.server.service import SERVICE_STAGES, GrapevineServer
from grapevine_tpu.wire import constants as C

CFG = GrapevineConfig(bucket_cipher_rounds=0, max_messages=64,
                      max_recipients=8, mailbox_cap=8, batch_size=4,
                      stash_size=64)


WALLS: list[float] = []  # seconds each Query spent in its handler


@pytest.fixture(scope="module")
def server():
    srv = GrapevineServer(CFG, seed=5, max_wait_ms=5.0,
                          clock=lambda: 1_700_000_000)
    inner = srv._query

    def timed(request_bytes, context):
        t0 = time.perf_counter()
        try:
            return inner(request_bytes, context)
        finally:
            WALLS.append(time.perf_counter() - t0)

    srv._query = timed  # before start(): gRPC binds the handler there
    port = srv.start("insecure-grapevine://127.0.0.1:0")
    yield srv, port
    srv.stop()


def _stages(srv) -> dict:
    return {p: srv._c_service_s.get(phase=p) for p in SERVICE_STAGES}


def test_stage_seconds_sum_over_queries_and_fit_the_handler_wall(server):
    srv, port = server
    walls = WALLS
    clients = []
    try:
        assert srv._c_service_n.get() == 0
        clients = [GrapevineClient(
            f"insecure-grapevine://127.0.0.1:{port}",
            identity_seed=bytes([40 + i]) * 32) for i in range(3)]
        for c in clients:
            c.auth()  # Auth is not a Query: nothing counted yet
        assert srv._c_service_n.get() == 0
        payload = b"x".ljust(C.PAYLOAD_SIZE, b"\x00")

        def work(c, peer):
            for _ in range(4):
                assert c.create(peer.public_key, payload).status_code in (
                    C.STATUS_CODE_SUCCESS, C.STATUS_CODE_TOO_MANY_MESSAGES)
                c.read()

        threads = [threading.Thread(target=work, args=(c, clients[i - 1]))
                   for i, c in enumerate(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        for c in clients:
            c.close()
    n = 3 * 4 * 2
    assert len(walls) == n
    assert srv._c_service_n.get() == n
    stages = _stages(srv)
    assert all(v > 0 for v in stages.values()), stages
    # four stamps inside the handler: the stages tile part of its wall
    assert sum(stages.values()) <= sum(walls)
    assert sum(stages.values()) >= 0.5 * sum(walls)
    # a round takes far longer than the codec: the wait dominates
    assert stages["wait"] > stages["open"] + stages["seal"]
    report = srv.metrics_registry.audit()  # label keys: phase only
    assert report
    text = srv.metrics_registry.snapshot()
    assert "grapevine_service_seconds_total{phase=wake}" in text


def test_a_refused_query_is_not_counted(server):
    srv, port = server
    before = srv._c_service_n.get()
    c = GrapevineClient(f"insecure-grapevine://127.0.0.1:{port}",
                        identity_seed=b"\x63" * 32)
    c.auth()
    # a hard protocol error (UPDATE with a zero id) fails before the
    # scheduler: no stage is charged, no query counted
    with pytest.raises(Exception):
        c.update(C.ZERO_MSG_ID, c.public_key,
                 b"y".ljust(C.PAYLOAD_SIZE, b"\x00"))
    c.close()
    assert srv._c_service_n.get() == before
