"""The shape ``chipshare-2p21-r2p17`` adds, at its toy twin's geometry.

One chip's whole share of the bus (``benchmarks/configs/
chipshare-2p21-r2p17.json``: 2^21 messages, 2^17 recipients, B = 2048)
is the first configuration whose records tree has almost as many
per-path levels as dense ones (9 under 12) and whose mailbox tree has
three per-path levels. Its toy twin turns both up: more per-path record
levels than dense ones, four per-path mailbox levels. The cell's own
driver and mix (``backlog-1chip-2p21``: the closed loop of signed ops
through the served scheduler) run on it here on the CPU, and every
round is then replayed on the program's plain reference
(``testing/reference.py``), op for op.
"""

import json
import os
import sys
import time

import pytest

from grapevine_tpu.config import GrapevineConfig
from grapevine_tpu.engine.state import EngineConfig
from grapevine_tpu.testing.reference import ReferenceEngine
from grapevine_tpu.wire import constants as C

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks", "tests"))

CELL = "backlog-1chip-2p21"
CONFIG = "chipshare-2p21-r2p17"

pytestmark = pytest.mark.filterwarnings("ignore")


def _config(*directory) -> GrapevineConfig:
    with open(os.path.join(REPO, "benchmarks", *directory, "configs",
                           f"{CONFIG}.json")) as f:
        return GrapevineConfig(**json.load(f)["grapevine_config"])


def test_the_toy_twin_has_the_shape_the_deployment_adds():
    cfg = _config("tests", "data")
    ecfg = EngineConfig.from_config(cfg)
    b, bd = cfg.batch_size, cfg.batch_size * ecfg.mb_choices
    rec_dense = ecfg.rec.dense_levels(b)
    assert ecfg.rec.path_len - rec_dense > rec_dense
    assert ecfg.mb.path_len - ecfg.mb.dense_levels(bd) >= 3
    assert ecfg.rec.perpath_bucket_rows(b) > 0 < ecfg.mb.perpath_bucket_rows(bd)
    # the deployment itself: nine per-path levels under twelve dense
    # ones, three per-path mailbox levels (shapes only)
    real = EngineConfig.from_config(_config())
    assert real.rec.path_len - real.rec.dense_levels(2048) == 9
    assert real.mb.path_len - real.mb.dense_levels(4096) == 3


def test_the_cells_mix_through_the_scheduler_equals_the_reference(tmp_path):
    from benchmarks.lib import harness
    from toy import toy_bench

    from grapevine_tpu.config import setup_compile_cache

    setup_compile_cache()
    seed = 2**31 + 36
    cell = harness.Cell(toy_bench(tmp_path / "base"), CELL, seed,
                        str(tmp_path))
    try:
        obs = cell.drive(seed, 2.0, False, time.perf_counter())
    finally:
        cell.close()
    assert obs["observed"]["unanswered"] == 0
    rounds = cell.log.entries
    assert len(rounds) > 8
    ref = ReferenceEngine(cell.cfg)
    statuses, ops = set(), 0
    for i, e in enumerate(rounds):
        assert e["resps"] is not None and len(e["resps"]) == len(e["reqs"])
        forced = [d.record.msg_id
                  if r.request_type == C.REQUEST_TYPE_CREATE
                  and d.status_code == C.STATUS_CODE_SUCCESS else None
                  for r, d in zip(e["reqs"], e["resps"])]
        want = ref.handle_batch(e["reqs"], e["now"], forced)
        for j, (r, d, w) in enumerate(zip(e["reqs"], e["resps"], want)):
            assert d.pack() == w.pack(), (i, j, r.request_type)
            statuses.add((r.request_type, d.status_code))
        ops += len(e["reqs"])
    # all four request types were answered with success somewhere
    assert {t for t, s in statuses if s == C.STATUS_CODE_SUCCESS} == {
        C.REQUEST_TYPE_CREATE, C.REQUEST_TYPE_READ, C.REQUEST_TYPE_UPDATE,
        C.REQUEST_TYPE_DELETE}
    assert ops > 8 * cell.cfg.batch_size
    health = cell.engine.health()
    assert health["stash_overflow"] == 0
    assert health["messages"] == ref.message_count() > 0
    assert health["recipients"] == ref.recipient_count() > 0
