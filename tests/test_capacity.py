"""Capacity scaling evidence: 2^24 messages on a v5e-8 pod.

BASELINE config 4 names a 2^24-capacity expiry sweep; one v5e chip has
~16 GB HBM, and 2^24 1-KB records are 17 GB of raw payload — the target
capacity is a *pod* configuration by construction, which is exactly the
sharding story (SURVEY.md §2c: bucket-tree sharded across chips,
BASELINE config 5). Evidence here comes in two tiers:

- an always-run geometry test pinning the arithmetic: at 2^24 and tree
  density 4 the records tree is 32 GB → 4 GB/chip on an 8-way mesh,
  comfortably inside HBM next to the mailbox tree and position map; and
  the per-chip shard is byte-identical to the single-chip
  2^20-at-density-2 tree the real-TPU bench runs (bench.py) — so the
  pod shape is the benched shape, 8 times over;
- a gated big test (GRAPEVINE_BIG_TESTS=1, default 2^23 ⇒ 16 GB
  sharded over the 8-device CPU mesh; GRAPEVINE_BIG_CAP_LOG2=24 for
  full scale on a multi-core host) that actually instantiates the
  engine, runs one batched CRUD round and one expiry sweep, and checks
  consistency — the SGX_MODE=SW-style simulation of the pod (reference
  .github/workflows/ci.yaml:15-16).
"""

import os

import numpy as np
import pytest

from grapevine_tpu.config import GrapevineConfig
from grapevine_tpu.engine.state import EngineConfig

V5E_HBM = 16 * 2**30
MESH = 8


def _tree_bytes(o) -> int:
    """HBM bytes of one ORAM's device-resident arrays (tree + nonces)."""
    z, v = o.bucket_slots, o.value_words
    per_bucket = z * v * 4 + z * 4 + 8  # values + slot idx + nonce
    return o.n_buckets_padded * per_bucket


def pod_config() -> GrapevineConfig:
    return GrapevineConfig(
        max_messages=1 << 24,
        max_recipients=1 << 14,
        batch_size=1024,
        stash_size=1024,
        tree_density=4,
    )


def test_pod_capacity_geometry():
    ecfg = EngineConfig.from_config(pod_config())
    rec_b, mb_b = _tree_bytes(ecfg.rec), _tree_bytes(ecfg.mb)
    # sharded axis 0 divides evenly across the mesh (n_buckets_padded is
    # a power of two, path_oram.py:n_buckets_padded)
    assert ecfg.rec.n_buckets_padded % MESH == 0
    assert ecfg.mb.n_buckets_padded % MESH == 0
    per_chip = (rec_b + mb_b) // MESH
    # replicated state (posmap + freelist + stash) rides along on every chip
    replicated = ecfg.rec.blocks * 4 * 2 + ecfg.mb.blocks * 4
    assert per_chip + replicated < V5E_HBM // 2, (
        f"per-chip {(per_chip + replicated) / 2**30:.1f} GB must leave "
        "headroom for working buffers"
    )
    # the per-chip shard is byte-for-byte the tree the single-chip bench
    # runs: 2^20 capacity at density 2 (bench.py batched_read/zipf/expiry
    # all use cap 2^20) — so the pod shape is the benched shape, 8×
    single = EngineConfig.from_config(
        GrapevineConfig(
            max_messages=1 << 20,
            max_recipients=1 << 14,
            batch_size=1024,
            stash_size=1024,
            tree_density=2,
        )
    )
    assert _tree_bytes(ecfg.rec) // MESH == _tree_bytes(single.rec)
    # capacity really is 2^24: enough tree slots for every message
    assert ecfg.rec.n_buckets * ecfg.rec.bucket_slots >= 1 << 24


def _held_to_its_file(config_name: str):
    """One ``benchmarks/configs/<name>.json`` and what its
    ``grapevine_config`` resolves to, shapes only (no tree allocated):
    both trees' round layout is held to the file's ``resolves_to``.
    Returns ``(spec, cfg, ecfg, state shapes, state bytes)``."""
    import json

    import jax

    from grapevine_tpu.engine.state import init_engine

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "configs",
        f"{config_name}.json")
    with open(path) as f:
        spec = json.load(f)
    cfg = GrapevineConfig(**spec["grapevine_config"])
    ecfg = EngineConfig.from_config(cfg)
    for tree, oram, accesses in (
            ("records", ecfg.rec, cfg.batch_size),
            ("mailbox", ecfg.mb, cfg.batch_size * ecfg.mb_choices)):
        want = spec["resolves_to"][tree]
        assert oram.path_len == want["path_len"]
        assert oram.dense_levels(accesses) == want["dense_levels"]
        assert oram.fetched_bucket_rows(accesses) == want["fetched_bucket_rows"]
        assert oram.perpath_bucket_rows(accesses) == want["perpath_bucket_rows"]
    state = jax.eval_shape(lambda: init_engine(ecfg, 0))
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(state))
    return spec, cfg, ecfg, state, nbytes


def _mailbox_pad_bytes(ecfg) -> int:
    """What PR 44's whole-tile mailbox row adds to the state: 64 pad
    words a bucket row (6,080 stored as 6,144) of the tree plane and of
    the tree-top cache plane. The records row, 1,024 words, has none."""
    mb, rec = ecfg.mb, ecfg.rec
    assert rec.stored_row_words == rec.val_row_words == 1024
    assert (mb.val_row_words, mb.stored_row_words) == (6080, 6144)
    return 4 * 64 * (mb.n_buckets_padded + mb.cache_buckets)


def test_the_2p16_recipient_deployment_resolves_to_what_its_file_says():
    """``benchmarks/configs/chipshare-2p20-r2p16.json`` (the bus whose
    mailboxes can fill its message store) states what its
    ``grapevine_config`` resolves to; shapes only, no tree allocated:
    a mailbox tree of 15 levels of which 4,096 accesses a pass cover
    13, so 8,192 of its 16,368 rows a pass are per-path rows."""
    spec, cfg, ecfg, _, state_bytes = _held_to_its_file(
        "chipshare-2p20-r2p16")
    assert spec["grapevine_config"] == {
        "max_messages": 1 << 20, "max_recipients": 1 << 16,
        "batch_size": 2048, "tree_density": 2}
    said = spec["resolves_to"]
    mb, want = ecfg.mb, said["mailbox"]
    assert (mb.path_len, mb.dense_levels(4096)) == (15, 13)
    assert mb.n_buckets == want["buckets"] == 32767
    assert ecfg.mb_table_buckets == 1 << 15 and mb.leaves == 1 << 14
    assert want["accesses_per_pass"] == 4096 < mb.leaves
    # index row, value row and nonce of one bucket: the block words the
    # file counts, and the row at rest, stored on whole lane tiles
    # (the file's figure predates PR 44; the file is the benchmark's)
    assert 4 * (4 + mb.val_row_words + 2) == want["bucket_bytes"] == 24344
    assert 4 * (mb.row_words + 2) == 24600
    # the file's figure predates PR 30, which took four u32 scalars of
    # delayed-eviction book-keeping (two a tree) out of the state, and
    # PR 44, which added the mailbox row's pad; the file is the
    # benchmark's, for a `benchmark` PR to bring up to date
    assert _mailbox_pad_bytes(ecfg) == 8_392_448
    assert state_bytes == said["state_bytes"] - 16 + 8_392_448 == 5_135_859_056
    assert cfg.mailbox_cap == spec["guarantees"]["mailbox_cap"] == 62
    assert spec["guarantees"]["max_recipients"] == cfg.max_recipients
    # its parent's mailbox tree is one the batch covers whole
    parent = EngineConfig.from_config(GrapevineConfig(**dict(
        spec["grapevine_config"], max_recipients=1 << 12)))
    assert parent.mb.perpath_bucket_rows(4096) == 0
    assert parent.mb.fetched_bucket_rows(4096) == 2032


def test_the_chips_real_share_resolves_to_what_its_file_says():
    """``benchmarks/configs/chipshare-2p21-r2p17.json`` is one chip of
    the v5e-8 bus at its own share (2^24 buckets over 8 chips), with
    nothing cut; shapes only, no tree allocated. It is the shape no
    smaller configuration has: nine per-path record levels under twelve
    dense ones, three per-path mailbox levels, a records value plane of
    exactly 2^31 words, 10.25 GB of state."""
    spec, cfg, ecfg, state, state_bytes = _held_to_its_file(
        "chipshare-2p21-r2p17")
    assert spec["reduced"] == {} and spec["chips"] == 1
    assert spec["grapevine_config"] == {
        "max_messages": (1 << 24) // MESH, "max_recipients": 1 << 17,
        "batch_size": 2048, "tree_density": 2}
    said = spec["resolves_to"]
    rec, mb = ecfg.rec, ecfg.mb
    assert (rec.path_len, rec.dense_levels(2048)) == (21, 12)
    assert rec.perpath_bucket_rows(2048) == 9 * 2048
    assert (mb.path_len, mb.dense_levels(4096)) == (16, 13)
    assert mb.perpath_bucket_rows(4096) == 3 * 4096
    assert mb.n_buckets == said["mailbox"]["buckets"] == 65535
    assert ecfg.mb_table_buckets == 1 << 16 and mb.leaves == 1 << 15
    assert said["mailbox"]["accesses_per_pass"] == 4096 < mb.leaves
    # the block words the file counts, and the row at rest (the file's
    # figure predates PR 44; the file is the benchmark's)
    assert 4 * (4 + mb.val_row_words + 2) == (
        said["mailbox"]["bucket_bytes"]) == 24344
    assert 4 * (mb.row_words + 2) == 24600
    # the first buffer of the program that holds 2^31 elements
    assert state.rec.tree_val.shape == (1 << 21, 8, 128)
    assert state.rec.tree_val.size == 1 << 31
    # 6,080 block words stored as 48 whole lane tiles (PR 44)
    assert state.mb.tree_val.shape == (1 << 16, 48, 128)
    assert state.mb.cache_val.shape == (15, 6144)
    # the file's figure predates PR 44; the file is the benchmark's
    assert _mailbox_pad_bytes(ecfg) == 16_781_056
    assert state_bytes == said["state_bytes"] + 16_781_056 == 10_270_604_656
    g = spec["guarantees"]
    assert cfg.mailbox_cap == g["mailbox_cap"] == 62
    assert (g["max_messages"], g["max_recipients"]) == (
        cfg.max_messages, cfg.max_recipients)
    # recipients in its sibling's ratio, and the cap can fill the store
    assert cfg.max_messages // cfg.max_recipients == (1 << 20) // (1 << 16)
    assert (cfg.max_recipients // 2) * cfg.mailbox_cap >= cfg.max_messages


def test_the_durable_deployment_resolves_to_what_its_file_says(tmp_path):
    """``benchmarks/configs/chipshare-2p21-r2p17-durable.json`` is
    ``chipshare-2p21-r2p17`` with ``--state-dir``; shapes only, no tree
    allocated. The round is its parent's key for key; what it adds is
    held here: the mapping the server's ``durability`` takes, the
    journal frame every round writes (the whole batch, 2048 x 1,020 B,
    plus header and seal), the checkpoint's bytes (the state, plus
    head, seq, manifest and seal), and the cadence's arithmetic."""
    import json

    from grapevine_tpu.config import DurabilityConfig
    from grapevine_tpu.engine import checkpoint as cp
    from grapevine_tpu.engine.journal import _HEADER, BatchJournal
    from grapevine_tpu.engine.state import state_spec

    spec, cfg, ecfg, _, state_bytes = _held_to_its_file(
        "chipshare-2p21-r2p17-durable")
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "configs",
            "chipshare-2p21-r2p17.json")) as f:
        parent = json.load(f)
    assert spec["grapevine_config"] == parent["grapevine_config"]
    assert spec["reduced"] == {} and spec["chips"] == 1
    said = spec["resolves_to"]
    for tree in ("records", "mailbox"):
        assert said[tree] == parent["resolves_to"][tree]
    # the file's figures predate PR 44's mailbox pad; the file is the
    # benchmark's, for a `benchmark` PR to bring up to date
    pad = _mailbox_pad_bytes(ecfg)
    assert state_bytes == said["state_bytes"] + pad == 10_270_604_656
    for k, v in parent["guarantees"].items():
        assert spec["guarantees"][k] == v
    assert "fsynced before it dispatches" in spec["guarantees"]["durability"]
    # the mapping builds the deployment OPERATIONS.md 11 documents
    fields = spec["server"]["durability"]
    dcfg = DurabilityConfig.coerce(fields)
    assert dcfg.journal_fsync_every == 1 and dcfg.seal_key_file is None
    assert dcfg.state_dir == os.path.abspath(fields["state_dir"])
    assert "/.scratch/backlog-durable-1chip-2p21/" in dcfg.state_dir
    # no cadence checkpoint can fall into a run: N is a power of two,
    # never under 512, and the least with stall / (N x period) <= 5 %
    n = dcfg.checkpoint_every_rounds
    sizing = spec["assumed"]["checkpoint_every_rounds"]
    assert n >= 512 and n & (n - 1) == 0 and sizing["N"] == n
    stall, period = sizing["checkpoint_stall_s"], sizing["round_period_s"]
    assert stall / (n * period) <= 0.05
    assert n == 512 or stall / (n // 2 * period) > 0.05
    # a round's frame: header, nonce, the whole batch, tag
    body = 17 + cfg.batch_size * 1020
    journal = BatchJournal(str(tmp_path), bytes(32), ecfg)
    assert max(journal._valid_blob_lens) == 12 + body + 32
    assert said["journal_frame_bytes"] == _HEADER.size + 12 + body + 32
    assert said["journal_frame_bytes"] == 2_089_037
    # the checkpoint: head, nonce, seq, manifest length, manifest, every
    # leaf, tag
    manifest = cp._manifest(ecfg, state_spec(ecfg)[1])
    # the file's figure also predates PR 46's (tiles, 128) rows: the
    # manifest spells the two value planes' shapes three bytes longer
    spelled = len(manifest) - len(
        manifest.replace(b"[2097152,8,128]", b"[2097152,1024]")
        .replace(b"[65536,48,128]", b"[65536,6144]"))
    assert spelled == 3
    assert said["checkpoint_bytes"] + pad + spelled == (
        len(cp.MAGIC) + 4 + 12 + 8 + 4 + len(manifest) + state_bytes + 32)
    assert said["checkpoint_bytes"] == 10_253_824_277


def test_the_runbook_deployment_resolves_to_what_its_file_says_and_holds_its_siblings():
    """``benchmarks/configs/chipshare-2p21-r2p17-runbook.json`` is
    ``chipshare-2p21-r2p17`` with the runbook's three options on
    together; shapes only, no tree allocated. It is held to its three
    sibling files key for key: the round's geometry is the parent's, the
    TTL and its guarantee are the TTL file's, the ``durability`` mapping
    (but for the state directory) and its guarantee are the durable
    file's; and the ``leakmon`` mapping is what the CLI passes for a
    bare ``--leakmon``."""
    import json

    from grapevine_tpu.config import DurabilityConfig
    from grapevine_tpu.engine.journal import _HEADER, _SEAL_OVERHEAD
    from grapevine_tpu.obs.leakmon import LeakMonitorConfig
    from grapevine_tpu.server.cli import _leakmon_config, build_parser

    spec, cfg, ecfg, _, state_bytes = _held_to_its_file(
        "chipshare-2p21-r2p17-runbook")
    configs = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "configs")
    parent, ttl, durable = (
        json.load(open(os.path.join(configs, f"chipshare-2p21-r2p17{x}.json")))
        for x in ("", "-ttl1d", "-durable"))
    assert spec["reduced"] == {} and spec["chips"] == 1
    assert spec["grapevine_config"] == ttl["grapevine_config"] == dict(
        parent["grapevine_config"], expiry_period=86400)
    assert durable["grapevine_config"] == parent["grapevine_config"]
    assert cfg.expiry_period == 86400
    said = spec["resolves_to"]
    for tree in ("records", "mailbox"):
        assert said[tree] == parent["resolves_to"][tree] == (
            ttl["resolves_to"][tree]) == durable["resolves_to"][tree]
    assert said["state_bytes"] == parent["resolves_to"]["state_bytes"]
    assert state_bytes == said["state_bytes"] + _mailbox_pad_bytes(ecfg)
    assert state_bytes == 10_270_604_656
    assert said["journal_frame_bytes"] == (
        durable["resolves_to"]["journal_frame_bytes"]) == 2_089_037
    # a sweep's frame: header, seal, kind and three words
    assert said["sweep_frame_bytes"] == _HEADER.size + _SEAL_OVERHEAD + 13
    # the guarantees: the parent's, the two siblings' own word for word,
    # and the auditor's
    g = spec["guarantees"]
    for k, v in parent["guarantees"].items():
        assert g[k] == v
    assert g["expiry"] == ttl["guarantees"]["expiry"]
    assert g["durability"] == durable["guarantees"]["durability"]
    assert "PASS" in g["audit"] and "dropped" in g["audit"]
    assert set(g) == set(parent["guarantees"]) | {
        "expiry", "durability", "audit"}
    # the server's three mappings
    server = spec["server"]
    assert set(server) == {"trace_ring_size", "durability", "leakmon"}
    assert server["trace_ring_size"] == parent["server"]["trace_ring_size"]
    fields, theirs = server["durability"], durable["server"]["durability"]
    assert {k: v for k, v in fields.items() if k != "state_dir"} == {
        k: v for k, v in theirs.items() if k != "state_dir"}
    dcfg = DurabilityConfig.coerce(fields)
    assert "/.scratch/backlog-runbook-1chip-2p21/" in dcfg.state_dir
    assert dcfg.journal_fsync_every == 1
    assert dcfg.checkpoint_every_rounds == 8192 == (
        spec["assumed"]["checkpoint_every_rounds"]["N"])
    # `--leakmon` with no other flag: every documented default
    args = build_parser().parse_args(["--leakmon"])
    assert LeakMonitorConfig.coerce(server["leakmon"]) == (
        _leakmon_config(args)) == LeakMonitorConfig()
    # what its siblings assume, it assumes
    for k in ("max_recipients", "batch_size", "tree_density",
              "scheduler_window"):
        assert spec["assumed"][k] == parent["assumed"][k]
    assert spec["assumed"]["journal"] == durable["assumed"]["journal"]
    assert len(spec["source"]) < 200


def test_the_four_chip_host_at_each_chips_share_resolves_to_what_its_file_says():
    """``benchmarks/configs/host4-sharded-2p23.json`` is one four-chip
    host of the v5e-8 bus, half of it, with each chip holding exactly
    what ``chipshare-2p21-r2p17`` holds on one: nothing is cut. Shapes
    only, no tree allocated: 23 record levels of which 12 are dense, 18
    mailbox levels of which 13 are (five per-path ones under
    ``shard_map``, where ``host4-sharded-2p22`` has none), 41 GB of
    state in equal quarters, 1.5 GB of value rows all-reduced a round."""
    import json

    import jax
    from jax.sharding import PartitionSpec as P

    from grapevine_tpu.parallel.mesh import (
        TREE_AXIS,
        engine_state_specs,
        make_mesh,
        validate_sharded_geometry,
    )

    spec, cfg, ecfg, state, state_bytes = _held_to_its_file(
        "host4-sharded-2p23")
    assert spec["reduced"] == {} and spec["chips"] == cfg.shards == 4
    assert spec["grapevine_config"] == {
        "max_messages": (1 << 24) // 2, "max_recipients": 1 << 19,
        "batch_size": 2048, "tree_density": 2, "shards": 4}
    said = spec["resolves_to"]
    rec, mb = ecfg.rec, ecfg.mb
    assert (rec.path_len, rec.dense_levels(2048)) == (23, 12)
    assert (rec.fetched_bucket_rows(2048),
            rec.perpath_bucket_rows(2048)) == (26_608, 11 * 2048)
    assert (mb.path_len, mb.dense_levels(4096)) == (18, 13)
    assert (mb.fetched_bucket_rows(4096),
            mb.perpath_bucket_rows(4096)) == (28_656, 5 * 4096)
    assert rec.n_buckets == said["records"]["buckets"] == (1 << 23) - 1
    assert mb.n_buckets == said["mailbox"]["buckets"] == 262_143
    for tree, oram in (("records", rec), ("mailbox", mb)):
        assert said[tree]["stored_row_words"] == oram.stored_row_words
        assert said["value_planes"][tree] == [oram.n_buckets_padded,
                                              oram.stored_row_words]
    assert state.rec.tree_val.shape == (1 << 23, 8, 128)
    assert state.mb.tree_val.shape == (1 << 18, 48, 128)
    assert state_bytes == said["state_bytes"] == 41_079_078_256
    # a quarter of each sharded plane a chip, and what is replicated
    # whole: the file's figure for one chip
    specs = engine_state_specs()
    held = []
    jax.tree.map(
        lambda sp, x: held.append(
            x.size * x.dtype.itemsize // (4 if sp == P(TREE_AXIS) else 1)),
        specs, state, is_leaf=lambda sp: isinstance(sp, P))
    assert sum(held) == said["state_bytes_held_by_a_chip"] == 10_321_722_736
    for oram in (rec, mb):
        assert oram.n_buckets_padded % 4 == 0
    # each chip's share is the one-chip deployment's, plane for plane
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "configs",
            "chipshare-2p21-r2p17.json")) as f:
        one = GrapevineConfig(**json.load(f)["grapevine_config"])
    share = EngineConfig.from_config(one)
    assert cfg.max_messages == 4 * one.max_messages
    assert cfg.max_recipients == 4 * one.max_recipients
    assert rec.n_buckets_padded == 4 * share.rec.n_buckets_padded
    assert mb.n_buckets_padded == 4 * share.mb.n_buckets_padded
    assert (rec.stored_row_words, mb.stored_row_words) == (
        share.rec.stored_row_words, share.mb.stored_row_words)
    assert (cfg.batch_size, cfg.mailbox_cap) == (one.batch_size,
                                                 one.mailbox_cap)
    # the rows a round hands to the all-reduce, value planes alone
    assert said["value_plane_bytes_all_reduced_a_round"] == 4 * (
        2 * 28_656 * 6144 + 26_608 * 1024) == 1_517_486_080
    g = spec["guarantees"]
    assert cfg.mailbox_cap == g["mailbox_cap"] == 62
    assert (g["max_messages"], g["max_recipients"]) == (
        cfg.max_messages, cfg.max_recipients)
    assert g["layout"] == "both trees held in equal quarters, one per chip"
    # the cap can fill the store from half of the recipient table, and
    # 2^18 recipients is the least power of two whose cap does
    assert (cfg.max_recipients // 2) * cfg.mailbox_cap >= cfg.max_messages
    assert (cfg.max_recipients // 4) * cfg.mailbox_cap < cfg.max_messages
    # the mesh takes it: shapes only, the devices stand in for chips
    validate_sharded_geometry(ecfg, make_mesh(jax.devices()[:4]))


def test_init_sharded_engine_matches_staged_init():
    """Shard-aware init is bit-identical to init-then-shard (threefry is
    deterministic under jit), at a shape small enough to stage both."""
    import jax
    import numpy as np

    from grapevine_tpu.engine.state import init_engine
    from grapevine_tpu.parallel import (
        init_sharded_engine,
        make_mesh,
        shard_engine_state,
    )

    cfg = GrapevineConfig(
        max_messages=256, max_recipients=32, mailbox_cap=4,
        batch_size=4, stash_size=64,
    )
    ecfg = EngineConfig.from_config(cfg)
    mesh = make_mesh(jax.devices()[:MESH])
    a = init_sharded_engine(ecfg, mesh, seed=7)
    b = shard_engine_state(init_engine(ecfg, seed=7), mesh)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.skipif(
    not os.environ.get("GRAPEVINE_BIG_TESTS"),
    reason="multi-GB instantiation; set GRAPEVINE_BIG_TESTS=1 to run",
)
def test_pod_2e24_round_and_sweep():
    """Defaults to half scale (2^23 ⇒ 16 GB sharded state) with batch
    256. Larger shapes DO run (bisected: 2^23 at B=1024 completes
    standalone) but sit on the edge of XLA CPU's collectives rendezvous
    terminate-timeout when 8 virtual devices timeslice one host core —
    the round's working-set psum is hundreds of MB per device, and a
    thread arriving tens of seconds late SIGABRTs the process. Real ICI
    moves that in milliseconds; this is simulation-infra timing, not a
    product limit. GRAPEVINE_BIG_CAP_LOG2 / GRAPEVINE_BIG_BATCH
    override the scale on beefier hosts."""
    import jax

    from grapevine_tpu.engine.expiry import expiry_sweep
    from grapevine_tpu.parallel import (
        init_sharded_engine,
        make_mesh,
        make_sharded_step,
    )

    cap_log2 = int(os.environ.get("GRAPEVINE_BIG_CAP_LOG2", "23"))
    # GRAPEVINE_BIG_MESH=1: single-device execution (no collectives) —
    # the path that carries full 2^24 scale on a one-core host, where
    # the 8-virtual-device rendezvous timeout (docstring) rules the
    # sharded form out. The program is the same engine_round_step the
    # mesh path runs under shard_map.
    mesh_n = int(os.environ.get("GRAPEVINE_BIG_MESH", str(MESH)))
    cfg = GrapevineConfig(
        max_messages=1 << cap_log2,
        max_recipients=1 << 14,
        batch_size=int(os.environ.get("GRAPEVINE_BIG_BATCH", "256")),
        stash_size=1024,
        tree_density=4,
    )
    ecfg = EngineConfig.from_config(cfg)
    if mesh_n > 1:
        assert len(jax.devices()) >= mesh_n
        mesh = make_mesh(jax.devices()[:mesh_n])
        # shard-aware init: the unsharded 32 GB state never exists anywhere
        state = init_sharded_engine(ecfg, mesh, seed=0)
        step = make_sharded_step(ecfg, mesh)
    else:
        from grapevine_tpu.engine.round_step import engine_round_step
        from grapevine_tpu.engine.state import init_engine

        state = jax.jit(lambda: init_engine(ecfg, seed=0))()
        step = jax.jit(
            lambda st, batch: engine_round_step(ecfg, st, batch),
            donate_argnums=0,
        )

    rng = np.random.default_rng(1)
    b = cfg.batch_size
    from grapevine_tpu.engine.state import ID_WORDS, KEY_WORDS, PAYLOAD_WORDS

    batch = {
        "req_type": np.ones((b,), np.uint32),  # all CREATEs
        "auth": rng.integers(1, 2**31, (b, KEY_WORDS)).astype(np.uint32),
        "msg_id": np.zeros((b, ID_WORDS), np.uint32),
        "recipient": rng.integers(1, 2**31, (b, KEY_WORDS)).astype(np.uint32),
        "payload": rng.integers(0, 2**31, (b, PAYLOAD_WORDS)).astype(np.uint32),
        "now": np.uint32(1_700_000_000),
    }
    state, resp, transcripts = step(state, batch)
    jax.block_until_ready(resp)
    from grapevine_tpu.wire import constants as C

    assert np.all(np.asarray(resp["status"]) == C.STATUS_CODE_SUCCESS)
    assert int(np.asarray(state.rec.overflow)) == 0
    assert np.asarray(transcripts).shape == (b, 2 * cfg.resolved_mailbox_choices + 1)

    # GRAPEVINE_BIG_SWEEP=0 skips the expiry sweep: the sweep dominates
    # wall clock (ChaCha over 2×32 GB at 2^24) and was already executed
    # at full scale single-device (BIGRUN_r4.md); the sharded-2^24
    # attempt targets the ROUND under collectives (round-4 review #6)
    if os.environ.get("GRAPEVINE_BIG_SWEEP", "1") == "0":
        return

    # donate: at 2^24 the 32 GB tree must not be double-buffered
    free_top_before = int(np.asarray(state.free_top))
    swept = jax.jit(expiry_sweep, static_argnums=(0,), donate_argnums=(1,))(
        ecfg, state, np.uint32(1_700_000_000 + 100), np.uint32(10)
    )
    jax.block_until_ready(swept.free_top)
    # every live record was older than the period → all expired
    assert int(np.asarray(swept.free_top)) == free_top_before + b
