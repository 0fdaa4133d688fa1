"""The row-placement kernel (oblivious/pallas_place.py) in interpret
mode against the jnp scatter it stands in for on a TPU: bit for bit the
same plane, alone, under ``_path_scatter`` with the round's ``owner``
mask, and on the four-device CPU mesh with each chip's ``mine``.

What the chip's compiler makes of it is tests/test_mosaic_lowering.py's
to hold; what it does on the chip, chip_smoke.py's kernel phase.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from grapevine_tpu.oblivious.pallas_place import place_rows
from grapevine_tpu.oram import path_oram
from grapevine_tpu.oram.path_oram import _path_scatter
from grapevine_tpu.parallel.mesh import TREE_AXIS, make_mesh

U32 = jnp.uint32


def _case(n, tiles, n_dense, n_sparse, seed, drop=0.25):
    """A plane ``[n, tiles, 128]`` of noise, and a write-back as the
    round hands it over: a dense range first (contiguous targets from
    row 3, every one owned), then per-path rows at unique random
    targets past it, a share of them not owned."""
    rng = np.random.default_rng(seed)
    plane = rng.integers(0, 2**32, (n, tiles, 128), dtype=np.uint32)
    dense = np.arange(3, 3 + n_dense)
    sparse = rng.permutation(np.arange(3 + n_dense, n))[:n_sparse]
    path_b = np.concatenate([dense, sparse]).astype(np.uint32)
    owner = np.concatenate(
        [np.ones(n_dense, bool), rng.random(n_sparse) >= drop])
    rows = rng.integers(
        0, 2**32, (path_b.shape[0], tiles * 128), dtype=np.uint32)
    return plane, path_b, owner, rows


def _jnp_scatter(plane, path_b, owner, rows):
    n = plane.shape[0]
    tgt = np.where(owner, path_b, n)
    return jnp.asarray(plane).at[tgt].set(
        jnp.asarray(rows).reshape(-1, *plane.shape[1:]), mode="drop",
        unique_indices=True)


@pytest.mark.parametrize(
    "tiles,n,n_dense,n_sparse",
    [
        # the mailbox row and the records row; 5 + 32 rows are more
        # than the copies in flight and no multiple of them
        (48, 96, 5, 32),
        (8, 256, 29, 72),
        # fewer rows than copies in flight
        (8, 64, 0, 7),
        (8, 64, 9, 0),
    ],
)
def test_the_kernel_places_what_the_jnp_scatter_places(
    tiles, n, n_dense, n_sparse
):
    plane, path_b, owner, rows = _case(n, tiles, n_dense, n_sparse, tiles + n)
    want = _jnp_scatter(plane, path_b, owner, rows)
    tgt = jnp.asarray(np.where(owner, path_b, n).astype(np.int32))
    got = place_rows(
        jnp.asarray(plane), tgt,
        jnp.asarray(rows).reshape(-1, tiles, 128), interpret=True)
    assert got.shape == plane.shape
    assert np.array_equal(np.asarray(got), np.asarray(want))
    # and the rows no copy targets are the plane's own
    kept = np.setdiff1d(np.arange(n), path_b[owner])
    assert np.array_equal(np.asarray(got)[kept], plane[kept])


def test_a_row_no_one_owns_starts_no_copy():
    """Every target dropped: the plane comes back as it went in."""
    plane, path_b, owner, rows = _case(64, 8, 4, 20, 5)
    got = place_rows(
        jnp.asarray(plane), jnp.full(path_b.shape, 64, jnp.int32),
        jnp.asarray(rows).reshape(-1, 8, 128), interpret=True)
    assert np.array_equal(np.asarray(got), plane)


@pytest.fixture
def dma_everywhere(monkeypatch):
    """``_path_scatter`` as a TPU resolves it, on the CPU: planes that
    store their rows as whole memory tiles go by the kernel (interpret
    mode here). The one question the program asks of the backend, so
    the one thing a test steers; there is no option for it."""
    monkeypatch.setattr(path_oram, "places_by_dma", lambda tree: tree.ndim == 3)


@pytest.mark.parametrize("tiles", [48, 8])
@pytest.mark.parametrize("masked", [False, True])
def test_path_scatter_by_dma_equals_path_scatter_by_xla(
    dma_everywhere, tiles, masked
):
    plane, path_b, owner, rows = _case(128, tiles, 13, 40, tiles + masked)
    if not masked:
        owner = np.ones_like(owner)
    want = _jnp_scatter(plane, path_b, owner, rows)
    got = jax.jit(_path_scatter, static_argnums=(3,))(
        jnp.asarray(plane), jnp.asarray(path_b), jnp.asarray(rows), None,
        jnp.asarray(owner) if masked else None)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_narrow_planes_keep_the_xla_scatter(dma_everywhere):
    """The slot-index, nonce and leaf planes, and a value plane under
    eight tiles, are 2-D: no kernel is traced for them on any backend."""
    rng = np.random.default_rng(0)
    for width in (4, 2, 380):
        plane = jnp.asarray(rng.integers(0, 2**32, (64, width), dtype=np.uint32))
        text = jax.jit(_path_scatter, static_argnums=(3,)).lower(
            plane, jnp.arange(9, dtype=U32), plane[:9], None,
            jnp.ones((9,), jnp.bool_)).as_text()
        assert "scatter" in text and "pallas" not in text and "while" not in text


@pytest.mark.parametrize("tiles", [48, 8])
def test_sharded_path_scatter_by_dma_equals_one_chip(dma_everywhere, tiles):
    """Under ``shard_map`` over four CPU devices each chip places the
    rows it owns (``mine & owner``) into its quarter of the plane, and
    the quarters laid end to end are the one-chip plane."""
    assert len(jax.devices()) >= 4, "conftest forces an 8-device CPU mesh"
    plane, path_b, owner, rows = _case(128, tiles, 21, 50, 7 + tiles)
    want = _jnp_scatter(plane, path_b, owner, rows)
    fn = jax.jit(jax.shard_map(
        lambda tree, b, vals, own: _path_scatter(
            tree, b, vals, TREE_AXIS, own),
        mesh=make_mesh(jax.devices()[:4]),
        in_specs=(P(TREE_AXIS), P(), P(), P()), out_specs=P(TREE_AXIS),
        check_vma=False,
    ))
    got = fn(jnp.asarray(plane), jnp.asarray(path_b), jnp.asarray(rows),
             jnp.asarray(owner))
    assert np.array_equal(np.asarray(got), np.asarray(want))
    # every chip wrote: the per-path rows fall in all four quarters
    assert {int(b) // 32 for b in path_b[owner]} == {0, 1, 2, 3}


# ----------------------------------------------------------------------
# the audits follow the code: traced as a TPU traces it (the kernel in
# place of the scatter), the write-back is still rows written at public
# addresses
# ----------------------------------------------------------------------


def _load_tool(name):
    import importlib.util
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(repo, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_cost_ledger_counts_the_kernel_as_rows_written():
    """``analysis/costmodel.py``'s analytic rows equal the traced
    census at a wide-row geometry in both forms: the jnp scatter the
    CPU traces, and the placement kernel a TPU traces, counted as the
    rows it writes (one DMA each) and not as a plane walked."""
    from grapevine_tpu.analysis import costmodel as cm
    from grapevine_tpu.analysis.jaxpr_walk import as_a_tpu_traces, census

    name, cfg, b = cm.audit_oram_configs()[-1]
    assert name == "flat_k2_wide_row" and cfg.stored_row_shape == (8, 128)
    on_cpu = cm.cross_validate_round(cfg, b)
    assert not census(cm.trace_oram_round(cfg, b)).get("pallas_call")
    with as_a_tpu_traces():
        on_tpu = cm.cross_validate_round(cfg, b)
        traced = census(cm.trace_oram_round(cfg, b))
    assert traced["pallas_call"] == 1 and traced["dma_start"] == 1
    plane = ((cfg.n_buckets_padded, 8, 128), 1)
    rows = cfg.fetched_bucket_rows(b)
    assert on_tpu == on_cpu and on_tpu[plane] == (rows, rows)


def test_the_kernels_addresses_do_not_depend_on_contents():
    """``tools/check_oblivious.py``'s audit of the write-back as a TPU
    traces it, one chip and sharded: with the rows and the plane secret
    and no allowlist, nothing reaches a DMA index or a ``pl.when``
    predicate (the analyzer's mutant ``dma_target_from_contents``,
    tests/test_oblint.py, is the control that something can)."""
    reports = _load_tool("check_oblivious").audit_dma_write_back()
    assert [r.name for r in reports] == [
        "dma_write_back/one_chip", "dma_write_back/sharded"]
    for rep in reports:
        assert rep.ok, rep.summary()
        assert rep.census["dma_start"] == 1 and not rep.census.get("scatter")


def test_the_sharded_rebase_stays_in_range_under_the_kernel():
    """``tools/check_ranges.py``: the rebase and the cast of the
    sharded write-back are the reviewed pair in both forms, and the
    kernel's own arithmetic adds no finding."""
    from grapevine_tpu.analysis.allowlist import RANGE_ALLOWLIST

    tool = _load_tool("check_ranges")
    for by_dma in (False, True):
        rep = tool.audit_sharded_path_scatter(
            RANGE_ALLOWLIST, 5, by_dma=by_dma)
        assert not rep.findings, rep.summary()
        assert set(rep.allowed) == {
            "sub@oram/path_oram.py:_path_scatter",
            "convert_element_type@oram/path_oram.py:_path_scatter"}


def test_chip_smokes_placement_phase_rehearses_on_the_cpu(capsys):
    """``chip_smoke.py``'s kernel phase meets the placement kernel
    before a cell does; here its check runs at a toy size, interpreted."""
    import json

    import chip_smoke

    chip_smoke.placement_phase(22, n=128, tiles=48, n_dense=20, n_paths=45)
    said = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert said["phase"] == "kernels.placement" and said["ok"]
    assert said["rows"] == 65 and said["rows_written"] <= 65
    assert said["row_words"] == 6144 and said["interpret"]


def test_the_dma_gauge_is_the_rows_the_traced_round_places():
    """``grapevine_round_dma_placed_rows{tree}`` is set once from the
    geometry and the backend: every row a tree's passes fetch where its
    value plane stores rows as whole memory tiles and the engine is on
    a TPU, else 0. Held to that arithmetic at the toy twin of
    ``host4-sharded-2p23`` on one device (the real row widths), to the
    rows the placement kernels of the traced round are handed, and to
    0 on the CPU."""
    import json
    import os

    from grapevine_tpu.analysis.jaxpr_walk import as_a_tpu_traces, plane_rows
    from grapevine_tpu.config import GrapevineConfig
    from grapevine_tpu.engine.batcher import GrapevineEngine, pack_batch

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "tests", "data", "configs", "host4-sharded-2p23.json")
    with open(path) as f:
        knobs = dict(json.load(f)["grapevine_config"], shards=1)
    eng = GrapevineEngine(GrapevineConfig(**knobs))
    ecfg = eng.ecfg
    gauge = eng.metrics.registry.get("grapevine_round_dma_placed_rows")
    assert gauge.get(tree="rec") == gauge.get(tree="mb") == 0  # the CPU
    assert eng.dma_placed_rows() == {"rec": 0, "mb": 0}
    assert ecfg.rec.stored_row_shape == (8, 128)
    assert ecfg.mb.stored_row_shape == (48, 128)
    b, bd = ecfg.batch_size, ecfg.batch_size * ecfg.mb_choices
    want = {"rec": ecfg.rec.fetched_bucket_rows(b),
            "mb": 2 * ecfg.mb.fetched_bucket_rows(bd)}
    planes = {
        tree: ((cfg.n_buckets_padded, *cfg.stored_row_shape), 1)
        for tree, cfg in (("rec", ecfg.rec), ("mb", ecfg.mb))}
    with as_a_tpu_traces():
        assert eng.dma_placed_rows() == want
        traced = jax.make_jaxpr(eng._step_jit, static_argnums=(0,))(
            ecfg, eng.state, pack_batch([], ecfg.batch_size, 1_700_000_000))
    placed = {
        tree: sum(rows for op, rows in moved if op == "pallas_call")
        for tree, moved in plane_rows(traced, planes).items()}
    assert placed == want and want["mb"] > 0 < want["rec"]
    # and nothing scatters into those planes beside the kernels
    assert not [
        op for moved in plane_rows(traced, planes).values()
        for op, _ in moved if op.startswith("scatter")]
