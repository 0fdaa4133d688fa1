"""The repo's compiler checks: what a TPU v5e's compiler accepts,
checked without the chip (CPU-hosted).

Interpret-mode tests prove kernel SEMANTICS but not the Mosaic
contract. Two rounds of kernels passed interpret-mode CI and were then
refused on first contact with a chip (rank-1 block of 86 rows,
TPURUN_r5.jsonl), and the ``jax.export(platforms=("tpu",))`` gate that
replaced that lesson still passed an 8-row manual-DMA kernel pair that
Mosaic refuses outright ("Slice shape along dimension 1 must be
aligned to tiling (128), but is 4" — removed in PR 22): export stops
at lowering and never runs the Mosaic compile proper.

So the kernel cases here COMPILE — ``.lower(...).compile()`` against a
described, unattached ``v5e:2x2`` chip — at the real row widths of the
served geometry (2^20 messages / B=2048), and assert the Mosaic kernel
is in the compiled program. A compile that passes is not a chip run:
chip_smoke.py's kernel phase is where the same kernels execute.

The topology is described inside a module-scoped fixture, never at
import: only one process may load libtpu, and under pytest-xdist every
worker imports every test file — only the worker that RUNS this file
may touch it. Keep every compiler check in this one file.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax import export

from grapevine_tpu.oblivious.pallas_cipher import cipher_rows_pallas
from grapevine_tpu.oblivious.pallas_place import place_rows

U32 = jnp.uint32


@pytest.fixture(scope="module")
def one_chip():
    """A ``SingleDeviceSharding`` on one chip of a described v5e:2x2,
    with the persistent compile cache off around the module (a compile
    for an unattached chip is written to the cache but cannot be read
    back, so the next run would warn and compile again)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_for(chip, fn, *specs, donate=(), **static):
    """Compile ``fn`` for the described chip; return the compiled text."""
    specs = [
        jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip) for s in specs
    ]
    return (
        jax.jit(functools.partial(fn, **static), donate_argnums=donate)
        .lower(*specs).compile().as_text()
    )


def _wide_row_copies(text, rows=20464):
    """Ops that copy, pad or relay an array of ``rows`` wide value rows
    (stored ``(48, 128)`` / ``(8, 128)`` or flat at the stored width).
    At the jit boundary of a lone call the compiler relays its NARROW
    parameters (``[R,4]``, ``[R,2]``, a ``u32[R,6080]`` whose default
    layout is the transposed one); inside the round their producers
    hand them over as the kernel wants them."""
    import re

    return re.findall(
        rf"u32\[{rows},(?:\d+,128|6144|1024)\]\S* (?:pad|copy|transpose)\(",
        text)


def _s(*shape, dtype=U32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _served_tree(tree):
    """(padded buckets, fetched rows per round, Z, stored value words
    per row) of one tree of the geometry chip_smoke.py serves: 2^20 messages,
    2^12 recipients, B=2048, density 2, the TPU's knob defaults."""
    from grapevine_tpu.config import GrapevineConfig
    from grapevine_tpu.engine.state import EngineConfig

    cfg = GrapevineConfig(
        max_messages=1 << 20, max_recipients=1 << 12, batch_size=2048,
        tree_density=2,
    )
    ecfg = EngineConfig.from_config(cfg)
    oc = {"records": ecfg.rec, "mailbox": ecfg.mb}[tree]
    fetches = cfg.batch_size * (
        1 if tree == "records" else cfg.resolved_mailbox_choices
    )
    rows = fetches * (oc.path_len - oc.top_cache_levels)
    return (oc.n_buckets_padded, rows, oc.bucket_slots,
            oc.stored_row_words)


@pytest.mark.parametrize("tree", ["records", "mailbox"])
def test_cipher_kernel_compiles_for_v5e(one_chip, tree):
    _, r, z, zv = _served_tree(tree)
    text = _compile_for(
        one_chip, cipher_rows_pallas, _s(8), _s(r), _s(r, 2), _s(r, z),
        _s(r, zv), rounds=8, interpret=False,
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("zv", [6144, 6080, 1024])
def test_cipher_kernel_compiles_at_the_2p17_mailbox_shape(one_chip, zv):
    """20,464 fetched rows (a mailbox pass at 2^21 messages / 2^17
    recipients, no multiple of the 64-row tile: the last grid step is a
    partial block) of 4 + 6144 words, the row as it is stored since
    PR 44 (48 whole value tiles, the index words on lanes 0-3 of tile
    48); of 4 + 6080, a width the kernel must still compile (three block
    groups with the index words on lanes 64-67 of the last tile); and
    of 4 + 1024."""
    text = _compile_for(
        one_chip, cipher_rows_pallas, _s(8), _s(20464), _s(20464, 2),
        _s(20464, 4), _s(20464, zv), rounds=8, interpret=False,
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("zin,zv", [(6080, 6144), (1024, 1024)])
def test_cipher_kernel_compiles_on_rows_as_the_plane_stores_them(
    one_chip, zin, zv
):
    """Since PR 46 a wide value row is stored ``(tiles, 128)`` and the
    kernel reads and writes it so: the fetch takes ``[R, tiles, 128]``
    and returns flat rows, the write-back takes flat plaintext (the
    mailbox row without its pad) and returns ``[R, tiles, 128]``; no
    copy of the rows is made around either."""
    import re

    tiles = zv // 128
    fetch = _compile_for(
        one_chip, cipher_rows_pallas, _s(8), _s(20464), _s(20464, 2),
        _s(20464, 4), _s(20464, tiles, 128), rounds=8, interpret=False,
        zv=zv,
    )
    write = _compile_for(
        one_chip, cipher_rows_pallas, _s(8), _s(20464), _s(20464, 2),
        _s(20464, 4), _s(20464, zin), rounds=8, interpret=False, zv=zv,
        tiled_out=True,
    )
    for text in (fetch, write):
        assert "tpu_custom_call" in text
        assert f"u32[20464,{tiles},128]" in text
        assert not _wide_row_copies(text)
        assert not re.search(r" pad\(", text)


@pytest.mark.parametrize(
    "n,rpc,tiles,zin",
    [(1 << 14, 4096, 8, 1024), (1 << 12, 1024, 48, 6080)],
    ids=["records", "mailbox"],
)
def test_a_sweeps_chunk_is_rekeyed_where_it_lies_in_the_plane(
    one_chip, n, rpc, tiles, zin
):
    """The expiry sweep's scan (engine/expiry.py, PR 48): each chunk of
    ``rpc`` rows is decrypted out of the plane by the kernel (its blocks
    offset by the scalar-prefetched chunk index) and the plaintext is
    re-keyed into the same rows, the plane aliased through the kernel
    and the scan's carry: at the published chunk shapes the compiled
    loop holds the two kernels and no op that cuts a chunk out of the
    plane, pastes one back or copies the plane (a second copy of the
    8 GiB records plane does not fit the chip)."""
    import re

    zv = tiles * 128

    def sweep(key, epochs, idx, plane):
        def body(plane, i):
            cut = lambda a: jax.lax.dynamic_slice_in_dim(a, i * rpc, rpc)  # noqa: E731
            bid = i * U32(rpc) + jnp.arange(rpc, dtype=U32)
            ix, vl = cipher_rows_pallas(
                key, bid, cut(epochs), cut(idx), plane, rounds=8, zv=zv,
                chunk=i)
            ix, plane = cipher_rows_pallas(
                key, bid, cut(epochs) + U32(1), ix, vl[:, :zin], rounds=8,
                zv=zv, tiled_out=True, chunk=i, into=plane)
            return plane, ix

        return jax.lax.scan(body, plane, jnp.arange(n // rpc, dtype=U32))

    text = _compile_for(
        one_chip, sweep, _s(8), _s(n, 2), _s(n, 4), _s(n, tiles, 128),
        donate=(3,),
    )
    assert text.count("tpu_custom_call") >= 2
    assert "output_to_operand_aliasing" in text
    assert f"u32[{rpc},{tiles},128]" not in text  # no chunk cut or pasted
    assert not re.search(
        rf"u32\[{n},{tiles},128\]\S* (?:copy|dynamic-update-slice|fusion)\(",
        text)


def test_cipher_kernel_pads_the_mailbox_plaintext_itself(one_chip):
    """The write-back's call: 6,080-word plaintext rows in, 6,144-word
    stored rows out, the 64 pad words' keystream stored beside the last
    value words, and no padded copy of the rows made around the
    kernel."""
    import re

    text = _compile_for(
        one_chip, cipher_rows_pallas, _s(8), _s(20464), _s(20464, 2),
        _s(20464, 4), _s(20464, 6080), rounds=8, interpret=False, zv=6144,
    )
    assert "tpu_custom_call" in text
    assert "u32[20464,6144]" in text
    assert not re.search(r" pad\(", text)


def test_cipher_rows_on_a_tpu_is_one_kernel_and_no_keystream_buffer(
    one_chip, monkeypatch
):
    """``cipher_rows`` as a TPU engine resolves it: the Mosaic kernel
    and nothing else of the rows' size — no ``u32[R,381,16]`` state
    planes or keystream (the j-major order's), no ``[R,6096]`` relayout,
    no ``[R,6084]`` masked copy. (At the jit boundary of this lone call
    the compiler transposes the value plane in and out, as it does for
    any ``u32[n,6080]`` parameter; inside the round the row gathers
    hand the kernel its ``{1,0}`` operand: PERF.md §5. Since PR 44 the
    plane is stored 6,144 words wide and is never transposed.)"""
    import re

    from grapevine_tpu.config import GrapevineConfig
    from grapevine_tpu.engine.state import EngineConfig
    from grapevine_tpu.oram.path_oram import cipher_rows

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ecfg = EngineConfig.from_config(GrapevineConfig(
        max_messages=1 << 21, max_recipients=1 << 17, batch_size=2048,
        tree_density=2,
    ))
    assert ecfg.mb.cipher_impl == "pallas" and ecfg.rec.cipher_impl == "pallas"
    mb = ecfg.mb
    z = mb.bucket_slots
    assert (z, mb.val_row_words, mb.stored_row_words) == (4, 6080, 6144)
    assert mb.stored_row_shape == (48, 128)
    # the fetch hands over rows as the plane stores them and takes flat
    # ones; the write-back hands over plaintext blocks and takes rows
    # for the plane
    for handed, back in (((48, 128), "u32[20464,6144]"),
                         ((6080,), "u32[20464,48,128]")):
        text = _compile_for(
            one_chip, functools.partial(cipher_rows, mb), _s(8), _s(20464),
            _s(20464, 2), _s(20464, z), _s(20464, *handed),
        )
        assert "tpu_custom_call" in text
        assert back in text.split("\n", 1)[0].split("->")[1]
        assert not _wide_row_copies(text)
        assert not re.search(r"u32\[\d+,381[,\]]", text)
        assert not re.search(r"u32\[\d+,6(?:0(?:96|84)|1(?:60|48))\]", text)
        assert " fusion(" not in text and " pad(" not in text


def test_cipher_kernel_compiles_at_the_row_count_the_chip_refused(one_chip):
    """172 rows of 4+380 words: the first chip window's rejection
    (TPURUN_r5.jsonl ``mosaic`` stage), kept as a regression case."""
    text = _compile_for(
        one_chip, cipher_rows_pallas, _s(8), _s(172), _s(172, 2),
        _s(172, 4), _s(172, 380), rounds=8, interpret=False,
    )
    assert "tpu_custom_call" in text


# ----------------------------------------------------------------------
# the whole phase-major engine round, at every corner of the knob matrix
# that is left (position map x tree-top cache): the slot-order masks,
# the one-hot matmuls, lax.sort, the admission walk's associative scan
# and the scatter tables must lower for a TPU just like the Pallas
# kernels. Export is enough for these eight (no Pallas kernel inside:
# the CPU resolves the jnp cipher); the Pallas round is compiled for
# real below.
# ----------------------------------------------------------------------


def _round_specs(cfg):
    from grapevine_tpu.engine.state import (
        EngineConfig,
        ID_WORDS,
        KEY_WORDS,
        PAYLOAD_WORDS,
        init_engine,
    )

    b = cfg.batch_size
    ecfg = EngineConfig.from_config(cfg)
    state = jax.eval_shape(lambda: init_engine(ecfg, 0))
    batch = {
        "req_type": _s(b),
        "auth": _s(b, KEY_WORDS),
        "msg_id": _s(b, ID_WORDS),
        "recipient": _s(b, KEY_WORDS),
        "payload": _s(b, PAYLOAD_WORDS),
        "now": _s(),
        "now_hi": _s(),
    }
    return ecfg, state, batch


@pytest.mark.parametrize("cache", [0, 4])
@pytest.mark.parametrize("posmap", ["flat", "recursive"])
@pytest.mark.parametrize(
    "geom",
    # (batch, max_messages, max_recipients, mailbox_cap, density)
    [(8, 64, 8, 4, 2), (16, 1 << 10, 1 << 6, 62, 4)],
    ids=["toy", "production-shaped"],
)
def test_engine_round_lowers_for_tpu(geom, posmap, cache):
    from grapevine_tpu.config import GrapevineConfig
    from grapevine_tpu.engine.round_step import engine_round_step

    b, cap, recips, mcap, density = geom
    ecfg, state, batch = _round_specs(GrapevineConfig(
        max_messages=cap,
        max_recipients=recips,
        mailbox_cap=mcap,
        batch_size=b,
        tree_density=density,
        bucket_cipher_rounds=8,
        posmap_impl=posmap,
        tree_top_cache_levels=cache,
    ))
    assert (ecfg.vphases_impl, ecfg.sort_impl) == ("dense", "xla")
    export.export(
        jax.jit(functools.partial(engine_round_step, ecfg)),
        platforms=("tpu",),
    )(state, batch)


# ----------------------------------------------------------------------
# the round at the real mailbox row (PR 44): 6,080 block words stored
# as 6,144, 48 whole lane tiles. What the chip's compiler makes of the
# value plane is the point: for ``u32[n,6080]`` its default layout is
# the transposed ``{0,1}``, so the round copied the plane whole after
# its entry and before its exit (4.7 + 4.6 ms of a 110.3 ms round at
# 2^21 / 2^17, ledger PR 43). Since PR 46 the plane stores that row as
# ``(48, 128)``, whole memory tiles on an untiled leading axis, so that
# the write-back places each row by one DMA. One compile, read by five
# cases.
# ----------------------------------------------------------------------


@pytest.fixture(scope="module")
def wide_row_round(one_chip):
    """``(compiled text, ecfg)`` of ``jit(engine_round_step)``, state
    donated, as a TPU engine resolves it (the Pallas cipher) at B = 2048
    and the real mailbox width, on a mailbox tree of 14 levels: the
    least that keeps a per-path level under the 13 dense ones (2^15
    recipients; the plane is ``u32[16384,48,128]``, 0.4 GB, described
    and never allocated)."""
    from grapevine_tpu.config import GrapevineConfig
    from grapevine_tpu.engine.round_step import engine_round_step

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        ecfg, state, batch = _round_specs(GrapevineConfig(
            max_messages=1 << 16, max_recipients=1 << 15, batch_size=2048,
            tree_density=2,
        ))
        assert ecfg.mb.cipher_impl == "pallas"
        assert ecfg.mb.perpath_bucket_rows(4096) == 4096
        place = lambda t: jax.tree.map(  # noqa: E731
            lambda s: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=one_chip),
            t,
        )
        text = (
            jax.jit(functools.partial(engine_round_step, ecfg),
                    donate_argnums=(0,))
            .lower(place(state), place(batch)).compile().as_text()
        )
    return text, ecfg


def _entry_ops(text):
    """``(name, result shape with layout, opcode, rest of the line)`` of
    every instruction of the module's ENTRY computation."""
    import re

    body = re.search(r"\nENTRY [^\n]*\n(.*?)\n\}", text, re.S).group(1)
    op = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\S+) ([\w\-]+)\((.*)$")
    return [m.groups() for m in map(op.match, body.split("\n")) if m]


def _mb_plane(ecfg):
    """The mailbox value plane as the compiled text names it: rows
    stored ``(48, 128)`` since PR 46."""
    n, shape = ecfg.mb.n_buckets_padded, ecfg.mb.stored_row_shape
    assert (n, shape) == (1 << 14, (48, 128))
    return f"u32[{n},48,128]"


def _mb_rows(ecfg):
    """Shapes the rows of a mailbox pass can take in the text: as the
    plane stores them, flat at the stored width, flat as blocks."""
    rows = ecfg.mb.fetched_bucket_rows(4096)
    assert rows == (1 << 13) - (1 << 4) + 4096
    return (f"u32[{rows},48,128]", f"u32[{rows},{ecfg.mb.stored_row_words}]",
            f"u32[{rows},{ecfg.mb.val_row_words}]")


def test_the_mailbox_plane_enters_and_leaves_the_round_row_major(
    wide_row_round
):
    """The parameter ``state.mb.tree_val`` and its result are laid out
    row-major, a bucket's 48 lane tiles six whole ``(8,128)`` memory
    tiles on an untiled leading axis: the rows the gathers and the
    placement kernel want, no transposed default to copy out of and
    back into, and no 2-D twin of the plane anywhere."""
    import re

    text, ecfg = wide_row_round
    plane = _mb_plane(ecfg)
    head = text.split("\n", 1)[0]
    layouts = re.findall(re.escape(plane) + r"\{([\d,]+:T\(8,128\))", head)
    assert layouts == ["2,1,0:T(8,128)"] * 2  # parameter and result
    n = ecfg.mb.n_buckets_padded
    assert f"u32[{n},{ecfg.mb.val_row_words}]" not in text
    assert f"u32[{n},{ecfg.mb.stored_row_words}]" not in text


def test_no_op_of_the_rounds_copies_or_relays_the_mailbox_plane(
    wide_row_round
):
    text, ecfg = wide_row_round
    plane = _mb_plane(ecfg)
    touching = [
        (name, opcode) for name, shape, opcode, rest in _entry_ops(text)
        if plane in shape or plane in rest.split(", metadata=")[0]
    ]
    assert touching  # the gathers and the two placements are there
    assert not [
        t for t in touching
        if t[1] in ("copy", "transpose", "reshape", "copy-start", "pad")
    ]


def _placements(text, plane):
    return [
        (name, rest) for name, shape, opcode, rest in _entry_ops(text)
        if shape.startswith(plane) and opcode == "custom-call"
        and "path_scatter/jit(place_rows)" in rest
    ]


def test_both_mailbox_scatters_write_into_their_operand(wide_row_round):
    """Each ``path_scatter`` of the value plane is the placement
    kernel (oblivious/pallas_place.py), a Mosaic custom call whose
    output is aliased onto the plane it was handed, its last operand:
    the parameter itself in round A, round A's result in round C. So a
    write-back touches the rows it places and not the plane."""
    import re

    text, ecfg = wide_row_round
    placed = _placements(text, _mb_plane(ecfg))
    assert len(placed) == 2
    for _, rest in placed:
        assert 'custom_call_target="tpu_custom_call"' in rest
        # output {} is operand 2 (targets 0, rows 1, the plane 2)
        assert re.search(
            r"output_to_operand_aliasing=\{\{\}: \(2, \{\}\)\}", rest)
    planes = [rest.split(", custom_call_target")[0].split(", ")[-1].rstrip(")")
              for _, rest in placed]
    assert "state_mb_tree_val" in planes[0]
    assert planes[1].lstrip("%") == placed[0][0]


def test_no_scatter_sort_or_permute_of_the_value_plane_is_left(
    wide_row_round
):
    """What PR 46 took out of the round: XLA's scatter of a share of
    rows this large sorted the targets, permuted the rows into a second
    copy and streamed the whole plane (9.0 + 2.2 ms a pass at 2^21 /
    2^17). Nothing under ``path_scatter`` now makes an array the size
    of the plane or of a pass's rows but the two placements, and the
    records plane, stored ``(8, 128)`` by the same rule, goes the same
    way."""
    text, ecfg = wide_row_round
    plane, rows = _mb_plane(ecfg), _mb_rows(ecfg)
    under_scatter = [
        (name, shape, opcode) for name, shape, opcode, rest in _entry_ops(text)
        if "path_scatter" in rest and shape.startswith((plane,) + rows)
    ]
    assert [op for _, _, op in under_scatter] == ["custom-call"] * 2
    assert not [
        name for name, shape, opcode, rest in _entry_ops(text)
        if opcode == "sort" and "path_scatter" in rest
    ]
    rec = (f"u32[{ecfg.rec.n_buckets_padded},"
           f"{','.join(map(str, ecfg.rec.stored_row_shape))}]")
    assert ecfg.rec.stored_row_shape == (8, 128)
    assert len(_placements(text, rec)) == 1
    assert not [
        name for name, shape, opcode, rest in _entry_ops(text)
        if shape.startswith(rec) and opcode == "fusion"
        and "path_scatter" in rest
    ]


def test_the_stored_row_adds_no_pass_over_the_fetched_rows(wide_row_round):
    """The cut after the decrypt is a bitcast (a ``[R,6080]`` row is 48
    tiles already), the pad before the encrypt is the kernel's own
    (test_cipher_kernel_pads_the_mailbox_plaintext_itself), and the
    rows go between ``[R,48,128]`` and ``[R,6144]`` inside the cipher
    kernel (test_cipher_kernel_compiles_on_rows_as_the_plane_stores_
    them): no ``pad``, ``slice``, ``copy`` or relaying fusion of the
    fetched rows' size is in the round. One is a 1.2-1.6 ms pass at
    2^17 recipients, four a round."""
    text, ecfg = wide_row_round
    made = [
        (name, opcode) for name, shape, opcode, _ in _entry_ops(text)
        if shape.startswith(_mb_rows(ecfg))
    ]
    assert made
    assert not [
        m for m in made
        if m[1] in ("pad", "slice", "copy", "transpose")
        or m[0].startswith(("copy", "transpose"))
    ]


# ----------------------------------------------------------------------
# the placement kernel alone (PR 46), at the shapes of 2^21 messages /
# 2^17 recipients. ISSUE 46's first gate.
# ----------------------------------------------------------------------


@pytest.mark.parametrize(
    "n,tiles,rows", [(1 << 16, 48, 20464), (1 << 21, 8, 22512)],
    ids=["mailbox", "records"],
)
def test_placement_kernel_compiles_for_v5e(one_chip, n, tiles, rows):
    """One DMA a row into the donated plane: a Mosaic kernel whose
    output is the plane it was handed, and nothing else the size of the
    plane or of the rows in the program."""
    text = _compile_for(
        one_chip, place_rows, _s(n, tiles, 128), _s(rows),
        _s(rows, tiles, 128), donate=(0,), interpret=False,
    )
    assert "tpu_custom_call" in text
    assert "output_to_operand_aliasing={{}: (2, {})}" in text
    assert " copy(" not in text and " fusion(" not in text


def test_mosaic_refuses_a_one_row_window_of_a_2d_plane(one_chip):
    """Why the plane is stored ``[n, tiles, 128]``: in HBM a 2-D
    ``u32[n, W]`` is ``T(8,128)``-tiled, a bucket's row 48 pieces of
    512 B at a stride of 4 KB, and Mosaic takes a window of a tiled
    dimension only in whole tiles. If this compile ever passes, the
    plane can go back to two dimensions (PERF.md section 6, PR 46)."""
    with pytest.raises(Exception, match=r"aligned to tiling \(8\)"):
        _compile_for(
            one_chip, place_rows, _s(1 << 16, 6144), _s(20464),
            _s(20464, 6144), interpret=False,
        )


def test_the_mesh_round_places_by_the_same_kernel(one_chip):
    """Under ``shard_map`` over the described 2x2 the round is the
    one-chip program on each chip's quarter of the planes: the same
    placement kernel, aliased onto the local shard, three to a round
    (two mailbox passes, one records pass), the ``mine`` predicate the
    only difference (it is folded into the targets before the kernel),
    and no scatter fusion over a value plane's shard."""
    import re

    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from grapevine_tpu.config import GrapevineConfig
    from grapevine_tpu.parallel import mesh as pm

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = Mesh(topo.devices, (pm.TREE_AXIS,))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        ecfg, state, batch = _round_specs(GrapevineConfig(
            max_messages=1 << 16, max_recipients=1 << 15, batch_size=2048,
            tree_density=2, shards=4,
        ))
        sharded = jax.tree.map(
            lambda spec, s: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(mesh, spec)),
            pm.engine_state_specs(), state,
            is_leaf=lambda s: isinstance(s, P))
        whole = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(mesh, P())),
            batch)
        text = pm.make_sharded_step(ecfg, mesh).lower(
            sharded, whole).compile().as_text()
    local = {"mb": f"u32[{ecfg.mb.n_buckets_padded // 4},48,128]",
             "rec": f"u32[{ecfg.rec.n_buckets_padded // 4},8,128]"}
    placed = re.findall(
        r"= (u32\[[\d,]+\])\S* custom-call\([^\n]*"
        r'custom_call_target="tpu_custom_call"[^\n]*'
        r"output_to_operand_aliasing=\{\{\}: \(2, \{\}\)\}[^\n]*"
        r"path_scatter/jit\(place_rows\)", text)
    assert sorted(placed) == sorted([local["mb"]] * 2 + [local["rec"]])
    assert not re.search(
        r"= (?:%s)\S* fusion\([^\n]*path_scatter" % "|".join(
            map(re.escape, local.values())), text)
