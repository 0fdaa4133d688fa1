"""Every scatter that promises ``unique_indices=True`` is shown distinct
in-bounds indices (ROADMAP Design 5's audit).

The CPU ignores the promise; a TPU lowers such a scatter to the parallel
form, where two writes to one in-bounds target race. The programs that
run rounds and sweeps (the engine round with a flat and with a recursive
position map, the round under a two-shard mesh, the op-major oracle
engine, the expiry sweep) are traced here with ``jax.lax``'s scatter
functions wrapped, test-side only: a wrapped scatter that asserts
``unique_indices`` also hands its index operand to a host callback, which
counts the in-bounds index vectors that occur twice. Nothing is added to
the program. Out-of-bounds targets may repeat: they are the ``mode="drop"``
sentinel of rows that are not written.

Each program runs under batches that are all one recipient, all one
record, all dummies, full of distinct keys, mixed, and within B of
saturation.
"""

import functools
import os
import sys

import jax
import jax._src.lax.slicing as lax_slicing
import numpy as np
import pytest

from test_vphases import NOW, _gen_batch, key, req

from grapevine_tpu.config import GrapevineConfig
from grapevine_tpu.engine.batcher import pack_batch, unpack_responses
from grapevine_tpu.engine.expiry import expiry_sweep
from grapevine_tpu.engine.round_step import engine_round_step
from grapevine_tpu.engine.state import EngineConfig, init_engine
from grapevine_tpu.engine.step import engine_step
from grapevine_tpu.wire import constants as C

PACKAGE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "grapevine_tpu", "")

#: max_messages two batches wide, so that three batches of creates bring
#: the bus within B of full and the admission takes its slow path
GEOM = dict(
    max_messages=16, max_recipients=8, mailbox_cap=16, batch_size=8,
    stash_size=96, expiry_period=100,
)
B = GEOM["batch_size"]

#: (site, index vectors, of them in bounds, of those seen before)
SEEN: list = []


def _call_site():
    """``file.py:function`` of the innermost frame inside the package,
    or None for a scatter that jax makes for itself."""
    f = sys._getframe(2)
    while f is not None:
        name = f.f_code.co_filename
        if name.startswith(PACKAGE):
            return f"{name[len(PACKAGE):]}:{f.f_code.co_name}"
        f = f.f_back
    return None


def _start_limits(operand_shape, updates_shape, dnums):
    """Per index component, the largest start that keeps the update
    window inside the operand."""
    window = {d: 1 for d in range(len(operand_shape))}
    full = [d for d in range(len(operand_shape))
            if d not in dnums.inserted_window_dims
            and d not in dnums.operand_batching_dims]
    for d, u in zip(full, dnums.update_window_dims):
        window[d] = updates_shape[u]
    return [operand_shape[d] - window[d]
            for d in dnums.scatter_dims_to_operand_dims]


def _record(site, limits, idx):
    idx = np.asarray(idx).astype(np.int64).reshape(-1, len(limits))
    inside = idx[((idx >= 0) & (idx <= np.asarray(limits))).all(axis=1)]
    repeated = len(inside) - len(np.unique(inside, axis=0))
    SEEN.append((site, len(idx), len(inside), repeated))


def _checked(scatter):
    @functools.wraps(scatter)
    def wrapped(operand, scatter_indices, updates, dimension_numbers,
                **kw):
        site = _call_site() if kw.get("unique_indices") else None
        if site is not None:
            limits = _start_limits(
                operand.shape, updates.shape, dimension_numbers)
            jax.debug.callback(
                functools.partial(_record, site, limits), scatter_indices)
        return scatter(operand, scatter_indices, updates,
                       dimension_numbers, **kw)

    return wrapped


@pytest.fixture(scope="module", autouse=True)
def scatters_wrapped():
    names = ("scatter", "scatter_add", "scatter_sub", "scatter_mul",
             "scatter_min", "scatter_max")
    saved = {n: getattr(lax_slicing, n) for n in names}
    for n, fn in saved.items():
        setattr(lax_slicing, n, _checked(fn))
    yield
    for n, fn in saved.items():
        setattr(lax_slicing, n, fn)


# -- the programs --------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _program(name):
    """(ecfg, fresh_state(), step(state, batch) -> (state, resp))."""
    knobs = {
        "round-flat": {},
        "round-recursive": {"posmap_impl": "recursive"},
        "round-two-shards": {"shards": 2},
        "round-op-major": {"commit": "op"},
    }[name]
    cfg = GrapevineConfig(**GEOM, **knobs)
    ecfg = EngineConfig.from_config(cfg)
    if cfg.shards > 1:
        from grapevine_tpu.parallel import (
            init_sharded_engine, make_mesh, make_sharded_step,
        )

        mesh = make_mesh(jax.devices()[: cfg.shards])
        step = make_sharded_step(ecfg, mesh)
        return ecfg, lambda: init_sharded_engine(ecfg, mesh, 3), step
    fn = engine_step if cfg.commit == "op" else engine_round_step
    # a lambda of this module: traced here, with the scatters wrapped
    step = jax.jit(lambda state, batch: fn(ecfg, state, batch))
    return ecfg, lambda: init_engine(ecfg, 3), step


@functools.lru_cache(maxsize=None)
def _sweep():
    ecfg = _program("round-flat")[0]
    return jax.jit(lambda state, now: expiry_sweep(
        ecfg, state, now, ecfg.expiry_period))


# -- the batches ---------------------------------------------------------


def _one_recipient(run):
    a, x = key(1), key(2)
    run([req(C.REQUEST_TYPE_CREATE, a, recipient=x, tag=j) for j in range(B)])
    run([req(C.REQUEST_TYPE_DELETE, x) for _ in range(B)])  # zero-id pops
    run([req(C.REQUEST_TYPE_READ, x) for _ in range(B)])


def _one_record(run):
    a, x = key(1), key(2)
    made = run([req(C.REQUEST_TYPE_CREATE, a, recipient=x, tag=7)])
    mid = made[0].record.msg_id
    assert made[0].status_code == C.STATUS_CODE_SUCCESS
    run([req(C.REQUEST_TYPE_READ, x, msg_id=mid) for _ in range(3)]
        + [req(C.REQUEST_TYPE_UPDATE, x, msg_id=mid, recipient=x, tag=j)
           for j in range(3)]
        + [req(C.REQUEST_TYPE_DELETE, x, msg_id=mid, recipient=x)
           for _ in range(2)])


def _all_dummies(run):
    run([])
    run([])


def _full_of_distinct_keys(run):
    made = run([req(C.REQUEST_TYPE_CREATE, key(10 + j), recipient=key(j + 1),
                    tag=j) for j in range(B)])
    run([req(C.REQUEST_TYPE_READ, key(j + 1), msg_id=m.record.msg_id)
         for j, m in enumerate(made)])
    run([req(C.REQUEST_TYPE_DELETE, key(j + 1)) for j in range(B)])


def _mixed(run):
    rng = np.random.default_rng(21)
    idents = [key(i) for i in range(1, 5)]
    live: list = []
    for _ in range(3):
        reqs = _gen_batch(rng, idents, live, int(rng.integers(2, B)))
        for r, d in zip(reqs, run(reqs)):
            if (r.request_type == C.REQUEST_TYPE_CREATE
                    and d.status_code == C.STATUS_CODE_SUCCESS):
                live.append((d.record.msg_id, r.record.recipient))


def _within_b_of_saturation(run):
    a, x = key(1), key(2)
    for k in range(3):  # the second round on: free_top < B
        out = run([req(C.REQUEST_TYPE_CREATE, a, recipient=x, tag=8 * k + j)
                   for j in range(B)])
    assert C.STATUS_CODE_TOO_MANY_MESSAGES in {r.status_code for r in out}
    run([req(C.REQUEST_TYPE_DELETE, x) for _ in range(3)]
        + [req(C.REQUEST_TYPE_CREATE, a, recipient=key(3 + j), tag=j)
           for j in range(5)])


BATCHES = {
    "one-recipient": _one_recipient,
    "one-record": _one_record,
    "all-dummies": _all_dummies,
    "full": _full_of_distinct_keys,
    "mixed": _mixed,
    "near-saturation": _within_b_of_saturation,
}

#: the functions whose promising scatters each program must reach: an
#: audit that saw none of them audited nothing
MUST_REACH = {
    "round-flat": {"oram/round.py:oram_round",
                   "oram/round.py:_assign_evictions",
                   "oram/path_oram.py:_path_scatter",
                   "oram/posmap.py:lookup_remap_round",
                   "engine/vphases.py:apply_batch"},
    "round-recursive": {"oram/round.py:oram_round",
                        "oram/posmap.py:apply_pm"},
    "round-two-shards": {"oram/round.py:oram_round",
                         "oram/path_oram.py:_path_scatter"},
    "round-op-major": {"oram/path_oram.py:_path_scatter"},
    "sweep": {"engine/expiry.py:expiry_sweep"},
}


@pytest.mark.parametrize("batches", list(BATCHES))
@pytest.mark.parametrize("program", list(MUST_REACH))
def test_scatters_that_promise_unique_indices_get_them(program, batches):
    # the sweep is shown what the flat round's batches leave behind
    ecfg, fresh_state, step = _program(
        "round-flat" if program == "sweep" else program)
    state = fresh_state()
    now = [NOW]

    def run(reqs):
        nonlocal state
        state, resp, _ = step(state, pack_batch(reqs, B, now[0]))
        now[0] += 1
        return unpack_responses(resp, len(reqs))

    del SEEN[:]
    BATCHES[batches](run)
    if program == "sweep":
        # what the batches left, swept when the first of it has expired
        jax.effects_barrier()
        del SEEN[:]
        state = _sweep()(state, NOW + ecfg.expiry_period + 1)
    jax.block_until_ready(state)
    jax.effects_barrier()
    assert int(state.rec.overflow) == 0 and int(state.mb.overflow) == 0
    sites = {site for site, *_ in SEEN}
    assert MUST_REACH[program] <= sites, sites
    assert any(n_in for _, _, n_in, _ in SEEN)
    bad = sorted({site for site, _, _, repeated in SEEN if repeated})
    assert not bad, (
        f"{program} under {batches}: in-bounds targets repeat in a scatter "
        f"that asserts unique_indices=True, at {bad}")


def test_the_audit_sees_a_repeated_target_and_forgives_a_dropped_one():
    import jax.numpy as jnp

    def expiry_sweep(x, tgt):  # a name of the package's, for the site
        return x.at[tgt].set(1, mode="drop", unique_indices=True)

    code = expiry_sweep.__code__.replace(
        co_filename=PACKAGE + "engine/expiry.py")
    fn = jax.jit(type(expiry_sweep)(code, globals()))
    for tgt, repeated in (([0, 3, 3, 9, 9], 1), ([0, 3, 2, 9, 9], 0)):
        del SEEN[:]
        jax.block_until_ready(fn(jnp.zeros(8, jnp.uint32), jnp.asarray(tgt)))
        jax.effects_barrier()
        assert SEEN == [("engine/expiry.py:expiry_sweep", 5, 3, repeated)]
