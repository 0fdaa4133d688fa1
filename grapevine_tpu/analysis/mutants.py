"""Seeded mutants: both analyzers' positive controls.

Each mutant is a small traced program with ONE deliberate defect of a
distinct class. The drivers (tools/check_oblivious.py,
tools/check_ranges.py) and the test suites run every mutant through the
SAME analyzer configuration as the production sweep — production
allowlists included — and require every one to FAIL. A mutant that
passes means an analyzer lost its teeth (or an allowlist entry grew
into a blanket permission), and the audit run itself errors out.

Obliviousness classes, per ISSUE 12: position-dependent branch,
key-indexed gather, data-dependent early exit, secret-shaped output,
un-allowlisted scatter, leaky debug print, python-level branch; and a
kernel's DMA target taken from the rows it moves (PR 46).

Overflow classes, per ISSUE 14 (``_RANGE_REGISTRY``, run through
analysis/rangelint.py): u32 leaf-arithmetic wrap, truncating cast,
off-by-one axis bound, unbounded scan counter, int32 byte-size
product. One shared runner (check_oblivious's mutant control) proves
both analyzers alive from a single tier-1 gate.
"""

from __future__ import annotations

from .oblint import analyze
from .rangelint import analyze_ranges

#: every mutant: name -> (builder returning (fn, args, secrets),
#: expected violation kind)
_REGISTRY: dict = {}


def _mutant(name: str, kind: str):
    def deco(builder):
        _REGISTRY[name] = (builder, kind)
        return builder
    return deco


def _sds(*shape, dtype=None):
    import jax
    import numpy as np

    return jax.ShapeDtypeStruct(shape, dtype or np.uint32)


@_mutant("position_branch", "cond-predicate")
def _position_branch():
    """lax.cond on an ORAM position: the executed branch (and its
    device-time signature) reveals where the block lives."""
    import jax.numpy as jnp
    from jax import lax

    def fn(pos, table):
        return lax.cond(
            pos[0] > 7,
            lambda: jnp.sum(table),
            lambda: jnp.zeros((), table.dtype),
        )

    return fn, {"pos": _sds(4), "table": _sds(16)}, ("pos",)


@_mutant("key_indexed_gather", "gather-index")
def _key_indexed_gather():
    """A table read addressed by the recipient key — the classic
    access-pattern leak the whole ORAM exists to prevent."""
    def fn(key, table):
        return table[key % 16]  # vector index -> gather

    return fn, {"key": _sds(8), "table": _sds(16)}, ("key",)


@_mutant("data_dependent_early_exit", "while-predicate")
def _data_dependent_early_exit():
    """A while loop whose trip count depends on the secret: wall-clock
    (and transcript length) becomes a function of the data."""
    import jax.numpy as jnp
    from jax import lax

    def fn(secret):
        def cond(c):
            i, acc = c
            return i < secret[0]

        def body(c):
            i, acc = c
            return i + jnp.uint32(1), acc + i

        return lax.while_loop(
            cond, body, (jnp.uint32(0), jnp.uint32(0))
        )

    return fn, {"secret": _sds(4)}, ("secret",)


@_mutant("secret_shaped_output", "trace-dependence")
def _secret_shaped_output():
    """An output whose SHAPE is the secret (a result list sized by how
    many records matched). Cannot even trace — the analyzer converts
    the concretization abort into the finding."""
    import jax.numpy as jnp

    def fn(secret):
        n = int(secret[0])  # concretizes a traced value
        return jnp.zeros((n,), jnp.uint32)

    return fn, {"secret": _sds(4)}, ("secret",)


@_mutant("unallowlisted_scatter", "scatter-index")
def _unallowlisted_scatter():
    """A scatter targeted by a secret-derived index at a site no review
    ever admitted — the 'new private state without a proof' case the
    ROADMAP items 1-2 will create pressure for."""
    import jax.numpy as jnp

    def fn(secret, plane):
        return plane.at[secret[0] % 16].set(jnp.uint32(1))

    return fn, {"secret": _sds(4), "plane": _sds(16)}, ("secret",)


@_mutant("dma_target_from_contents", "dma-index")
def _dma_target_from_contents():
    """The row-placement kernel (oblivious/pallas_place.py) handed a
    target derived from the rows it places: a DMA address that depends
    on a block's content, the leak ``_path_scatter``'s public targets
    rule out."""
    import jax.numpy as jnp

    from ..oblivious.pallas_place import place_rows

    def fn(rows, plane):
        tgt = (rows[:, 0, 0] % 16).astype(jnp.int32)
        return place_rows(plane, tgt, rows, interpret=True)

    return fn, {"rows": _sds(4, 8, 128), "plane": _sds(16, 8, 128)}, ("rows",)


@_mutant("leaky_debug_print", "callback")
def _leaky_debug_print():
    """jax.debug.print of a secret: the host callback is an access
    pattern too — it reaches the operator's terminal and logs."""
    import jax

    def fn(secret, x):
        jax.debug.print("selected leaf {s}", s=secret[0])
        return x + 1

    return fn, {"secret": _sds(4), "x": _sds(8)}, ("secret",)


@_mutant("adaptive_batch_from_contents", "cond-predicate")
def _adaptive_batch_from_contents():
    """The adaptive-batching failure mode (ISSUE 20): a collection
    window sized from queue *contents* instead of public aggregates.
    The production policy (server/adaptive.py) decides from the queue
    DEPTH, the arrival EWMA, and the SLO burn rates — counts and rates
    a passive /metrics observer already sees. This mutant threads the
    queued ops' payload bits into the window choice: op-mix-dependent
    round cadence, visible on the wire as a recipient-correlated
    dispatch schedule. Pins that a contents branch cannot slip into
    the window decision unflagged."""
    import jax.numpy as jnp
    from jax import lax

    def fn(payloads, wait):
        hot = jnp.sum(payloads & jnp.uint32(1))  # reads op contents
        return lax.cond(
            hot > 4,  # "queue looks pop-heavy: dispatch early"
            lambda: wait // jnp.uint32(2),
            lambda: wait,
        )

    return fn, {"payloads": _sds(16), "wait": _sds(1)}, ("payloads",)


@_mutant("hold_from_contents", "cond-predicate")
def _hold_from_contents():
    """The dispatch rule's failure mode (ISSUE 33): a hold that ends on
    what the queued ops ARE. The production rule (server/scheduler.py
    ``hold``) keeps a short queue open behind a round in flight and
    ends on two integers — the queue's length against the batch size
    and the number of rounds in flight. This mutant lets the held ops'
    payload bits release the round early: the cadence, visible on the
    wire, then says which kind of op was waiting. Pins that a contents
    branch cannot slip into the hold unflagged."""
    import jax.numpy as jnp
    from jax import lax

    def fn(payloads, queued, in_flight):
        urgent = jnp.sum(payloads >> jnp.uint32(31))  # reads op contents
        return lax.cond(
            urgent > 0,  # "a queued op looks urgent: do not hold it"
            lambda: jnp.uint32(0),
            lambda: (in_flight[0] > 0).astype(jnp.uint32)
            * (queued[0] < 16).astype(jnp.uint32),
        )

    return (fn,
            {"payloads": _sds(16), "queued": _sds(1), "in_flight": _sds(1)},
            ("payloads",))


@_mutant("python_level_branch", "trace-dependence")
def _python_level_branch():
    """A host-Python `if` on a traced secret — different Python paths
    trace different programs; jax aborts, the analyzer reports."""
    import jax.numpy as jnp

    def fn(secret):
        if secret[0] > 3:  # TracerBoolConversionError
            return jnp.zeros((2,), jnp.uint32)
        return jnp.ones((2,), jnp.uint32)

    return fn, {"secret": _sds(4)}, ("secret",)


# ----------------------------------------------------------------------
# overflow mutants (ISSUE 14): each one deliberate lane escape of a
# distinct class, analyzed by rangelint under the PRODUCTION range
# allowlist — none of whose mod-2^32 arguments may cover these sites
# ----------------------------------------------------------------------

#: name -> (builder returning (fn, args, bounds), expected finding kind)
_RANGE_REGISTRY: dict = {}


def _range_mutant(name: str, kind: str):
    def deco(builder):
        _RANGE_REGISTRY[name] = (builder, kind)
        return builder
    return deco


@_range_mutant("u32_leaf_arith_wrap", "overflow")
def _u32_leaf_arith_wrap():
    """Heap-bucket-id arithmetic one recursion level past the certified
    geometry: (2^31 - 1) + 4·leaf at 2^30 leaves silently wraps the u32
    lane — the exact class the 2^36 design point walks into."""
    import jax.numpy as jnp

    U32 = jnp.uint32

    def fn(leaf):
        return (U32(1) << U32(31)) - U32(1) + leaf * U32(4)

    return fn, {"leaf": _sds(8)}, {"leaf": (0, (1 << 30) - 1)}


@_range_mutant("truncating_cast", "trunc-cast")
def _truncating_cast():
    """An unbounded u32 value narrowed to the int32 index lane: every
    value >= 2^31 goes negative on the way into whatever it indexes."""
    import jax.numpy as jnp

    def fn(x):
        return x.astype(jnp.int32)

    return fn, {"x": _sds(8)}, {}


@_range_mutant("off_by_one_axis_bound", "oob-index")
def _off_by_one_axis_bound():
    """A gather whose declared index bound equals the axis extent — the
    classic <= vs < slip. XLA clamps the overrun onto the last row, so
    the program 'works' while reading the wrong data."""
    def fn(idx, table):
        return table[idx]

    return fn, {"idx": _sds(4), "table": _sds(16)}, {"idx": (0, 16)}


@_range_mutant("unbounded_scan_counter", "overflow")
def _unbounded_scan_counter():
    """A u32 accumulator gaining up to 2^16 per iteration over a 2^20-
    step scan: fine for any single step, 2^36 by the end of the run —
    only the carry fixpoint's trip-count extrapolation can see it."""
    import jax
    import jax.numpy as jnp

    U32 = jnp.uint32

    def fn(inc):
        def body(c, x):
            return c + inc[0], x

        return jax.lax.scan(body, U32(0), jnp.zeros((1 << 20,), U32))

    return fn, {"inc": _sds(2)}, {"inc": (0, 1 << 16)}


@_range_mutant("int32_byte_size_product", "overflow")
def _int32_byte_size_product():
    """A byte-length product computed in int32: 2^20 rows of a 4 KiB
    bucket row is 2^32 bytes — positive sizes multiply into a negative
    length."""
    import jax.numpy as jnp

    def fn(rows):
        return rows.astype(jnp.int32) * jnp.int32(4096)

    return fn, {"rows": _sds(4)}, {"rows": (0, 1 << 20)}


def mutant_names() -> tuple:
    return tuple(_REGISTRY)


def range_mutant_names() -> tuple:
    return tuple(_RANGE_REGISTRY)


def run_range_mutants(allowlist=()) -> dict:
    """Analyze every overflow mutant under ``allowlist``; returns
    name -> (report, expected_kind, failed_as_expected)."""
    out = {}
    for name, (builder, kind) in _RANGE_REGISTRY.items():
        fn, args, bounds = builder()
        rep = analyze_ranges(fn, args, bounds, allowlist=allowlist,
                             name=f"range_mutant/{name}")
        hit = any(f.kind == kind for f in rep.findings)
        out[name] = (rep, kind, hit)
    return out


def control_failures(results: dict, flavor: str, log=print) -> list:
    """Shared mutant-control reporting for both drivers
    (tools/check_oblivious.py, tools/check_ranges.py): print one status
    line per mutant via ``log`` and return the not-caught failures.
    ``flavor`` labels the mutant class (e.g. "mutant", "range mutant");
    works over both report shapes (oblint ``violations``, rangelint
    ``findings``)."""
    failures = []
    for name, (rep, kind, hit) in results.items():
        status = "FAIL (expected)" if hit else "PASSED — NO TEETH"
        log(f"{flavor} {name}: {status}")
        if not hit:
            got = [
                v.kind for v in getattr(rep, "violations", None)
                or getattr(rep, "findings", [])
            ]
            failures.append(
                f"{flavor} {name!r} was NOT caught (expected a {kind}; "
                f"got {got})"
            )
    return failures


def run_mutants(allowlist=()) -> dict:
    """Analyze every mutant under ``allowlist``; returns
    name -> (report, expected_kind, failed_as_expected)."""
    out = {}
    for name, (builder, kind) in _REGISTRY.items():
        fn, args, secrets = builder()
        rep = analyze(fn, args, secrets, allowlist=allowlist,
                      name=f"mutant/{name}")
        hit = any(v.kind == kind for v in rep.violations)
        out[name] = (rep, kind, hit)
    return out
