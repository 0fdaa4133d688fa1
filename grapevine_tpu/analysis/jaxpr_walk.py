"""Shared jaxpr-walking core for every obliviousness audit in the repo.

Before ISSUE 12 the equation walk, the primitive census, and the
HBM-plane row accounting each lived as private copies inside
tools/check_posmap_oblivious.py and tools/check_tree_cache_oblivious.py
(the PR-3/5/7/8 audit lineage). They are one implementation here so the
legacy gates and the taint analyzer (:mod:`.oblint`) see the identical
equation stream — a sub-jaxpr a census misses is a sub-jaxpr the taint
walk misses, and that class of drift is exactly what a unified analyzer
exists to kill.
"""

from __future__ import annotations

import contextlib
from collections import Counter

#: primitives that move data between HBM arrays — the access schedule
#: the transcript argument is about (superset of both legacy tools')
ACCESS_PRIMS = ("gather", "scatter", "scatter-add", "scatter-mul",
                "scatter-min", "scatter-max", "dynamic_slice",
                "dynamic_update_slice")
#: data-dependent control flow: forbidden anywhere in a traced round
CONTROL_PRIMS = ("cond", "while")


@contextlib.contextmanager
def as_a_tpu_traces():
    """Trace-only: ``config.on_tpu()`` answers True inside, so an audit
    on the CPU walks the program a TPU runs, its Pallas kernels
    un-interpreted (traceable anywhere, lowerable only there). The
    backend is the one thing the program asks about its host; there is
    no option for it."""
    from unittest import mock

    import jax

    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        yield


@contextlib.contextmanager
def cipher_form(ecfg, kernel: bool):
    """Trace-only: the engine config an audit traces a program under.
    ``kernel`` false: ``ecfg`` as it is (on the CPU the jnp keystream).
    True: both trees' at-rest cipher resolved as on a TPU
    (``cipher_impl`` "pallas"), inside :func:`as_a_tpu_traces`, so the
    program holds the kernel of oblivious/pallas_cipher.py
    un-interpreted and the audit walks its body."""
    if not kernel:
        yield ecfg
        return
    import dataclasses

    with as_a_tpu_traces():
        yield dataclasses.replace(
            ecfg,
            rec=dataclasses.replace(ecfg.rec, cipher_impl="pallas"),
            mb=dataclasses.replace(ecfg.mb, cipher_impl="pallas"),
        )


def _sub_jaxprs(eqn):
    """Every jaxpr-valued param of ``eqn`` (pjit bodies, scan/while/cond
    branches, custom-call wrappers), in a stable order."""
    for v in eqn.params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for x in vs:
            if hasattr(x, "eqns") or hasattr(x, "jaxpr"):
                yield x


def walk_eqns(jaxpr, into_kernels: bool = True):
    """Yield every equation, recursing into every sub-jaxpr; not into a
    Pallas kernel's body where ``into_kernels`` is false (its operands
    are blocks in VMEM, which a count of HBM planes must not meet)."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in inner.eqns:
        yield eqn
        if not into_kernels and eqn.primitive.name == "pallas_call":
            continue
        for sub in _sub_jaxprs(eqn):
            yield from walk_eqns(sub, into_kernels)


def census(jaxpr) -> Counter:
    """Primitive-name counts over a (closed) jaxpr, recursively."""
    return Counter(eqn.primitive.name for eqn in walk_eqns(jaxpr))


def site_of(eqn, pkg: str = "grapevine_tpu") -> str:
    """Stable source-site key for an equation: ``file.py:function`` of
    the innermost user frame (preferring frames inside ``pkg``).

    The allowlist (:mod:`.allowlist`) is keyed on these, so the key must
    survive line churn: function granularity, no line numbers. The
    function is its bare name — the traceback reports qualified names
    (``phase_a_batch.<locals>.apply_batch``, ``_DenseGroups.select_by_rank``)
    and this is the one place that reduces them to the last component.
    Returns ``"<unknown>"`` when the trace carries no usable frames
    (e.g. a jaxpr rebuilt without source info)."""
    tb = getattr(eqn.source_info, "traceback", None)
    frames = list(tb.frames) if tb is not None else []
    best = None
    for fr in frames:
        fn = fr.file_name.replace("\\", "/")
        if fn.endswith("analysis/oblint.py") or fn.endswith("analysis/rangelint.py"):
            continue  # an analyzer's own make_jaxpr frame, never a site
        if f"/{pkg}/" in fn or fn.startswith(f"{pkg}/"):
            tail = fn.split(f"{pkg}/")[-1]
            return f"{tail}:{fr.function_name.rsplit('.', 1)[-1]}"
        if best is None and "site-packages" not in fn and "/jax/" not in fn \
                and not fn.endswith("/jax.py"):
            best = (f"{fn.rsplit('/', 1)[-1]}:"
                    f"{fr.function_name.rsplit('.', 1)[-1]}")
    return best or "<unknown>"


def _placed_rows(eqn):
    """``(plane shape, rows shape)`` of a row-placement kernel
    (oblivious/pallas_place.py): a ``pallas_call`` that DMAs, whose one
    aliased operand is the plane it writes into and whose operand of the
    same row shape is the rows it places, one copy each. None for any
    other kernel."""
    aliases = eqn.params["input_output_aliases"]
    if len(aliases) != 1 or not any(
        e.primitive.name == "dma_start"
        for e in walk_eqns(eqn.params["jaxpr"])
    ):
        return None
    plane = tuple(eqn.invars[aliases[0][0]].aval.shape)
    rows = [
        tuple(v.aval.shape) for i, v in enumerate(eqn.invars)
        if i != aliases[0][0] and len(v.aval.shape) == len(plane) > 1
        and tuple(v.aval.shape[1:]) == plane[1:]
    ]
    return (plane, rows[0]) if len(rows) == 1 else None


def plane_rows(jaxpr, planes: dict) -> dict:
    """Rows moved per named array plane by every gather/scatter in the
    traced program, and by the row-placement kernel that stands in for
    a wide plane's scatter on a TPU (counted as the scatter it replaces:
    rows written, one DMA each).

    ``planes`` maps name -> ``(shape, divisor)``: an operand whose aval
    shape equals ``shape`` is attributed to that plane; the moved leading
    dim is divided by ``divisor`` (flat slot planes report slots/Z). A
    gather's row count is its output leading dim; a scatter's is its
    updates leading dim — exactly the tree-cache tool's accounting,
    generalized so any audit can declare its own planes."""
    out: dict[str, list] = {k: [] for k in planes}
    for eqn in walk_eqns(jaxpr):
        name = eqn.primitive.name
        if name == "pallas_call":
            placed = _placed_rows(eqn)
            if placed is None:
                continue
            op_shape, moved = placed
        elif name.startswith("scatter") or name == "gather":
            op_shape = tuple(eqn.invars[0].aval.shape)
            moved = (
                eqn.outvars[0].aval.shape
                if name == "gather"
                else eqn.invars[2].aval.shape
            )
        else:
            continue
        for pname, (pshape, div) in planes.items():
            if op_shape == tuple(pshape):
                rows = (moved[0] if moved else 0) // div
                out[pname].append((name, rows))
    return out
