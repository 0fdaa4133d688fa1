"""Oblint: jaxpr-level taint-propagation obliviousness analyzer.

Secret inputs (recipient keys, msg ids, ORAM positions, stash/cache
contents, cipher keys, per-op payloads — declared as ``OBLINT_SECRETS``
anchors next to the code where each secret enters, see oram/round.py,
oram/posmap.py, engine/round_step.py, engine/expiry.py) are marked
tainted at trace time; the analyzer walks the closed jaxpr of the traced
round and proves that nothing secret-derived reaches an access-deciding
sink:

- a ``gather`` index operand or any ``scatter*`` index operand,
- a ``dynamic_slice`` / ``dynamic_update_slice`` start index,
- a ``cond`` branch predicate or a ``while`` loop predicate,
- inside a Pallas kernel, the row a DMA leaves from or lands on
  (``dma_start`` / ``dma_wait`` indices) and the index of a ref read or
  write,
- a host callback (``debug_callback`` & friends — a leaky debug print
  is an access pattern too: it reaches the operator's terminal).

Taint propagation is a conservative union over every primitive (a leak
can only be over-reported, never missed), recursing into pjit bodies,
custom-call wrappers, cond branches, and running scan/while bodies to a
carry-taint fixpoint. Secret-dependent *Python* control flow and
secret-shaped outputs cannot survive tracing at all — jax raises a
concretization error, which the analyzer converts into a
``trace-dependence`` violation rather than crashing the audit.

Sites that are oblivious **by construction** (the ORAM's own machinery:
path fetches by one-time uniform leaves, the stash's owner-masked
scatters, the private working-set row map …) are admitted through an
explicit reviewed allowlist (:mod:`.allowlist`) keyed by
``prim@file.py:function``; every entry carries its one-line leak
argument, and the driver (tools/check_oblivious.py) fails the run if an
entry is never reached in any shipped knob combination — dead allowlist
entries rot.

The census-equality check of the legacy tools rides along as
:func:`census_equal`: trace the same program with adversarially
different *concrete* secret values and require an identical primitive
census — the strongest form of "the program does not depend on the
data", and the teeth against secret-shaped outputs.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Iterable

from .jaxpr_walk import _sub_jaxprs, census, site_of

#: sink table: primitive -> (kind, fn(eqn) -> index operand atoms)
_CALLBACK_PRIMS = ("debug_callback", "debug_print", "pure_callback",
                   "io_callback", "host_callback_call", "outside_call")

#: ref primitives of a Pallas kernel body: a read, the writes, a DMA
_REF_PRIMS = ("get", "swap", "addupdate", "dma_start", "dma_wait")

EMPTY: frozenset = frozenset()


def _is_ref(atom) -> bool:
    """Whether a kernel-body atom is a ref (memory) and not a value."""
    return hasattr(getattr(atom, "aval", None), "inner_aval")


@dataclasses.dataclass(frozen=True)
class Violation:
    """One secret-derived value reaching an access-deciding sink."""

    kind: str  # gather-index | scatter-index | dynamic-slice-start |
    #            cond-predicate | while-predicate | callback |
    #            dma-index | ref-index | ref-write |
    #            trace-dependence | program-mismatch
    site: str  # "file.py:function" (jaxpr_walk.site_of key)
    prim: str  # primitive name ("" for trace-level findings)
    labels: tuple  # sorted secret labels that reached the sink
    message: str = ""

    def __str__(self) -> str:
        via = f" via {', '.join(self.labels)}" if self.labels else ""
        msg = f" — {self.message}" if self.message else ""
        return f"{self.kind}: {self.prim or '<trace>'} at {self.site}{via}{msg}"


@dataclasses.dataclass(frozen=True)
class AllowEntry:
    """One reviewed oblivious-by-construction sink site.

    ``prim`` matches exactly or as a family prefix (``"scatter"`` covers
    ``scatter-add`` etc.); ``site`` is the ``file.py:function`` key. The
    ``reason`` is the entry's one-line leak argument — an entry without a
    real argument should not exist."""

    prim: str
    site: str
    reason: str

    @property
    def key(self) -> str:
        return f"{self.prim}@{self.site}"

    def matches(self, v: Violation) -> bool:
        if v.site != self.site:
            return False
        return v.prim == self.prim or v.prim.startswith(self.prim + "-")


@dataclasses.dataclass
class OblintReport:
    """Outcome of one analysis: surviving violations, allowlist hits
    (entry.key -> count), and the traced program's primitive census."""

    name: str
    violations: list
    allowed: dict
    census: dict
    n_eqns: int = 0

    @property
    def ok(self) -> bool:
        return not self.violations

    def summary(self) -> str:
        lines = [
            f"[oblint] {self.name}: {len(self.violations)} violation(s), "
            f"{sum(self.allowed.values())} allowlisted sink(s) at "
            f"{len(self.allowed)} site(s), {self.n_eqns} equations"
        ]
        lines += [f"  VIOLATION {v}" for v in self.violations]
        return "\n".join(lines)


class _Ctx:
    """Mutable walk state: violations dedup + allowlist hit counts."""

    def __init__(self, allowlist: Iterable[AllowEntry]):
        self.allowlist = tuple(allowlist)
        self.violations: dict = {}  # keyed for dedup across fixpoint passes
        self.allowed: dict = {}

    def sink(self, kind: str, eqn, labels: frozenset, message: str = ""):
        if not labels:
            return
        v = Violation(
            kind=kind, site=site_of(eqn), prim=eqn.primitive.name,
            labels=tuple(sorted(labels)), message=message,
        )
        for entry in self.allowlist:
            if entry.matches(v):
                self.allowed[entry.key] = self.allowed.get(entry.key, 0) + 1
                return
        self.violations.setdefault((v.kind, v.site, v.prim, v.labels), v)


def _propagate(closed, in_taints: list, ctx: _Ctx) -> list:
    """Walk one (closed) jaxpr, return per-outvar taints."""
    jaxpr = getattr(closed, "jaxpr", closed)
    env: dict = {}

    def read(atom):
        # Literals (have .val) are trace-time constants: public
        return EMPTY if hasattr(atom, "val") else env.get(atom, EMPTY)

    def write(var, t):
        if t:
            env[var] = t

    for v, t in zip(jaxpr.invars, in_taints):
        write(v, t)
    # consts of a closed jaxpr are trace-time constants: public
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        ins = [read(a) for a in eqn.invars]
        union = frozenset().union(*ins) if ins else EMPTY

        # ---- sinks -----------------------------------------------------
        if name == "gather":
            ctx.sink("gather-index", eqn, ins[1],
                     "gather indexed by a secret-derived value")
        elif name.startswith("scatter"):
            ctx.sink("scatter-index", eqn, ins[1],
                     "scatter targeted by a secret-derived value")
        elif name == "dynamic_slice":
            ctx.sink("dynamic-slice-start", eqn,
                     frozenset().union(*ins[1:]) if ins[1:] else EMPTY,
                     "slice start derived from a secret")
        elif name == "dynamic_update_slice":
            ctx.sink("dynamic-slice-start", eqn,
                     frozenset().union(*ins[2:]) if ins[2:] else EMPTY,
                     "update start derived from a secret")
        elif name in _CALLBACK_PRIMS:
            ctx.sink("callback", eqn, union,
                     "secret-derived value escapes to a host callback")
        elif name in _REF_PRIMS:
            # inside a Pallas kernel: operands are refs and, flattened
            # after them, the indices of their windows
            refs = [t for a, t in zip(eqn.invars, ins) if _is_ref(a)]
            idx = [t for a, t in zip(eqn.invars, ins) if not _is_ref(a)]
            if name in ("swap", "addupdate"):
                idx, stored, held = idx[1:], idx[0], refs[0]
            elif name == "dma_start":
                stored, held = refs[0], refs[1]  # src -> dst
            else:
                stored = held = EMPTY
            ctx.sink("dma-index" if name.startswith("dma") else "ref-index",
                     eqn, frozenset().union(*idx) if idx else EMPTY,
                     "kernel window placed by a secret-derived index")
            # a ref holds the taint it entered the kernel with: a write
            # that would add to it is refused, not modelled
            ctx.sink("ref-write", eqn, stored - held,
                     "kernel stores a secret into a ref the walk holds "
                     "public")

        # ---- taint transfer --------------------------------------------
        if name == "cond":
            ctx.sink("cond-predicate", eqn, ins[0],
                     "branch selected by a secret-derived predicate")
            outs = None
            for br in eqn.params["branches"]:
                bouts = _propagate(br, ins[1:], ctx)
                outs = (
                    bouts if outs is None
                    else [a | b for a, b in zip(outs, bouts)]
                )
            # a secret predicate taints every branch output
            outs = [t | ins[0] for t in (outs or [])]
        elif name == "while":
            ncc = eqn.params["cond_nconsts"]
            nbc = eqn.params["body_nconsts"]
            cond_c, body_c = ins[:ncc], ins[ncc:ncc + nbc]
            carry = list(ins[ncc + nbc:])
            for _ in range(len(carry) + 1):
                nxt = _propagate(eqn.params["body_jaxpr"], body_c + carry, ctx)
                merged = [a | b for a, b in zip(carry, nxt)]
                if merged == carry:
                    break
                carry = merged
            pred = _propagate(eqn.params["cond_jaxpr"], cond_c + carry, ctx)
            ctx.sink(
                "while-predicate", eqn,
                frozenset().union(*pred) if pred else EMPTY,
                "loop trip count depends on a secret",
            )
            outs = carry
        elif name == "scan":
            nc, ncar = eqn.params["num_consts"], eqn.params["num_carry"]
            consts, carry = ins[:nc], list(ins[nc:nc + ncar])
            xs = ins[nc + ncar:]
            ys: list = []
            for _ in range(len(carry) + 1):
                res = _propagate(eqn.params["jaxpr"], consts + carry + xs, ctx)
                nxt, ys = res[:ncar], res[ncar:]
                merged = [a | b for a, b in zip(carry, nxt)]
                if merged == carry:
                    break
                carry = merged
            outs = carry + ys
        elif name == "pallas_call":
            # the kernel's refs, in order: the operands (scalar prefetch
            # first), the outputs, the scratch. An operand's ref holds
            # that operand's taint; an output, a scratch ref, and an
            # operand aliased onto an output (the kernel may read back
            # what it wrote there) hold everything the kernel was handed
            body = eqn.params["jaxpr"]
            aliased = {i for i, _ in eqn.params["input_output_aliases"]}
            held = [union if i in aliased else t for i, t in enumerate(ins)]
            held += [union] * (len(body.invars) - len(ins))
            _propagate(body, held, ctx)
            outs = [union] * len(eqn.outvars)
        else:
            # the SAME sub-jaxpr discovery the census walk uses
            # (tuple/list params included — custom_linear_solve and
            # friends park jaxprs inside namedtuples): a sub-jaxpr the
            # census sees but the taint walk skips would be a silent
            # hole in the "over-reported, never missed" contract
            subs = list(_sub_jaxprs(eqn))
            if subs:
                # pjit / closed_call / custom_jvp / remat wrappers: one
                # body whose invars align positionally when arities match;
                # otherwise broadcast the conservative union
                outs = None
                for sub in subs:
                    n_in = len(getattr(sub, "jaxpr", sub).invars)
                    sub_in = ins if n_in == len(ins) else [union] * n_in
                    souts = _propagate(sub, sub_in, ctx)
                    outs = (
                        souts if outs is None
                        else [a | b for a, b in zip(outs, souts)]
                    )
                if len(outs or []) != len(eqn.outvars):
                    outs = [union] * len(eqn.outvars)
            else:
                outs = [union] * len(eqn.outvars)

        for var, t in zip(eqn.outvars, outs):
            write(var, t)
    return [read(v) for v in jaxpr.outvars]


def _path_str(path) -> str:
    """'state.rec.stash_idx' / 'batch.auth' style labels from jax key
    paths (GetAttrKey / DictKey / SequenceKey / FlattenedIndexKey)."""
    parts = []
    for k in path:
        if hasattr(k, "name"):
            parts.append(str(k.name))
        elif hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:  # pragma: no cover - future key types
            parts.append(str(k))
    return ".".join(parts)


def _secret_match(label: str, prefixes) -> bool:
    return any(
        label == p or label.startswith(p + ".") for p in prefixes
    )


def analyze(
    fn: Callable,
    args: dict,
    secrets: Iterable[str],
    allowlist: Iterable[AllowEntry] = (),
    name: str = "program",
) -> OblintReport:
    """Trace ``fn(*args.values())`` and taint-check the closed jaxpr.

    ``args`` maps argument name -> example value (arrays or
    ShapeDtypeStructs; pytrees welcome). ``secrets`` are dotted label
    prefixes over those names (``"batch.auth"``, ``"state.rec.posmap"``)
    — every flattened leaf under a prefix is tainted with its own full
    label, so violations name the exact secret that reached the sink.

    Secret-dependent Python control flow or shapes abort tracing; that
    abort IS the finding (``trace-dependence``)."""
    import jax
    from jax import tree_util as jtu
    from .jaxpr_walk import walk_eqns

    secrets = tuple(secrets)
    ctx = _Ctx(allowlist)
    values = list(args.values())
    try:
        closed = jax.make_jaxpr(fn)(*values)
    except Exception as exc:  # concretization = data-dependent trace
        if type(exc).__name__ in (
            "TracerBoolConversionError", "ConcretizationTypeError",
            "TracerIntegerConversionError", "TracerArrayConversionError",
        ):
            v = Violation(
                kind="trace-dependence", site=name, prim="",
                labels=(), message=(
                    "tracing aborted on a data-dependent Python branch "
                    f"or shape: {type(exc).__name__}"
                ),
            )
            return OblintReport(name, [v], {}, {})
        raise

    # map flattened invars -> secret labels, argument by argument
    in_taints: list = []
    for argname, val in args.items():
        leaves_with_path = jtu.tree_flatten_with_path(val)[0]
        for path, _leaf in leaves_with_path:
            sub = _path_str(path)
            label = f"{argname}.{sub}" if sub else argname
            in_taints.append(
                frozenset([label]) if _secret_match(label, secrets) else EMPTY
            )
    if len(in_taints) != len(closed.jaxpr.invars):
        raise ValueError(
            f"oblint: {len(in_taints)} flattened args vs "
            f"{len(closed.jaxpr.invars)} jaxpr invars — static/implicit "
            "arguments must be closed over, not passed"
        )
    _propagate(closed, in_taints, ctx)
    return OblintReport(
        name=name,
        violations=sorted(
            ctx.violations.values(), key=lambda v: (v.site, v.kind)
        ),
        allowed=dict(ctx.allowed),
        census=dict(census(closed)),
        n_eqns=sum(1 for _ in walk_eqns(closed)),
    )


def census_equal(
    variants: dict, name: str = "program"
) -> list:
    """Trace each ``variants[vname] = (fn, args)`` (secrets baked into
    ``fn`` as concrete constants; public state passed via ``args``) and
    require identical primitive censuses.

    Constants are the strongest form of the check — a Python-level
    branch on the secret, a shortcut for special values, or a
    secret-shaped output traces to a *different program*, which
    taint analysis over one abstract trace can never see. Returns
    ``program-mismatch`` violations (empty = pass)."""
    import jax

    censuses = {
        vname: census(jax.make_jaxpr(fn)(*args))
        for vname, (fn, args) in variants.items()
    }
    base_name, base = next(iter(censuses.items()))
    out = []
    for vname, c in censuses.items():
        if c != base:
            diff = (c - base) + (base - c)
            out.append(Violation(
                kind="program-mismatch", site=name, prim="",
                labels=(vname, base_name),
                message=(
                    f"secret instantiation {vname!r} traces a DIFFERENT "
                    f"program than {base_name!r}: {dict(diff)} — the "
                    "compiled round depends on the secret values"
                ),
            ))
    return out
