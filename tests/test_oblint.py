"""ISSUE 12: the unified obliviousness analyzer + host lock lint.

Four suites:

1. taint propagation units — one tiny traced program per jax primitive
   class (elementwise, gather, scatter, dynamic-slice, select, sort,
   cond, while, scan carry, pjit nesting, callback), pinning both the
   flow (secret reaches the sink) and the non-flow (public indices stay
   clean);
2. the seeded-mutant teeth matrix: every leaky mutant FAILS under the
   production allowlist (tools/check_oblivious.py runs the same set);
3. allowlist round-trip at tier-1 scale: the smoke engine audit is
   violation-free, and the DEFAULT sweep reaches every allowlist entry
   (dead entries fail) — the full cross-product rides -m slow;
4. locklint directed tests against deliberately mis-locked fake
   batchers, plus the real repo passing.
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from grapevine_tpu.analysis.allowlist import ENGINE_ALLOWLIST
from grapevine_tpu.analysis.locklint import lint_repo, lint_sources
from grapevine_tpu.analysis.mutants import mutant_names, run_mutants
from grapevine_tpu.analysis.oblint import AllowEntry, analyze

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

U32 = jnp.uint32


def _sds(*shape):
    return jax.ShapeDtypeStruct(shape, np.uint32)


def _kinds(rep):
    return {v.kind for v in rep.violations}


# ----------------------------------------------------------------------
# 1. taint propagation units, one per primitive class
# ----------------------------------------------------------------------


def test_elementwise_propagates_and_public_stays_clean():
    def fn(s, p):
        mixed = (s * 2 + p).astype(U32) ^ s
        return p[mixed % 4], p[p % 4]  # tainted gather + clean gather

    rep = analyze(fn, {"s": _sds(4), "p": _sds(4)}, secrets=("s",))
    assert len(rep.violations) == 1  # ONLY the secret-indexed gather
    v = rep.violations[0]
    assert v.kind == "gather-index" and "s" in v.labels


def test_gather_by_secret_flagged_with_label():
    def fn(s, table):
        return table[s % 8]

    rep = analyze(fn, {"s": _sds(4), "table": _sds(8)}, secrets=("s",))
    assert _kinds(rep) == {"gather-index"}
    assert rep.violations[0].labels == ("s",)


def test_scatter_family_by_secret_flagged():
    def fn(s, plane):
        a = plane.at[s % 8].set(U32(1))
        b = plane.at[s % 8].add(U32(1))  # scatter-add: same family
        return a, b

    rep = analyze(fn, {"s": _sds(4), "plane": _sds(8)}, secrets=("s",))
    assert _kinds(rep) == {"scatter-index"}
    fam = AllowEntry("scatter", rep.violations[0].site, "test")
    assert all(fam.matches(v) for v in rep.violations)


def test_dynamic_slice_start_by_secret_flagged():
    def fn(s, x):
        return jax.lax.dynamic_slice(x, (s[0].astype(jnp.int32),), (2,))

    rep = analyze(fn, {"s": _sds(2), "x": _sds(8)}, secrets=("s",))
    assert _kinds(rep) == {"dynamic-slice-start"}


def test_select_and_sort_transmit_taint_without_sinking():
    """where/sort on secrets is fine — until the result indexes memory."""
    def fn(s, p, table):
        picked = jnp.where(s > 0, s, p)  # tainted
        perm = jnp.argsort(picked)  # tainted, but sort is not a sink
        return table[perm]  # the gather IS

    rep = analyze(
        fn, {"s": _sds(4), "p": _sds(4), "table": _sds(4)}, secrets=("s",)
    )
    assert _kinds(rep) == {"gather-index"}
    assert "s" in rep.violations[0].labels


def test_cond_predicate_flagged_and_branches_walked():
    def fn(s, table):
        # the predicate leaks AND a branch hides a secret gather
        return jax.lax.cond(
            s[0] > 1,
            lambda: table[s % 4].sum(),
            lambda: jnp.zeros((), U32),
        )

    rep = analyze(fn, {"s": _sds(4), "table": _sds(4)}, secrets=("s",))
    assert {"cond-predicate", "gather-index"} <= _kinds(rep)


def test_while_predicate_flagged_via_carry_fixpoint():
    """The secret enters the predicate only through the carry after one
    body iteration — catches analyzers that skip the fixpoint."""
    def fn(s):
        def body(c):
            i, acc = c
            return i + U32(1), acc | s[0]  # taint enters carry here

        def cond(c):
            i, acc = c
            return (i < U32(3)) | (acc > U32(0))  # tainted via acc

        return jax.lax.while_loop(cond, body, (U32(0), U32(0)))

    rep = analyze(fn, {"s": _sds(2)}, secrets=("s",))
    assert "while-predicate" in _kinds(rep)


def test_scan_carry_fixpoint_and_clean_scan_passes():
    def leaky(s, table):
        def body(c, x):
            # the sink reads the CARRY, which is clean on the first
            # body pass and secret only after one iteration — a
            # single-pass analyzer misses it, the fixpoint must not
            y = table[c % 4]  # scalar index -> dynamic_slice sink
            return c + s[0], y

        return jax.lax.scan(body, U32(0), jnp.arange(3, dtype=U32))

    rep = analyze(
        leaky, {"s": _sds(2), "table": _sds(4)}, secrets=("s",)
    )
    assert "dynamic-slice-start" in _kinds(rep)
    assert "s" in rep.violations[0].labels

    def clean(s, table):
        def body(c, x):
            return c + x, table[x % 4] + s[0]  # public index, secret data

        return jax.lax.scan(body, U32(0), jnp.arange(3, dtype=U32))

    rep2 = analyze(
        clean, {"s": _sds(2), "table": _sds(4)}, secrets=("s",)
    )
    assert rep2.ok, rep2.summary()


def test_pjit_nesting_walked():
    @jax.jit
    def inner(s, table):
        return table[s % 4]

    def fn(s, table):
        return inner(s, table) + 1

    rep = analyze(fn, {"s": _sds(4), "table": _sds(4)}, secrets=("s",))
    assert _kinds(rep) == {"gather-index"}


def test_callback_sink_flagged():
    def fn(s, x):
        jax.debug.print("leaf {v}", v=s[0])
        return x

    rep = analyze(fn, {"s": _sds(2), "x": _sds(2)}, secrets=("s",))
    assert _kinds(rep) == {"callback"}


def test_secret_prefix_matches_pytree_paths():
    """Dotted prefixes select pytree leaves: state.stash is secret,
    state.nonces is not."""
    state = {"stash": _sds(4), "nonces": _sds(4)}

    def fn(state, table):
        return table[state["stash"] % 4], table[state["nonces"] % 4]

    rep = analyze(
        fn, {"state": state, "table": _sds(4)},
        secrets=("state.stash",),
    )
    assert len(rep.violations) == 1
    assert rep.violations[0].labels == ("state.stash",)


def test_allowlist_admits_and_counts_hits():
    def fn(s, table):
        return table[s % 4]

    bare = analyze(fn, {"s": _sds(4), "table": _sds(4)}, secrets=("s",))
    site = bare.violations[0].site
    allowed = analyze(
        fn, {"s": _sds(4), "table": _sds(4)}, secrets=("s",),
        allowlist=(AllowEntry("gather", site, "test entry"),),
    )
    assert allowed.ok
    assert allowed.allowed == {f"gather@{site}": 1}


# ----------------------------------------------------------------------
# 2. mutant teeth matrix (under the PRODUCTION allowlist)
# ----------------------------------------------------------------------


def test_mutant_matrix_all_caught():
    assert len(mutant_names()) >= 6
    results = run_mutants(ENGINE_ALLOWLIST)
    missed = {
        name: (kind, [v.kind for v in rep.violations])
        for name, (rep, kind, hit) in results.items()
        if not hit
    }
    assert not missed, f"mutants NOT caught (analyzer lost teeth): {missed}"


def test_mutants_caught_for_the_right_reason():
    """Each mutant's finding is its seeded class, not incidental noise."""
    for name, (rep, kind, hit) in run_mutants(ENGINE_ALLOWLIST).items():
        kinds = [v.kind for v in rep.violations]
        assert kinds.count(kind) >= 1, (name, kind, kinds)


def test_debug_print_of_a_secret_in_the_engine_round_is_caught():
    """The leaky-debug-print mutant seeded into the REAL round: under
    the installed JAX ``jax.debug.print`` traces to a ``debug_print``
    primitive (it was ``debug_callback`` when the analyzer was
    written), and a printed op type inside ``engine_round_step`` must
    read as a callback sink under the production allowlist — exactly
    one finding more than the clean round's none."""
    import check_oblivious as gate

    from grapevine_tpu.engine import round_step
    from grapevine_tpu.engine.state import init_engine

    ecfg = gate._small_engine(*gate.SMOKE_COMBO)

    def leaky_round(st, ba):
        jax.debug.print("op type {t}", t=ba["req_type"][0])
        return round_step.engine_round_step(ecfg, st, ba)

    rep = analyze(
        leaky_round,
        {"state": jax.eval_shape(lambda: init_engine(ecfg, 0)),
         "batch": gate._batch_spec(ecfg)},
        secrets=round_step.OBLINT_SECRETS,
        allowlist=ENGINE_ALLOWLIST,
        name="engine_round/leaky_debug_print",
    )
    assert [(v.kind, v.prim) for v in rep.violations] == [
        ("callback", "debug_print")
    ], rep.summary()
    assert rep.violations[0].labels == ("batch.req_type",)


# ----------------------------------------------------------------------
# 3. the engine audit (smoke always-on; sweep reachability; full = slow)
# ----------------------------------------------------------------------


def test_check_oblivious_smoke_gate():
    """tools/check_oblivious.py --smoke wired into tier-1 next to the
    telemetry/seal/perf gates: one engine trace, taint-clean, all
    mutants caught, locklint green. Budget: ~1 engine trace, 0 compiles."""
    import check_oblivious as gate

    assert gate.main(["--smoke"]) == 0


def test_engine_round_audit_is_violation_free_and_uses_allowlist():
    import check_oblivious as gate

    rep = gate.audit_engine_round(
        gate._small_engine(*gate.SMOKE_COMBO), ENGINE_ALLOWLIST,
        "tier1_smoke",
    )
    assert rep.ok, rep.summary()
    # the audit is not vacuous: dozens of reviewed sinks were exercised
    assert sum(rep.allowed.values()) > 20
    assert rep.n_eqns > 1000


def test_allowlist_round_trip_default_sweep():
    """Every reviewed allowlist entry is REACHED by the default sweep
    and no combo produces a violation — dead entries rot, so their
    presence alone fails this test."""
    import check_oblivious as gate

    problems, hits = gate.run_audit(gate.DEFAULT_COMBOS)
    assert not problems, problems
    dead = gate.check_allowlist_reachability(hits)
    assert not dead, dead


@pytest.mark.slow
def test_full_matrix_and_mutants_via_cli():
    """The whole gate end to end, the census on every combination."""
    import check_oblivious as gate

    assert gate.main(["--full"]) == 0


# ----------------------------------------------------------------------
# 4. locklint directed tests
# ----------------------------------------------------------------------


_FAKE_OK = '''
import threading

def pack_batch(reqs): return reqs
def validate_request(r): pass

class BatchJournal:
    def append_round(self, b, n): pass

class GrapevineEngine:
    def __init__(self):
        self._lock = threading.Lock()
        self.state = 0
        self.durability = None

    def _assemble_round(self, reqs):
        for r in reqs: validate_request(r)
        return pack_batch(reqs)

    def _journal_round(self, batch):
        if self.durability: self.durability.append_round(batch, 1)

    def _dispatch_round(self, batch):
        self.state = self.state + 1
        return batch

    def handle_queries_async(self, reqs):
        batch = self._assemble_round(reqs)
        with self._lock:
            self._journal_round(batch)
            out = self._dispatch_round(batch)
        return out
'''


def _mutate(src: str, old: str, new: str) -> str:
    assert old in src
    return src.replace(old, new)


def test_locklint_fake_batcher_clean():
    assert lint_sources({"fake.py": _FAKE_OK}, allow=()) == []


def test_locklint_split_holds_flagged():
    bad = _mutate(
        _FAKE_OK,
        "        with self._lock:\n"
        "            self._journal_round(batch)\n"
        "            out = self._dispatch_round(batch)\n",
        "        with self._lock:\n"
        "            self._journal_round(batch)\n"
        "        with self._lock:\n"
        "            out = self._dispatch_round(batch)\n",
    )
    vs = lint_sources({"fake.py": bad}, allow=())
    assert any(v.kind == "same-hold" for v in vs), vs


def test_locklint_stage1_under_lock_flagged():
    bad = _mutate(
        _FAKE_OK,
        "        batch = self._assemble_round(reqs)\n        with self._lock:",
        "        with self._lock:\n            batch = self._assemble_round(reqs)\n"
        "        with self._lock:",
    )
    vs = lint_sources({"fake.py": bad}, allow=())
    assert any(v.kind == "stage1-under-lock" for v in vs), vs


def test_locklint_journal_growing_a_lock_flagged():
    bad = _mutate(
        _FAKE_OK,
        "class BatchJournal:\n    def append_round(self, b, n): pass",
        "class BatchJournal:\n"
        "    def __init__(self):\n"
        "        self._jlock = threading.Lock()\n"
        "    def append_round(self, b, n):\n"
        "        with self._jlock: pass",
    )
    vs = lint_sources({"fake.py": bad}, allow=())
    assert any(v.kind == "journal-lock" for v in vs), vs


def test_locklint_ordering_cycle_flagged():
    cyc = _FAKE_OK + '''
class BatchScheduler:
    def __init__(self, engine: GrapevineEngine):
        self.engine = engine
        self._cv = threading.Condition()

    def submit(self, req):
        with self._cv:
            self.engine.handle_queries_async([req])  # cv -> engine lock
'''
    # close the cycle: the engine, under its lock, calls back into a
    # scheduler method that takes the cv
    cyc = _mutate(
        cyc,
        "    def __init__(self):\n        self._lock = threading.Lock()",
        "    def __init__(self, sched: BatchScheduler):\n"
        "        self.sched = sched\n"
        "        self._lock = threading.Lock()",
    )
    cyc = _mutate(
        cyc,
        "            self._journal_round(batch)\n",
        "            self._journal_round(batch)\n"
        "            self.sched.submit(None)\n",
    )
    # give the binding a target class annotation order-independently:
    # BatchScheduler is annotated above; GrapevineEngine.sched binds it
    vs = lint_sources({"fake.py": cyc}, allow=())
    assert any(v.kind == "lock-cycle" for v in vs), vs


def test_locklint_unguarded_shared_attr_flagged():
    shared = _FAKE_OK + '''
import threading as _t

class BatchScheduler:
    def __init__(self, engine):
        self.engine = engine
        self._cv = threading.Condition()
        self._depth = 0
        self._worker = threading.Thread(target=self._run)

    def _run(self):
        self._depth = self._depth - 1  # worker write, no lock

    def submit(self, req):
        self._depth = self._depth + 1  # caller write, no lock
        return self._depth
'''
    vs = lint_sources({"fake.py": shared}, allow=())
    assert any(
        v.kind == "shared-attr" and "_depth" in v.where for v in vs
    ), vs


def test_locklint_covers_hostpipe_handoff():
    """The multiprocess host pipeline's main-side hand-off (ISSUE 20)
    is in coverage: a HostPipeline whose reader thread and submitters
    race on an unlocked attribute must be flagged like the scheduler's."""
    piped = _FAKE_OK + '''
class HostPipeline:
    def __init__(self):
        self._lock = threading.Lock()
        self._inflight = 0

    def _start(self):
        self._reader = threading.Thread(target=self._read_loop)

    def _read_loop(self):
        self._inflight = self._inflight - 1  # reader write, no lock

    def submit(self, task):
        self._inflight = self._inflight + 1  # caller write, no lock
        return self._inflight
'''
    vs = lint_sources({"fake.py": piped}, allow=())
    assert any(
        v.kind == "shared-attr" and "HostPipeline._inflight" in v.where
        for v in vs
    ), vs


def test_locklint_missing_code_is_loud():
    vs = lint_sources({"fake.py": "x = 1\n"}, allow=())
    assert any(v.kind == "missing-code" for v in vs)


def test_locklint_dead_allow_entry_flagged():
    """A LOCK_ALLOW entry documenting a race that no longer exists must
    fail the lint — the oblint dead-entry rule, host-side."""
    from grapevine_tpu.analysis.locklint import LockAllow

    vs = lint_sources(
        {"fake.py": _FAKE_OK},
        allow=(LockAllow("GrapevineEngine", "ghost",
                         "a race that was refactored away"),),
    )
    assert any(
        v.kind == "dead-allow" and "ghost" in v.where for v in vs
    ), vs


def test_locklint_reads_only_entry_still_fails_unlocked_write():
    from grapevine_tpu.analysis.locklint import LockAllow

    src = _FAKE_OK + '''
class Extra:
    pass
'''
    src = src.replace(
        "    def handle_queries_async(self, reqs):",
        "    def poke(self):\n"
        "        self.state = self.state + 1  # unlocked WRITE\n\n"
        "    def handle_queries_async(self, reqs):",
    )
    entry = LockAllow("GrapevineEngine", "state", "reads tolerated",
                      reads_only=True)
    vs = lint_sources({"fake.py": src}, allow=(entry,))
    assert any(
        v.kind == "shared-attr" and "state" in v.where for v in vs
    ), vs


def test_locklint_real_repo_passes():
    """The PR-10 invariant holds in the live tree — statically."""
    vs = lint_repo(os.path.join(REPO, "grapevine_tpu"))
    assert vs == [], [str(v) for v in vs]


# ----------------------------------------------------------------------
# legacy-checker convergence (satellite: identical verdicts via the core)
# ----------------------------------------------------------------------


def test_legacy_checkers_share_the_analyzer_core():
    import check_posmap_oblivious as posmap_gate
    import check_tree_cache_oblivious as cache_gate

    from grapevine_tpu.analysis import jaxpr_walk

    assert posmap_gate._census is jaxpr_walk.census
    assert cache_gate._census is jaxpr_walk.census
    assert cache_gate._shared_plane_rows is jaxpr_walk.plane_rows


def test_k0_recursive_census_cell():
    """Regression (ISSUE 12 satellite): the k=0 recursive cell the
    pre-unification wiring never ran always-on — the uncached recursive
    round must be index-blind and move every level's rows per plane —
    the levels the batch covers once, the rest per path (ISSUE 26) —
    tree_leaf included, with no cache planes declared. height=5 keeps
    the bucket-axis [n, Z] plane shapes disjoint from the inner posmap
    round's working buffers (the shape-keyed accounting's one
    constraint, see _tree_planes)."""
    import check_tree_cache_oblivious as cache_gate

    out = cache_gate.check_k0_recursive_census(b=4, height=5)
    # B=4 covers levels 0-2 (7 buckets, once); levels 3-5 per path
    assert out["tree_leaf"] == [7 + 4 * 3]
    assert "cache_idx" not in out
