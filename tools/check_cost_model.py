"""Cost-model gate: the two-derivation ledger identity, its mutant
teeth, and the model-graded knob decisions.

Three checks, the PR-12/14 analyzer discipline applied to the cost
observatory (grapevine_tpu/analysis/costmodel.py, obs/costmon.py):

1. **Ledger ↔ census identity** (``--smoke``, the tier-1 slice): the
   analytic row model — a pure function of geometry × knobs — must
   agree **bit-exactly per operand shape class** with the traced
   census accounting (the shared ``jaxpr_walk`` reduction) across the
   shipped knob matrix: cache-k × posmap for ``oram_round``, the
   composed engine round, and the expiry sweep's chunked scan.
   Trace-only — zero engine compiles.
2. **Mutant teeth**: every seeded undercount mutant (a dropped plane, a
   halved fetch, a forgotten second nonce gather, a missed mailbox
   double-round, …) must trip ``CostModelMismatch``, reported through
   the shared ``mutants.control_failures`` runner — a checker that
   cannot catch a planted defect is vacuous.
3. **Trajectory grading** (``--grade``): replay every banked
   BENCH_trajectory.jsonl A/B line of a program that still exists
   (sort_ab / tree_cache_ab / pipeline_ab, machinery and sweep
   scopes) and report the
   modeled winner next to the measured winner (a pick within
   ``MEASURED_TIE`` of the measured best agrees; an A/B marked
   ``superseded`` in the trajectory is skipped). Agreement is REPORTED
   per config — a disagreement is a finding about the model (or a
   machine regime the bytes model does not price), printed loudly, not
   a gate failure; missing coverage of a banked A/B kind IS a failure.

Standalone: ``python tools/check_cost_model.py [--smoke] [--grade]
[--trajectory PATH] [--skip-mutants] [-v]`` (no flags = smoke + grade).
Tier-1 wiring: tests/test_cost_model.py runs the smoke slice in-process.
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from grapevine_tpu.analysis import costmodel as cm  # noqa: E402
from grapevine_tpu.analysis.mutants import control_failures  # noqa: E402

TRAJECTORY = os.path.join(REPO, "BENCH_trajectory.jsonl")


# -- check 1: the two-derivation identity over the shipped matrix -------


def run_identity_matrix(verbose: bool = False) -> list:
    """Cross-validate analytic vs traced rows across the shipped
    trace-only knob matrix. Returns problem strings (empty = pass)."""
    problems = []

    def _run(label, fn, *a, **kw):
        try:
            fn(*a, **kw)
            if verbose:
                print(f"[check_cost_model]   ok {label}")
        except cm.CostModelMismatch as m:
            problems.append(f"{label}: {m}")

    for name, cfg, b in cm.audit_oram_configs():
        _run(f"round/{name}", cm.cross_validate_round, cfg, b)
    for name, ecfg in cm.audit_engine_configs():
        _run(f"{name}/round", cm.cross_validate_engine_round, ecfg)
        _run(f"{name}/sweep", cm.cross_validate_sweep, ecfg)
        _run(f"{name}/sweep/kernel", cm.cross_validate_sweep, ecfg,
             kernel=True)
    return problems


# -- check 2: mutant teeth ---------------------------------------------


def run_cost_mutant_controls(log=print) -> list:
    return control_failures(cm.run_cost_mutants(), "cost-model mutant",
                            log=log)


# -- check 3: grade the model against the banked trajectory ------------


def _measured_winner(arms: dict, key: str, lower_is_better=True):
    """Winner among arm sub-dicts carrying metric ``key``."""
    scored = {a: d[key] for a, d in arms.items()
              if isinstance(d, dict) and key in d}
    if not scored:
        return None
    pick = min if lower_is_better else max
    return pick(scored, key=scored.get)


#: how far apart two arms must measure before the A/B names a winner.
#: The banked A/Bs are a min of 7 on a shared sandbox: PR 26 ran
#: tree_cache_ab twice in a row on an idle machine and the same arm of
#: the same config came back up to 16.7 % apart, the two runs naming
#: different winners in five of six configs. A model pick that measures
#: within this of the measured best is not contradicted by the line.
MEASURED_TIE = 0.17


def _grade_entry(results, kind, config_id, modeled, measured, basis="",
                 arms=None, key=None):
    """One graded row. With the line's timed ``arms`` the model agrees
    when its pick measures within :data:`MEASURED_TIE` of the measured
    winner (``lead``: how far behind it is); without them (ratios,
    throughputs) only when it names the same arm."""
    lead = None
    if arms and measured and key and modeled in arms:
        lead = arms[modeled][key] / arms[measured][key] - 1.0
    agree = None
    if measured:
        agree = modeled == measured or (
            lead is not None and lead <= MEASURED_TIE)
    results.append({
        "kind": kind, "config": config_id, "modeled": modeled,
        "measured": measured, "agree": agree, "lead": lead,
        "basis": basis,
    })


def _live(configs: dict, name: str):
    """The banked A/B ``name`` of one trajectory line, or None where the
    line has none or marks it ``superseded`` (its arms ran a program
    that no longer exists; a later line re-measured it)."""
    ab = configs.get(name)
    return None if ab is None or ab.get("superseded") else ab


def _parse_cap_b(group_name: str):
    """'round_cap65536_b256' -> (65536, 256)."""
    cap = int(group_name.split("cap")[1].split("_")[0])
    b = int(group_name.split("_b")[1])
    return cap, b


def grade_trajectory(path: str = TRAJECTORY) -> tuple:
    """Grade the model against every banked A/B line.

    Returns ``(results, problems)``: one result row per banked config
    (modeled vs measured winner), problems for parse/coverage gaps."""
    results: list = []
    problems: list = []
    kinds_seen = set()
    if not os.path.exists(path):
        return results, [f"trajectory file missing: {path}"]
    with open(path) as fh:
        lines = [json.loads(ln) for ln in fh if ln.strip()]

    for line in lines:
        pr = line.get("pr", "?")
        configs = line.get("configs", {})

        ab = _live(configs, "tree_cache_ab")
        if ab:
            kinds_seen.add("tree_cache")
            for gname, arms in ab.get("machinery", {}).items():
                cap, b = _parse_cap_b(gname)
                ks = sorted(int(a[1:]) for a in arms if a[1:].isdigit())
                v = cm.ab_verdict("tree_cache", scope="machinery",
                                  cap_n=cap, batch=b, arms=ks)
                measured = _measured_winner(arms, "round_ms")
                _grade_entry(results, "tree_cache",
                             f"{pr}/machinery/{gname}",
                             v["winner"], measured, v["basis"],
                             arms, "round_ms")
            for bstr, arms in ab.get("sweep", {}).items():
                numeric = {a: d for a, d in arms.items()
                           if a[1:].isdigit()}
                ks = sorted(int(a[1:]) for a in numeric)
                v = cm.ab_verdict("tree_cache", scope="sweep",
                                  batch=int(bstr), arms=ks)
                measured = _measured_winner(numeric, "round_ms")
                _grade_entry(results, "tree_cache",
                             f"{pr}/sweep/b{bstr}",
                             v["winner"], measured, v["basis"],
                             numeric, "round_ms")

        if "pipeline_ab" in configs:
            kinds_seen.add("pipeline")
            ab = configs["pipeline_ab"]
            v = cm.ab_verdict("pipeline")
            measured = _measured_winner(
                {a: ab[a] for a in ("depth1", "depth2") if a in ab},
                "ops_per_sec", lower_is_better=False)
            _grade_entry(results, "pipeline", f"{pr}/pipeline_ab",
                         v["winner"], measured, v["basis"])

    for kind in ("tree_cache", "pipeline"):
        if kind not in kinds_seen:
            problems.append(
                f"banked trajectory has no {kind}_ab line to grade — "
                "every banked A/B config must get a modeled verdict"
            )
    return results, problems


def print_grade_report(results) -> tuple:
    agree = sum(1 for r in results if r["agree"])
    total = sum(1 for r in results if r["agree"] is not None)
    for r in results:
        mark = ("AGREE" if r["agree"]
                else "DISAGREE" if r["agree"] is not None else "n/a")
        behind = ("" if not r.get("lead") else
                  f" (model's pick {r['lead'] * 100:.1f} % behind)")
        print(f"[check_cost_model]   {r['kind']:11s} "
              f"{r['config']:42s} model={r['modeled']:6s} "
              f"measured={str(r['measured']):6s} {mark}{behind}")
    print(f"[check_cost_model] model-vs-measured winner agreement: "
          f"{agree}/{total} banked configs")
    return agree, total


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="identity matrix + mutants only (tier-1)")
    ap.add_argument("--grade", action="store_true",
                    help="grade the model against the banked "
                         "trajectory only")
    ap.add_argument("--trajectory", default=TRAJECTORY)
    ap.add_argument("--skip-mutants", action="store_true")
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args(argv)
    do_smoke = args.smoke or not args.grade
    do_grade = args.grade or not args.smoke

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    problems: list = []

    if do_smoke:
        print("[check_cost_model] cross-validating the ledger against "
              "the traced census (shipped knob matrix, trace-only)")
        problems.extend(run_identity_matrix(verbose=args.verbose))
        if not args.skip_mutants:
            problems.extend(run_cost_mutant_controls())

    if do_grade:
        print("[check_cost_model] grading modeled winners against the "
              "banked trajectory")
        results, gp = grade_trajectory(args.trajectory)
        problems.extend(gp)
        print_grade_report(results)

    if problems:
        print(f"[check_cost_model] FAIL: {len(problems)} problem(s)")
        for p in problems:
            print(f"  - {p}")
        return 1
    scope = ("smoke" if do_smoke and not do_grade
             else "grade" if do_grade and not do_smoke else "full")
    print(f"[check_cost_model] PASS ({scope}): ledger == census "
          "bit-exactly per shape class; all undercount mutants caught"
          if do_smoke else
          f"[check_cost_model] PASS ({scope})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
