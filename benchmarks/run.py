"""Benchmark suite driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (plus a header comment per
section).  ``--quick`` shrinks iteration counts for CI.  ``--json PATH``
additionally writes the rows as structured JSON so perf trajectories can
be committed (e.g. ``BENCH_2026-07-30.json``) and diffed across PRs.
``--compare OLD.json`` diffs the fresh us_per_call numbers against such
a committed baseline and exits non-zero on >25% regressions (tune with
``--regression-threshold``) so CI can gate on perf.
``--perf-gate`` (opt-in, needs ``--compare``) gates the *pallas/jnp
ratio*: every ``*_pallas_*`` row's ratio to its ``*_jnp_*``/``*_ref_*``
counterpart is compared against the same ratio in the committed
baseline, and the run fails when it grew by more than
``--regression-threshold``.  Ratios-of-ratios cancel host speed, so the
gate holds the fused-dispatch contract even across machines.
``--impl`` selects the protocol backend timed by the kernels suite.
"""
from __future__ import annotations

import argparse
import json
import sys


def _row_to_record(suite: str, row: str) -> dict:
    import math
    name, us, derived = row.split(",", 2)
    try:
        us_val: float | None = float(us)
    except ValueError:
        us_val = None
    if us_val is not None and not math.isfinite(us_val):
        us_val = None        # keep the JSON artifact strictly parseable
    return {"suite": suite, "name": name, "us_per_call": us_val,
            "derived": derived}


def main() -> None:
    from repro.launch.xla_env import configure_compile_cache
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated subset: topologies,scaling,"
                         "straggler,packet_loss,heterogeneity,kernels,"
                         "showdown,sweep,serve")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--impl", default="",
                    help="protocol backend for the kernels-suite round "
                         "benchmark (default: both; see "
                         "repro.core.protocol.IMPLS)")
    ap.add_argument("--json", default="", metavar="PATH",
                    help="also write results as JSON (commit as "
                         "BENCH_*.json for perf trajectories)")
    ap.add_argument("--compare", default="", metavar="OLD.json",
                    help="diff us_per_call against a committed baseline "
                         "JSON and exit non-zero on regressions beyond "
                         "--regression-threshold")
    ap.add_argument("--regression-threshold", type=float, default=0.25,
                    help="fractional us_per_call increase treated as a "
                         "regression in --compare mode (default 0.25)")
    ap.add_argument("--perf-gate", action="store_true",
                    help="with --compare: fail when a *_pallas_* row's "
                         "ratio to its jnp/ref counterpart grew beyond "
                         "--regression-threshold vs the baseline's ratio "
                         "(host-speed invariant; opt-in)")
    ap.add_argument("--structural", action="store_true",
                    help="with --compare: gate only on errored and "
                         "missing rows, never on timing regressions "
                         "(for CI runners whose timings are too noisy "
                         "for the threshold)")
    ap.add_argument("--lint", action="store_true",
                    help="skip the benchmark suites and run the "
                         "repro.analysis plan-invariant linter + jaxpr "
                         "auditor over the full scenario x topology "
                         "matrix; JSON report to --json (or stdout), "
                         "exit 1 on any diagnostic")
    args = ap.parse_args()

    if args.lint:
        from repro.analysis.runner import run_all

        report = run_all(quick=args.quick,
                         progress=lambda m: print(f"[lint] {m}",
                                                  file=sys.stderr))
        doc = json.dumps(report, indent=2)
        if args.json:
            with open(args.json, "w") as fh:
                fh.write(doc + "\n")
        else:
            print(doc)
        n_diag = report["summary"]["diagnostics"]
        print(f"[lint] {n_diag} diagnostic(s)", file=sys.stderr)
        raise SystemExit(1 if n_diag else 0)

    from repro.core.protocol import IMPLS

    from . import (bench_heterogeneity, bench_kernels, bench_packet_loss,
                   bench_scaling, bench_serve, bench_showdown,
                   bench_straggler, bench_sweep, bench_topologies)

    if args.impl and args.impl not in IMPLS:
        ap.error(f"--impl must be one of {IMPLS}, got {args.impl!r}")
    if args.structural and not args.compare:
        ap.error("--structural only makes sense with --compare")
    if args.perf_gate and not args.compare:
        ap.error("--perf-gate needs --compare (the baseline supplies "
                 "the reference pallas/jnp ratios)")

    suites = {
        "topologies": lambda: bench_topologies.run(
            K=4000 if args.quick else 12_000),
        "scaling": lambda: bench_scaling.run(quick=args.quick),
        "straggler": lambda: bench_straggler.run(
            rounds=400 if args.quick else 1200),
        "packet_loss": lambda: bench_packet_loss.run(
            K=5000 if args.quick else 14_000),
        "heterogeneity": lambda: bench_heterogeneity.run(
            K=4000 if args.quick else 12_000),
        "kernels": lambda: bench_kernels.run(impl=args.impl or None,
                                             quick=args.quick),
        "showdown": lambda: bench_showdown.run(
            rounds=150 if args.quick else 1000)
        + bench_showdown.run_dynamic(rounds=150 if args.quick else 400)
        + bench_showdown.run_lm(rounds=40 if args.quick else 120),
        "sweep": lambda: bench_sweep.run(
            K=1200 if args.quick else 3000),
        "serve": lambda: bench_serve.run(quick=args.quick),
    }
    only = [s for s in args.only.split(",") if s]
    meta = {"quick": bool(args.quick), "impl": args.impl or "both",
            "only": only}
    print("name,us_per_call,derived")
    records: list[dict] = []
    failed = False
    for name, fn in suites.items():
        if only and name not in only:
            continue
        print(f"# --- {name} ---", file=sys.stderr)
        try:
            for row in fn():
                print(row, flush=True)
                records.append(_row_to_record(name, row))
        except Exception as e:  # noqa: BLE001
            failed = True
            row = f"{name},nan,ERROR:{type(e).__name__}:{e}"
            print(row)
            records.append(_row_to_record(name, row))
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"meta": meta, "rows": records}, f, indent=2)
            f.write("\n")
        print(f"# wrote {len(records)} rows to {args.json}", file=sys.stderr)
    if args.compare:
        problems = _compare(records, args.compare,
                            args.regression_threshold, run_meta=meta,
                            structural=args.structural)
        if problems:
            raise SystemExit(2)
    if args.perf_gate:
        if _perf_gate(records, args.compare, args.regression_threshold):
            raise SystemExit(3)
    if failed:
        raise SystemExit(1)


def _pallas_ratios(rows: list[dict]) -> dict:
    """Map each timed ``*_pallas_*`` row to its pallas/counterpart ratio
    (counterpart = the same-named ``*_jnp_*`` or ``*_ref_*`` row)."""
    by = {(r["suite"], r["name"]): r["us_per_call"] for r in rows}
    out = {}
    for (suite, name), us in by.items():
        if not us or "_pallas_" not in name:
            continue
        for alt in ("_jnp_", "_ref_"):
            base = by.get((suite, name.replace("_pallas_", alt)))
            if base:
                out[(suite, name)] = us / base
                break
    return out


def _perf_gate(records: list[dict], baseline_path: str,
               threshold: float) -> list[str]:
    """Opt-in (``--perf-gate``) pallas/jnp ratio gate.

    For every timed row whose name contains ``_pallas_`` (the fused
    dispatch path: ``protocol/round_pallas_*``, ``kernel/*_pallas_*``),
    compute its ratio to the same-named ``_jnp_``/``_ref_`` row from the
    SAME run, then compare with the identical ratio in the committed
    baseline JSON; fail when the ratio grew by more than ``threshold``.
    A ratio-of-ratios cancels absolute host speed, so the gate is valid
    on runners where raw-timing thresholds are meaningless.  Rows with
    no counterpart or no baseline ratio are reported, never gated."""
    with open(baseline_path) as f:
        base_ratios = _pallas_ratios(json.load(f)["rows"])
    now_ratios = _pallas_ratios(records)
    problems: list[str] = []
    print(f"# --- perf gate (pallas/jnp ratio drift <= +{threshold:.0%} "
          f"vs baseline) ---", file=sys.stderr)
    for (suite, name), ratio in sorted(now_ratios.items()):
        base = base_ratios.get((suite, name))
        if base is None:
            print(f"# {suite}/{name}: ratio {ratio:.2f}x (no baseline "
                  f"ratio — not gated)", file=sys.stderr)
            continue
        bad = ratio > base * (1 + threshold)
        print(f"# {suite}/{name}: ratio {ratio:.2f}x vs baseline "
              f"{base:.2f}x{' PERF-GATE FAIL' if bad else ''}",
              file=sys.stderr)
        if bad:
            problems.append(name)
    if problems:
        print(f"# perf gate FAILS: {len(problems)} pallas ratio(s) "
              f"regressed beyond +{threshold:.0%}", file=sys.stderr)
    else:
        print("# perf gate OK", file=sys.stderr)
    return problems


# Row-name prefixes every run of a suite must produce: the dynamic-graph
# robustness families (epochized root failover incl. the frozen-stall
# control row, and churn/regional failures), the mesh-mapped scaling
# rows past the single-device ceiling (n63..n255 + the 100M-parameter
# LM through the sharded wavefront engine), the lane-throughput sharding
# row, and the serving-engine rows (throughput, tail latency, tail
# latency through a live weight swap, and the staleness/loss pairing).
# The structural gate requires them even against baselines that predate
# the rows, so a future PR cannot silently drop the failover scenarios,
# the production-scale paths, or the serving loop.
REQUIRED_PREFIXES = {
    "showdown": ("showdown/root_failover/", "churn/"),
    "scaling": ("scaling/n63", "scaling/n127", "scaling/n255",
                "lm100m/"),
    "sweep": ("sweep/fleet_sharded_",),
    "serve": ("serve/reqs_per_s", "serve/p50_us", "serve/p99_us",
              "serve/swap_p99_us", "serve/staleness_vs_loss"),
}


def _compare(records: list[dict], baseline_path: str,
             threshold: float, run_meta: dict | None = None,
             structural: bool = False) -> list[dict]:
    """Diff ``records`` against a committed BENCH_*.json.

    Returns every row that should fail the gate: regressions beyond
    ``threshold``, rows that errored this run (derived ``ERROR:...`` —
    correctness-only rows intentionally record ``nan`` us and must NOT
    gate), and baseline rows that disappeared.  Regressions and vanished rows
    are only gated when the run's quick/impl settings match the
    baseline's recorded meta (quick changes per-call compile
    amortization, impl changes which rows exist), and vanished rows only
    for suites that actually ran (so ``--only`` subsets pass).  Errored
    rows always gate — they are about this run, not the baseline.
    ``structural=True`` reports timing ratios but never gates on them
    (errored/missing rows only — shared CI runners are too noisy for a
    timing threshold).
    """
    with open(baseline_path) as f:
        base_doc = json.load(f)
    old = {(r["suite"], r["name"]): r["us_per_call"]
           for r in base_doc["rows"]}
    base_meta = base_doc.get("meta", {})
    # quick changes K (compile amortization) and impl changes which rows
    # exist: per-call ratios and row presence are only comparable when
    # this run was recorded the same way as the baseline
    comparable = run_meta is None or all(
        run_meta.get(k) == base_meta.get(k) for k in ("quick", "impl"))
    fresh = {(r["suite"], r["name"]): r for r in records}
    executed = {r["suite"] for r in records}
    problems = []
    print(f"# --- compare vs {baseline_path} "
          f"(threshold +{threshold:.0%}) ---", file=sys.stderr)
    for r in records:
        base = old.get((r["suite"], r["name"]))
        new = r["us_per_call"]
        if new is None:
            if str(r.get("derived", "")).startswith("ERROR:"):
                print(f"# {r['suite']}/{r['name']}: ERRORED this run "
                      f"({r['derived']})", file=sys.stderr)
                problems.append({**r, "problem": "errored"})
            # else: a correctness-only row (nan us by design) — no gate
            continue
        if not base:
            # new row, or the baseline errored there (None) or recorded
            # 0 us: no meaningful ratio to gate on
            continue
        ratio = new / base
        flag = (" REGRESSION" if comparable and not structural
                and ratio > 1 + threshold else "")
        print(f"# {r['suite']}/{r['name']}: {base:.1f} -> {new:.1f} us "
              f"({ratio - 1:+.0%} vs baseline){flag}", file=sys.stderr)
        if flag:
            problems.append({**r, "problem": "regression",
                             "baseline_us": base, "ratio": ratio})
    if structural:
        print("# (structural mode: timing regressions reported, "
              "not gated)", file=sys.stderr)
        for suite, prefixes in REQUIRED_PREFIXES.items():
            if suite not in executed:
                continue
            for pre in prefixes:
                ok = any(s == suite and n.startswith(pre)
                         and not str(r.get("derived", "")
                                     ).startswith("ERROR:")
                         for (s, n), r in fresh.items())
                if not ok:
                    print(f"# {suite}: REQUIRED row prefix {pre!r} "
                          f"produced no healthy rows", file=sys.stderr)
                    problems.append({"suite": suite, "name": pre,
                                     "problem": "required-missing"})
    if not comparable:
        print("# (regression/missing gates off: run quick/impl settings "
              "differ from the baseline's)", file=sys.stderr)
    else:
        for (suite, name), base in old.items():
            if suite in executed and (suite, name) not in fresh:
                print(f"# {suite}/{name}: MISSING from this run "
                      f"(baseline {base} us)", file=sys.stderr)
                problems.append({"suite": suite, "name": name,
                                 "problem": "missing", "baseline_us": base})
    if problems:
        kinds = {}
        for p in problems:
            kinds[p["problem"]] = kinds.get(p["problem"], 0) + 1
        desc = ", ".join(f"{v} {k}" for k, v in sorted(kinds.items()))
        print(f"# gate FAILS: {desc} (threshold +{threshold:.0%})",
              file=sys.stderr)
    else:
        print("# no regressions, no missing/errored rows", file=sys.stderr)
    return problems


if __name__ == "__main__":
    main()
