"""CLI: `python -m lightgbm_tpu.analysis [--strict] [...]`.

Runs every registered analysis pass (passes.PASSES — trace-safety
lint, concurrency lint, jaxpr invariant audits, XLA cost/memory +
wire-bytes audits) and prints a combined report. `--strict` (the CI /
tier-1 hook mode) exits 1 on any unsuppressed finding or failed
contract; the default mode reports and exits 0.

Budget maintenance:
  --update-budget     rewrite jaxpr_budget.json (+25% headroom)
  --refresh-budgets   rewrite cost_budget.json (+25% headroom on cost
                      metrics, EXACT wire bytes) and scale_budget.json
                      (EXACT per-rung pins over the full D-ladder),
                      printing an old->new diff of each for review

The jax-backed audits need a multi-device CPU mesh; this entry point
forces `jax_platforms=cpu` with 8 virtual devices (same as
tests/conftest.py) so a bare invocation never touches real
accelerators.
"""

from __future__ import annotations

import argparse
import os
import sys


def _force_cpu_mesh() -> None:
    """cpu + 8 virtual devices BEFORE any backend initializes (package
    import already loaded jax, but the backend is lazy — mirror the
    conftest.py override)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")


def main(argv=None) -> int:
    from .passes import PASSES

    ap = argparse.ArgumentParser(
        prog="python -m lightgbm_tpu.analysis",
        description="static analysis suite: "
        + "; ".join(f"{p.name} = {p.doc}" for p in PASSES.values())
        + " (docs/STATIC_ANALYSIS.md)",
    )
    ap.add_argument("--strict", action="store_true",
                    help="exit 1 on any violation / failed contract")
    ap.add_argument("--lint-only", action="store_true",
                    help="only the AST passes (no jax backend needed)")
    ap.add_argument("--audit-only", action="store_true",
                    help="only the jaxpr/cost audits (skip the AST lints)")
    ap.add_argument("--passes", default=None,
                    help="comma-separated subset of passes to run "
                    f"(registered: {', '.join(PASSES)})")
    ap.add_argument("--show-suppressed", action="store_true",
                    help="also print suppressed lint findings")
    ap.add_argument("--update-budget", action="store_true",
                    help="rewrite jaxpr_budget.json from current sizes "
                    "(+25%% headroom); review the diff before commit")
    ap.add_argument("--refresh-budgets", action="store_true",
                    help="rewrite cost_budget.json from current compiles "
                    "(+25%% headroom, exact wire bytes) and print the diff")
    ap.add_argument("--package", default=None,
                    help="package directory to lint (default: the "
                    "installed lightgbm_tpu package)")
    args = ap.parse_args(argv)

    if args.passes is not None:
        names = [n.strip() for n in args.passes.split(",") if n.strip()]
        if not names:
            # an empty selection must not report a vacuous clean run
            ap.error("--passes got an empty selection; registered: "
                     + ", ".join(PASSES))
    elif args.lint_only:
        names = [n for n, p in PASSES.items() if not p.needs_jax]
    elif args.audit_only:
        names = [n for n, p in PASSES.items() if p.needs_jax]
    else:
        names = list(PASSES)

    if any(PASSES[n].needs_jax for n in names if n in PASSES) \
            or args.update_budget or args.refresh_budgets:
        _force_cpu_mesh()

    if args.update_budget or args.refresh_budgets:
        # budget maintenance still reports contract health: a FAILing
        # non-budget contract (wire dtype, callbacks, f64) during a
        # refresh must not hide behind "budgets updated" under --strict
        failed = False
        if args.update_budget:
            from .jaxpr_audit import run_audits

            results = run_audits(update_budget=True)
            for r in results:
                print(r.format())
            failed |= not all(r.ok for r in results)
            print("jaxpr_budget.json updated")
        if args.refresh_budgets:
            from .cost_audit import (
                format_budget_diff,
                refresh_budgets,
                run_cost_audits,
            )

            old, new = refresh_budgets()
            print("cost_budget.json updated:")
            print(format_budget_diff(old, new))
            results = run_cost_audits()
            failed |= not all(r.ok for r in results)
            for r in results:
                if not r.ok:
                    print(r.format())
            # scaling-contract pins too (full D-ladder, exact)
            from .scale_audit import (
                format_scale_diff,
                refresh_scale_budget,
                run_scale_audits,
            )

            sold, snew = refresh_scale_budget()
            print("scale_budget.json updated:")
            print(format_scale_diff(sold, snew))
            sresults = run_scale_audits()
            failed |= not all(r.ok for r in sresults)
            for r in sresults:
                if not r.ok:
                    print(r.format())
        if failed:
            print("analysis: FAIL (budgets updated, but contracts are "
                  "red)" if args.strict else
                  "analysis: contract violations found (non-strict: "
                  "exit 0)")
            return 1 if args.strict else 0
        return 0

    from .passes import run_passes

    results = run_passes(names, pkg_root=args.package,
                         show_suppressed=args.show_suppressed)
    for r in results:
        print(f"== {r.name} ==")
        print(r.report)
    failed = not all(r.ok for r in results)
    if failed:
        print("analysis: FAIL" if args.strict else
              "analysis: violations found (non-strict: exit 0)")
        return 1 if args.strict else 0
    print("analysis: clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
