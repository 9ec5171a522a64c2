"""Registry of every analysis pass the `--strict` gate must run.

One table, consumed by `__main__.py` (the CLI) and asserted by the
meta-test in tests/test_cost_audit.py: a new auditor registered here is
automatically part of the strict gate, and an auditor removed from the
strict path without being removed here fails the meta-test — the gate
cannot silently shed passes.

Each pass runs independently and returns a PassResult; `needs_jax`
splits the pure-AST passes (runnable anywhere, `--lint-only`) from the
trace/compile passes that need the multi-device CPU backend
(`--audit-only` skips the AST side instead).
"""

from __future__ import annotations

from typing import Callable, Dict, List, NamedTuple, Optional, Sequence


class PassResult(NamedTuple):
    name: str
    ok: bool
    report: str


def _run_lint(pkg_root: Optional[str], show_suppressed: bool) -> PassResult:
    from .lint import format_findings, lint_package

    findings = lint_package(pkg_root) if pkg_root else lint_package()
    return PassResult(
        "lint",
        not any(not f.suppressed for f in findings),
        format_findings(findings, show_suppressed=show_suppressed),
    )


def _run_concurrency(pkg_root: Optional[str],
                     show_suppressed: bool) -> PassResult:
    from .concurrency_lint import concurrency_lint_package
    from .lint import format_findings

    findings = concurrency_lint_package(pkg_root) \
        if pkg_root else concurrency_lint_package()
    return PassResult(
        "concurrency",
        not any(not f.suppressed for f in findings),
        format_findings(findings, show_suppressed=show_suppressed,
                        label="concurrency"),
    )


def _run_jaxpr(pkg_root: Optional[str], show_suppressed: bool) -> PassResult:
    from .jaxpr_audit import run_audits

    results = run_audits()
    return PassResult(
        "jaxpr",
        all(r.ok for r in results),
        "\n".join(r.format() for r in results),
    )


def _run_cost(pkg_root: Optional[str], show_suppressed: bool) -> PassResult:
    from .cost_audit import run_cost_audits

    results = run_cost_audits()
    return PassResult(
        "cost",
        all(r.ok for r in results),
        "\n".join(r.format() for r in results),
    )


def _run_scale(pkg_root: Optional[str], show_suppressed: bool) -> PassResult:
    from .scale_audit import run_scale_audits

    results = run_scale_audits()  # full D-ladder: the strict gate
    return PassResult(
        "scale",
        all(r.ok for r in results),
        "\n".join(r.format() for r in results),
    )


class AnalysisPass(NamedTuple):
    name: str
    needs_jax: bool
    doc: str
    run: Callable[[Optional[str], bool], PassResult]


PASSES: Dict[str, AnalysisPass] = {
    "lint": AnalysisPass(
        "lint", False,
        "trace-safety AST linter (lint.py)", _run_lint,
    ),
    "concurrency": AnalysisPass(
        "concurrency", False,
        "lock-discipline linter for the threaded serving layer "
        "(concurrency_lint.py)", _run_concurrency,
    ),
    "jaxpr": AnalysisPass(
        "jaxpr", True,
        "jaxpr invariant auditor: wire dtype / callbacks / f64 / eqn "
        "budgets (jaxpr_audit.py)", _run_jaxpr,
    ),
    "cost": AnalysisPass(
        "cost", True,
        "XLA cost/memory budgets + collective wire-bytes accounting "
        "(cost_audit.py)", _run_cost,
    ),
    "scale": AnalysisPass(
        "scale", True,
        "SPMD scaling-contract auditor: collective census, wire "
        "scaling laws, and sharding-spec verification over the "
        "D in {1,2,4,8} mesh ladder (scale_audit.py)", _run_scale,
    ),
}


def run_passes(names: Optional[Sequence[str]] = None,
               pkg_root: Optional[str] = None,
               show_suppressed: bool = False) -> List[PassResult]:
    """Run the named passes (default: every registered pass, the
    strict-gate set). Unknown names raise — a typoed pass must not
    pass vacuously."""
    if names is None:
        names = list(PASSES)
    unknown = set(names) - set(PASSES)
    if unknown:
        raise KeyError(
            f"unknown analysis pass(es) {sorted(unknown)}; "
            f"registered: {sorted(PASSES)}"
        )
    return [
        PASSES[n].run(pkg_root, show_suppressed)
        for n in PASSES if n in set(names)
    ]
