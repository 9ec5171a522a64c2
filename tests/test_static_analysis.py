"""Trace-safety static analysis suite (analysis/): rule fixtures with
known violations, red-to-green jaxpr contracts, the retrace guard, and
the strict clean run over the real package — the tier-1 hook that makes
new lint violations and jaxpr-contract breaks fail the suite."""

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from lightgbm_tpu.analysis.lint import (
    Finding,
    RULES,
    format_findings,
    lint_package,
    lint_source,
)

REPO = Path(__file__).resolve().parents[1]


# ----------------------------------------------------------------- lint
_VIOLATIONS = '''
import jax
import jax.numpy as jnp
import numpy as np
from functools import partial

@jax.jit
def tracer_hazards(x, y):
    if x > 0:                       # tracer-branch
        z = float(x)                # tracer-cast
    q = x > 1 and y > 2             # tracer-branch (short-circuit)
    w = np.asarray(y)               # np-on-tracer
    v = x.item()                    # host-sync
    return x + y

@partial(jax.jit, static_argnames=("n",))
def static_ok(x, n):
    if n > 2:                       # static arg: clean
        x = x + 1
    G, N = x.shape
    if N > 4:                       # shape: clean
        x = x * 2
    if x is None:                   # identity: clean
        return x
    return jnp.sum(x)

def helper(a, flag=False):
    if flag:                        # literal-default param: clean
        a = a * 2
    return bool(a > 0)              # tracer-cast through the call graph

@jax.jit
def root(x):
    return helper(x)

def not_traced(q):
    if q:                           # host code: clean
        return float(q)
    return 0.0

def make_baked(base):
    arr = jnp.asarray(base)
    def inner(z):
        return z + arr
    return jax.jit(inner)           # device-closure

def mut(a, b=[]):                   # mutable-default
    return a
'''


def _rules_at(findings, rule):
    return [f for f in findings if f.rule == rule and not f.suppressed]


def test_each_rule_fires_on_fixture():
    fs = lint_source(_VIOLATIONS)
    assert len(_rules_at(fs, "tracer-branch")) == 2
    assert len(_rules_at(fs, "tracer-cast")) == 2  # float() + helper bool()
    assert len(_rules_at(fs, "np-on-tracer")) == 1
    assert len(_rules_at(fs, "host-sync")) == 1
    assert len(_rules_at(fs, "device-closure")) == 1
    assert len(_rules_at(fs, "mutable-default")) == 1
    # every registered rule is exercised by this fixture
    assert {f.rule for f in fs} == set(RULES)


def test_static_constructs_stay_clean():
    fs = lint_source(_VIOLATIONS)
    lines = {f.line for f in fs}
    src_lines = _VIOLATIONS.splitlines()
    for i, txt in enumerate(src_lines, start=1):
        if "clean" in txt:
            assert i not in lines, f"false positive on line {i}: {txt}"


def test_suppression_comment_and_file_allow():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return float(x)  # lint: allow[tracer-cast]\n"
    )
    fs = lint_source(src)
    assert len(fs) == 1 and fs[0].suppressed
    src2 = (
        "# lint: allow-file[tracer-cast]\n"
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return float(x)\n"
    )
    fs2 = lint_source(src2)
    assert len(fs2) == 1 and fs2[0].suppressed
    # an unrelated rule id does NOT suppress
    src3 = src.replace("tracer-cast", "host-sync")
    fs3 = lint_source(src3)
    assert len(fs3) == 1 and not fs3[0].suppressed


def test_real_package_is_lint_clean():
    """The acceptance bar: zero unsuppressed violations over the real
    package source (intentional sites are annotated, not silenced)."""
    fs = lint_package(str(REPO / "lightgbm_tpu"))
    bad = [f for f in fs if not f.suppressed]
    assert not bad, "\n" + format_findings(bad)


def test_format_findings_counts():
    fs = lint_source(_VIOLATIONS)
    out = format_findings(fs, show_suppressed=True)
    assert "violation(s)" in out and "tracer-cast" in out


# ----------------------------------------------------- jaxpr contracts
def _wire_fixture_jaxpr(widen: bool):
    """An 8-shard psum_scatter wire, int32 or deliberately f32-widened
    (shared with tests/test_cost_audit.py's wire-bytes tests). Uses
    the audit suite's own `_mesh()` (the one XLA_FLAGS bootstrap
    owner) rather than a private mesh builder."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from lightgbm_tpu.analysis.jaxpr_audit import _mesh

    mesh = _mesh()

    def f(h):
        wire = h.astype(jnp.float32) if widen else h.astype(jnp.int32)
        return lax.psum_scatter(
            wire, "data", scatter_dimension=0, tiled=True
        )

    sm = jax.shard_map(f, mesh=mesh, in_specs=(P(None, "data"),),
                       out_specs=P("data"), check_vma=False)
    return jax.make_jaxpr(sm)(
        jax.ShapeDtypeStruct((16, 8), jnp.int32)
    )


def test_wire_dtype_red_to_green():
    """The dtype contract, parameterized (satellite of the int16 wire
    plan): a deliberately f32-widened reduce-scatter wire FAILS
    wire_dtype("int32"); the int32 wire passes — and the same int32
    wire FAILS wire_dtype("int16"), which is what pins the ROADMAP 3a
    flip once QUANT_WIRE_DTYPE changes."""
    from lightgbm_tpu.analysis.jaxpr_audit import audit_jaxpr, wire_dtype

    red = audit_jaxpr(_wire_fixture_jaxpr(widen=True),
                      [wire_dtype("int32")], "widened")
    assert not red.ok, red.format()
    green = audit_jaxpr(_wire_fixture_jaxpr(widen=False),
                        [wire_dtype("int32")], "int32")
    assert green.ok, green.format()
    # after the int16 flip, today's int32 wire must read as a regression
    not_halved = audit_jaxpr(_wire_fixture_jaxpr(widen=False),
                             [wire_dtype("int16")], "int32-vs-int16")
    assert not not_halved.ok, not_halved.format()


def test_entry_table_records_quant_wire_dtype():
    """The quant data-parallel entry declares its wire dtype in the
    entry table (the cost auditor and the jaxpr contract both read
    it), and it matches the module-level QUANT_WIRE_DTYPE flip point."""
    from lightgbm_tpu.analysis.jaxpr_audit import ENTRIES, QUANT_WIRE_DTYPE

    assert ENTRIES["rounds_quant_rs"].wire_dtype == QUANT_WIRE_DTYPE
    # ROADMAP 3a flipped in round 12 (rs_wire_dtype narrowest-exact
    # policy); the int32 step-down regime keeps its own pinned entry
    assert QUANT_WIRE_DTYPE == "int16"
    assert ENTRIES["rounds_quant_rs_int32"].wire_dtype == "int32"


def test_host_callback_contract_red_to_green():
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.analysis.jaxpr_audit import (
        audit_jaxpr,
        no_host_callbacks,
    )

    def dirty(x):
        return jax.pure_callback(
            lambda v: np.asarray(v) * 2,
            jax.ShapeDtypeStruct((4,), jnp.float32), x,
        )

    red = audit_jaxpr(
        jax.make_jaxpr(dirty)(jax.ShapeDtypeStruct((4,), jnp.float32)),
        [no_host_callbacks()], "callback",
    )
    assert not red.ok
    green = audit_jaxpr(
        jax.make_jaxpr(lambda x: x * 2)(
            jax.ShapeDtypeStruct((4,), jnp.float32)
        ),
        [no_host_callbacks()], "clean",
    )
    assert green.ok


def test_eqn_budget_contract_red_to_green():
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.analysis.jaxpr_audit import audit_jaxpr, within_budget

    closed = jax.make_jaxpr(lambda x: jnp.sin(x) + jnp.cos(x) * 2)(
        jax.ShapeDtypeStruct((4,), jnp.float32)
    )
    assert not audit_jaxpr(closed, [within_budget(1)], "tiny").ok
    assert audit_jaxpr(closed, [within_budget(100)], "roomy").ok
    # a missing checked-in budget is a FAILURE, not a skip
    assert not audit_jaxpr(closed, [within_budget(None)], "nobudget").ok


def _gather_operand_sizes(lowered_text: str) -> list:
    """Element counts of the operand of every gather of a lowering."""
    import math
    import re

    sizes = []
    for line in lowered_text.splitlines():
        if "stablehlo.gather" not in line:
            continue
        operand = re.search(r":\s*\(tensor<([0-9x]*)x?[a-z]", line).group(1)
        sizes.append(math.prod(int(d) for d in operand.split("x") if d))
    return sizes


def test_no_element_gather_of_the_category_sets_red_to_green():
    """The valid traversal tests a row's category by bit words from its
    per-node table (tree.cat_mask_words, PR 37): its lowering holds no
    gather over the (max_nodes x B) category sets, which cost this chip
    4 ms per 1M rows a level (PERF.md section 6). Red: the flat element
    gather the traversal used to trace."""
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.learner import GrowerSpec
    from lightgbm_tpu.parallel.data_parallel import _tree_arrays_structure
    from lightgbm_tpu.tree import traverse_tree_bins

    L, B, G, N = 255, 255, 8, 4096
    s = jax.ShapeDtypeStruct
    arrays = _tree_arrays_structure(
        GrowerSpec(num_leaves=L, num_bins=B, max_depth=-1))
    bins, nan_bin = s((G, N), jnp.int32), s((G,), jnp.int32)

    red = jax.jit(lambda m, k, b: m.reshape(-1)[k * B + b]).lower(
        arrays.node_cat_mask, s((N,), jnp.int32), s((N,), jnp.int32))
    assert (L - 1) * B in _gather_operand_sizes(red.as_text())

    for has_cat in (True, False):
        text = jax.jit(traverse_tree_bins, static_argnames="has_cat").lower(
            arrays, bins, nan_bin, has_cat=has_cat).as_text()
        sizes = _gather_operand_sizes(text)
        assert (L - 1) * B not in sizes, sizes
        # what is gathered is per-node or per-column: never per (node, bin)
        assert all(n <= 32 * (L - 1) for n in sizes), sizes


def test_rs_exact_ok_bounds():
    """The overflow/exactness gate (ADVICE r5 medium) as pure policy:
    global rows * levels < 2^31 AND local rows * levels < 2^24."""
    from lightgbm_tpu.learner.histogram import rs_exact_ok

    assert rs_exact_ok(2048, 8, 16)
    # local bound: rows * levels hits exactly 2^24 -> inexact f32 cast
    assert rs_exact_ok(2 ** 16 - 1, 8, 256)  # 16776960 < 2^24: ok
    assert not rs_exact_ok(2 ** 16, 8, 256)  # 2^24 exactly: gate off
    # global int32 wrap ISOLATED from the local bound: per-shard sum
    # 16776960 < 2^24 is fine, but 256 ranks push the global cell sum
    # to ~4.29e9 > 2^31 — only the global clause can catch this
    assert not rs_exact_ok(2 ** 16 - 1, 256, 256)
    # unquantized callers pass levels=0 -> treated as exact counts
    assert rs_exact_ok(2 ** 20, 8, 0)


def test_grower_wire_contracts_end_to_end():
    """The real entries: inside the bounds the int32 reduce-scatter
    wire is present end to end; past the per-shard bound the overflow
    gate removes it and the f32 psum fallback appears. (Red-to-green
    for the gate: before rounds.py grew rs_exact_ok, the overflow
    entry traced a reduce_scatter and this test fails.)"""
    from lightgbm_tpu.analysis.jaxpr_audit import run_audits

    results = {
        r.name: r
        for r in run_audits(
            names=["rounds_quant_rs", "rounds_quant_rs_overflow"]
        )
    }
    ok_entry = results["rounds_quant_rs"]
    assert ok_entry.ok, ok_entry.format()
    over = results["rounds_quant_rs_overflow"]
    assert over.ok, over.format()


def test_fold_attr_static_audit_green():
    from lightgbm_tpu.analysis.jaxpr_audit import audit_fold_attrs

    r = audit_fold_attrs()
    assert r.ok, r.format()


def test_fold_attr_runtime_audit_red_to_green():
    """A fold-varying device array outside _OBJ_FOLD_ATTRS must fail
    loudly at fused build time (ADVICE r5 item 3)."""
    import jax.numpy as jnp

    from lightgbm_tpu.boosting import _audit_fold_attrs
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.log import LightGBMError
    from lightgbm_tpu.objectives import create_objective

    obj = create_objective(Config({"objective": "regression"}))
    obj.label = jnp.zeros(8, jnp.float32)
    _audit_fold_attrs(obj)  # green: listed attrs only
    obj._evil_fold_state = jnp.ones(8, jnp.float32)
    with pytest.raises(LightGBMError, match="_evil_fold_state"):
        _audit_fold_attrs(obj)


# ------------------------------------------------------- retrace guard
def test_retrace_guard_red_to_green(retrace_guard):
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.analysis.retrace import RetraceError

    @jax.jit
    def f(x):
        return x * 2 + 1

    f(jnp.ones(3))  # warmup
    with retrace_guard(entry_points=[f], what="stable shapes") as rep:
        f(jnp.ones(3))
        f(jnp.zeros(3))
    assert rep.per_entry["f"] == 0

    # deliberately retracing function: every call sees a fresh shape
    with pytest.raises(RetraceError, match="f: 2 new trace-cache"):
        with retrace_guard(entry_points=[f], what="drifting shapes"):
            f(jnp.ones(4))
            f(jnp.ones(5))


def test_retrace_guard_leak_detection(retrace_guard):
    import jax
    import jax.numpy as jnp

    leaked = []

    with pytest.raises(Exception, match="[Ll]eak"):
        with retrace_guard(check_leaks=True):

            @jax.jit
            def g(x):
                leaked.append(x)  # tracer escapes the trace
                return x

            g(jnp.ones(2))


def test_grower_trains_without_retrace(retrace_guard):
    """The training entry point itself: a second identically-shaped
    tree growth must reuse the first trace (the regression class the
    guard exists for)."""
    import jax.numpy as jnp

    import lightgbm_tpu as lgb
    from lightgbm_tpu.learner.grower import grow_tree

    rs = np.random.RandomState(0)
    X = rs.randn(400, 5)
    y = (X @ rs.randn(5) > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
              "tpu_growth_mode": "exact"}
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    lgb.train(params, ds, num_boost_round=2)  # warmup traces everything
    with retrace_guard(entry_points=[grow_tree], max_retraces=0,
                       what="repeated identical training"):
        ds2 = lgb.Dataset(X, label=y, free_raw_data=False)
        lgb.train(params, ds2, num_boost_round=2)


# ----------------------------------------------------- strict CLI hook
@pytest.mark.slow
def test_cli_strict_exits_zero():
    """`python -m lightgbm_tpu.analysis --strict` is the CI hook: a new
    unsuppressed lint violation or broken jaxpr contract fails it."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu.analysis", "--strict"],
        cwd=str(REPO), env=env, capture_output=True, text=True,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "analysis: clean" in proc.stdout


def test_strict_equivalent_in_process():
    """The same strict gate, in-process (runs in tier-1 even when the
    subprocess variant is skipped as slow): zero unsuppressed findings
    from BOTH AST linters AND every jaxpr/fold-attr audit green. (The
    cost/memory compiles are covered by their own tests in
    test_cost_audit.py plus the slow CLI test above — recompiling all
    five entries here would double tier-1's audit wall time.)"""
    from lightgbm_tpu.analysis.concurrency_lint import (
        concurrency_lint_package,
    )
    from lightgbm_tpu.analysis.jaxpr_audit import run_audits

    fs = lint_package(str(REPO / "lightgbm_tpu"))
    assert not [f for f in fs if not f.suppressed], format_findings(fs)
    cfs = concurrency_lint_package(str(REPO / "lightgbm_tpu"))
    assert not [f for f in cfs if not f.suppressed], \
        format_findings(cfs, label="concurrency")
    results = run_audits()
    bad = [r.format() for r in results if not r.ok]
    assert not bad, "\n".join(bad)
    # Pass 7 (scaling contracts) tier-1 hook: the tiny D in {1, 2}
    # ladder on the three law archetypes (1/D, elected + its baseline,
    # bounded) — budget pins still checked EXACT at those rungs. The
    # int32/overflow entries and the 4/8 rungs ride --strict /
    # tools/analysis.sh; re-tracing all five entries at every rung
    # here would blow the tier-1 time budget.
    from lightgbm_tpu.analysis.scale_audit import (
        TIER1_LADDER,
        run_scale_audits,
    )

    sresults = run_scale_audits(
        names=["rounds_quant_rs", "rounds_voting", "feature_parallel"],
        ladder=TIER1_LADDER,
    )
    sbad = [r.format() for r in sresults if not r.ok]
    assert not sbad, "\n".join(sbad)
