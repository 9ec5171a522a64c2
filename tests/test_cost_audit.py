"""XLA cost/memory auditor + collective wire-bytes accounting
(analysis/cost_audit.py): wire accounting red-to-green on deliberately
widened payloads, budget contracts, the budget/entry consistency
meta-tests, and the pass registry the --strict gate runs."""

import json
from pathlib import Path

import pytest

from lightgbm_tpu.analysis.cost_audit import (
    CostSummary,
    audit_cost,
    collect_wire,
)

REPO = Path(__file__).resolve().parents[1]
BUDGETS = REPO / "lightgbm_tpu" / "analysis"


def _wire_jaxpr(widen: bool):
    from tests.test_static_analysis import _wire_fixture_jaxpr

    return _wire_fixture_jaxpr(widen)


def _summary(wire=(), **kw) -> CostSummary:
    base = dict(flops=100, bytes_accessed=200, temp_bytes=300,
                output_bytes=40, argument_bytes=50)
    base.update(kw)
    return CostSummary(wire=tuple(wire), **base)


# ------------------------------------------------------- wire account
def test_collect_wire_reads_payload_bytes():
    """The per-shard psum_scatter payload: (16, 8) int32 over 8 shards
    -> a (16, 1) int32 reduce_scatter operand = 64 bytes."""
    wire = collect_wire(_wire_jaxpr(widen=False))
    rs = [w for w in wire if w.prim == "reduce_scatter"]
    assert len(rs) == 1, wire
    assert rs[0].dtype == "int32" and rs[0].nbytes == 16 * 4, rs
    assert sum(w.nbytes for w in wire) == rs[0].nbytes


def test_widened_collective_payload_fails_wire_audit():
    """ACCEPTANCE: f32 in place of int32 on the quant reduce fails the
    wire audit — the dtype leg catches the same-itemsize f32 swap, and
    the exact byte budget catches any payload growth (the int16-era
    budget makes today's int32 wire read as the 2x regression it
    would be)."""
    int32_summary = _summary(wire=collect_wire(_wire_jaxpr(widen=False)))
    f32_summary = _summary(wire=collect_wire(_wire_jaxpr(widen=True)))
    budget = {"flops": 1000, "bytes_accessed": 1000, "temp_bytes": 1000,
              "output_bytes": 1000, "wire_bytes": int32_summary.wire_bytes}

    green = audit_cost(int32_summary, budget, "int32", wire_dtype="int32")
    assert green.ok, green.format()

    red = audit_cost(f32_summary, budget, "widened", wire_dtype="int32")
    assert not red.ok, red.format()
    bad = [c for c in red.contracts if not c.ok]
    assert any(c.name == "wire_int32" for c in bad), red.format()

    # the ROADMAP 3a ratchet: once the budget pins the halved int16
    # wire, an int32 payload EXCEEDS it byte-for-byte
    int16_era = dict(budget, wire_bytes=int32_summary.wire_bytes // 2)
    regressed = audit_cost(int32_summary, int16_era, "post-flip",
                           wire_dtype="int16")
    assert not regressed.ok
    names = {c.name for c in regressed.contracts if not c.ok}
    assert "wire_bytes" in names and "wire_int16" in names, \
        regressed.format()


# ------------------------------------------------------ cost budgets
def test_cost_budget_red_to_green():
    s = _summary()
    roomy = {"flops": 1000, "bytes_accessed": 1000, "temp_bytes": 1000,
             "output_bytes": 1000, "wire_bytes": 0}
    assert audit_cost(s, roomy, "roomy").ok

    tiny = dict(roomy, temp_bytes=299)
    r = audit_cost(s, tiny, "tiny")
    assert not r.ok
    assert any(c.name == "temp_bytes" and not c.ok for c in r.contracts)

    # a missing budget (entry or key) is a FAILURE, not a skip
    assert not audit_cost(s, None, "nobudget").ok
    partial = {k: v for k, v in roomy.items() if k != "flops"}
    r2 = audit_cost(s, partial, "partial")
    assert not r2.ok
    assert any(c.name == "flops" and not c.ok for c in r2.contracts)


def test_refresh_budgets_headroom_and_diff(monkeypatch, tmp_path):
    """--refresh-budgets writes +25% headroom on cost metrics, EXACT
    wire bytes, and the diff formatter reports per-metric deltas."""
    from lightgbm_tpu.analysis import cost_audit

    path = tmp_path / "cost_budget.json"
    monkeypatch.setattr(cost_audit, "_BUDGET_PATH", path)
    from lightgbm_tpu.analysis.cost_audit import WireRecord

    stub = _summary(
        wire=[WireRecord("reduce_scatter", (16,), "int32", 64)],
        flops=1000,
    )
    monkeypatch.setattr(cost_audit, "compile_entry", lambda name: stub)
    old, new = cost_audit.refresh_budgets()
    assert old == {}
    written = json.loads(path.read_text())
    assert set(written) == set(cost_audit.ENTRIES)
    for b in written.values():
        assert b["flops"] == 1250       # ceil(1000 * 1.25)
        assert b["wire_bytes"] == 64    # exact, no headroom
    diff = cost_audit.format_budget_diff(old, new)
    assert "flops: None -> 1250" in diff
    # unchanged refresh reads as unchanged
    old2, new2 = cost_audit.refresh_budgets()
    assert "unchanged" in cost_audit.format_budget_diff(old2, new2)


# -------------------------------------------------- consistency meta
def test_every_entry_has_both_budgets():
    """Meta-test: ENTRIES, jaxpr_budget.json and cost_budget.json agree
    key-for-key — no orphan budgets, no unbudgeted entries. (An entry
    added without budgets would fail its audits too, but this fails
    FAST and names the missing side.)"""
    from lightgbm_tpu.analysis.jaxpr_audit import ENTRIES

    jaxpr = json.loads((BUDGETS / "jaxpr_budget.json").read_text())
    cost = json.loads((BUDGETS / "cost_budget.json").read_text())
    assert set(jaxpr) == set(ENTRIES), (
        f"jaxpr_budget.json keys {sorted(jaxpr)} != entries "
        f"{sorted(ENTRIES)} — run --update-budget / prune orphans"
    )
    assert set(cost) == set(ENTRIES), (
        f"cost_budget.json keys {sorted(cost)} != entries "
        f"{sorted(ENTRIES)} — run --refresh-budgets / prune orphans"
    )
    required = {"flops", "bytes_accessed", "temp_bytes", "output_bytes",
                "wire_bytes"}
    for name, b in cost.items():
        assert required <= set(b), f"{name} budget missing {required - set(b)}"


def test_scale_budget_consistent_with_mesh_entries():
    """Meta-test for Pass 7's pins: scale_budget.json keys == the
    mesh-bearing entries == the declared SCALE_ENTRIES specs, and
    every entry pins every rung of the full ladder with every budget
    key (a missing rung would let the 4/8 legs rot while tier-1 only
    exercises {1, 2})."""
    from lightgbm_tpu.analysis.jaxpr_audit import mesh_entry_names
    from lightgbm_tpu.analysis.scale_audit import (
        _BUDGET_KEYS,
        LADDER,
        SCALE_ENTRIES,
    )

    scale = json.loads((BUDGETS / "scale_budget.json").read_text())
    mesh = set(mesh_entry_names())
    assert set(scale) == mesh, (
        f"scale_budget.json keys {sorted(scale)} != mesh entries "
        f"{sorted(mesh)} — run --refresh-budgets / prune orphans"
    )
    assert set(SCALE_ENTRIES) == mesh, (
        f"SCALE_ENTRIES {sorted(SCALE_ENTRIES)} != mesh entries "
        f"{sorted(mesh)} — declare a ScaleSpec for every mesh entry"
    )
    for name, pins in scale.items():
        assert set(pins) == {str(d) for d in LADDER}, (
            f"{name} pins rungs {sorted(pins)} != ladder {LADDER}"
        )
        for d, pin in pins.items():
            assert set(pin) == set(_BUDGET_KEYS), (
                f"{name}[D={d}] keys {sorted(pin)}"
            )


def test_strict_gate_runs_every_registered_pass(monkeypatch, capsys):
    """Meta-test: `--strict` exercises ALL registered auditors — stub
    every pass runner, drive the real CLI main(), and assert each got
    called (the gate cannot silently shed a pass)."""
    from lightgbm_tpu.analysis import __main__ as cli
    from lightgbm_tpu.analysis import passes

    ran = []

    def stub(name):
        def run(pkg_root, show_suppressed):
            ran.append(name)
            return passes.PassResult(name, True, f"{name} ok")
        return run

    for name, p in passes.PASSES.items():
        monkeypatch.setitem(passes.PASSES, name, p._replace(run=stub(name)))
    monkeypatch.setattr(cli, "_force_cpu_mesh", lambda: None)
    rc = cli.main(["--strict"])
    assert rc == 0
    assert set(ran) == set(passes.PASSES)
    assert "analysis: clean" in capsys.readouterr().out

    # a failing pass flips the strict exit code
    bad = passes.PASSES["cost"]._replace(
        run=lambda pkg_root, show_suppressed: passes.PassResult(
            "cost", False, "cost FAIL"
        )
    )
    monkeypatch.setitem(passes.PASSES, "cost", bad)
    assert cli.main(["--strict"]) == 1
    assert cli.main([]) == 0  # non-strict reports but exits 0


def test_run_passes_rejects_unknown_names():
    from lightgbm_tpu.analysis.passes import PASSES, run_passes

    with pytest.raises(KeyError, match="nope"):
        run_passes(["nope"])
    assert set(PASSES) == {"lint", "concurrency", "jaxpr", "cost",
                           "scale"}


# ------------------------------------------------------ real entries
def test_serving_entry_cost_audit_green(cost_audit):
    """One real lower+compile through the fixture (serving_forest is
    the cheap entry, ~1 s); the full five-entry sweep is the slow CLI
    test + test_all_entries_green below."""
    results = cost_audit(names=["serving_forest"])
    assert [r.name for r in results] == ["serving_forest"]


@pytest.mark.slow
def test_all_entries_cost_audit_green(cost_audit):
    cost_audit()


def test_unknown_entry_name_raises():
    from lightgbm_tpu.analysis.cost_audit import run_cost_audits

    with pytest.raises(KeyError, match="typo_entry"):
        run_cost_audits(names=["typo_entry"])
