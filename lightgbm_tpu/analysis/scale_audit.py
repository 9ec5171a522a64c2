"""SPMD scaling-contract auditor (Pass 7): the D-ladder gate.

Every other jaxpr/cost contract is pinned at ONE mesh shape (the
forced 8-device host platform), so nothing in `--strict` could detect
a collective whose count, kind, or payload grows with device count —
exactly the failure mode that would sink pod-scale training (ROADMAP
3) and the 2D rows x features mesh (ROADMAP 5). This pass re-traces
every mesh-bearing entry in `jaxpr_audit.ENTRIES` at a device ladder
D in {1, 2, 4, 8} (sub-meshes of the forced 8-device CPU platform,
`jaxpr_audit._mesh(n)`) and proves scaling BEHAVIOR, not just
single-point budgets:

- **collective census** — the multiset of collective primitives
  (psum / reduce_scatter / all_gather / ...) must be D-invariant in
  kind and count above the entry's floor, and an all_gather may never
  appear where the entry declares none;
- **wire scaling law** — per-device collective payload bytes at each
  D are pinned EXACT (cost_audit's byte extraction) and checked
  against a declared law: `const` (payload independent of D), `1/D`
  (per-shard reduce-scatter bytes shrink exactly with the mesh),
  `elected` (flat AND strictly under the all-feature baseline wire —
  the PR 14 voting election), `bounded` (non-increasing in D);
- **eqn-count D-invariance** — the `chunk_c_invariance` pattern
  applied to mesh size: compiled program size cannot scale with the
  pod (small declared tolerance for shape-specialized simplification
  at the degenerate 1-shard rung);
- **sharding-spec verification** — a `match_partition_rules`-style
  declaration table checked against the actual shard_map
  in_names/out_names, so a per-row array silently falling back to
  full replication fails the gate instead of silently 8x-ing memory.

Pins live in `scale_budget.json` (exact, per entry per rung);
`python -m lightgbm_tpu.analysis --refresh-budgets` rewrites it and
prints an old->new diff. Tier-1 tests run the tiny D in {1, 2} ladder
in-process; `--strict` / tools/analysis.sh run the full ladder.
Traces are memoized per (entry, D) through `build_entry`, so the D=8
rung shares the trace the jaxpr/cost passes already paid for.
"""

from __future__ import annotations

import json
import re
from collections import Counter
from pathlib import Path
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

from .cost_audit import _aval_bytes, collect_wire
from .jaxpr_audit import (
    AuditResult,
    Contract,
    build_entry,
    iter_eqns,
    mesh_entry_names,
)

_BUDGET_PATH = Path(__file__).with_name("scale_budget.json")

# the full --strict ladder and the tiny tier-1 subset (the suite
# already runs ~770-860 s of its 870 s budget; D in {1, 2} catches a
# broken degenerate rung + the first real mesh while the 4/8 rungs
# ride tools/analysis.sh)
LADDER: Tuple[int, ...] = (1, 2, 4, 8)
TIER1_LADDER: Tuple[int, ...] = (1, 2)

_BUDGET_KEYS = ("census", "send_bytes", "rs_shard_bytes", "eqn_count")


class ShardRule(NamedTuple):
    """One row of a match_partition_rules-style table (SNIPPETS [3]):
    first rule whose regex fully matches a canonical array name wins;
    its expected spec must equal the rendered actual sharding."""
    label: str
    pattern: str   # fullmatch regex over "in/<i>/<dtype>[dims]" names
    expected: str  # "P(data)", "P(None, data)", ... or "replicated"


class ScaleSpec(NamedTuple):
    """Declared scaling contract for one mesh-bearing entry."""
    law: str                     # const | 1/D | elected | bounded
    floor: int = 1               # smallest D the law/census cover (rs
    #                              entries degrade to psum at D=1 by
    #                              design: use_rs needs axis_size > 1)
    allows_all_gather: bool = False
    baseline: Optional[str] = None   # elected law: entry to undercut
    eqn_tol: int = 0             # max-min eqn spread over D >= floor
    axis: str = "data"
    rules: Tuple[ShardRule, ...] = ()
    # symbol -> per-device rows; a global dim equal to rows*D renders
    # as the symbol so one rule covers every rung
    symbols: Dict[str, int] = {}


class ScaleSummary(NamedTuple):
    """Everything the contracts read off one (entry, D) trace.
    Tests fabricate these directly to drive the red paths."""
    census: Dict[str, int]       # collective prim -> count
    send_bytes: int              # per-device collective payload (sum
    #                              of collective operand bytes —
    #                              cost_audit's wire account)
    rs_shard_bytes: int          # reduce_scatter OUTPUT bytes: the
    #                              per-shard histogram slice
    eqn_count: int
    shardings: Tuple[Tuple[str, str], ...]  # (canonical name, spec)


# ------------------------------------------------------- declarations
# Shared rules for the data-parallel rounds entries: bins (F, N) and
# every per-row array ride the 'data' axis; the per-row leaf output
# must STAY sharded (a replicated row_leaf is the 8x-memory fallback
# this table exists to catch); everything else — split records, leaf
# values, scalar params — is replicated.
_ROUNDS_RULES: Tuple[ShardRule, ...] = (
    ShardRule("bins_rows_sharded", r"in/0/int32\[8,N\]", "P(None, data)"),
    ShardRule("per_row_grad_hess_mask", r"in/[5-7]/float32\[N\]", "P(data)"),
    ShardRule("row_leaf_stays_sharded", r"out/16/int32\[N\]", "P(data)"),
    ShardRule("records_and_params_replicated", r"(in|out)/.*", "replicated"),
)

# Feature-parallel flips the axes: per-feature metadata and the bin
# matrix shard over 'feature', rows are replicated BY DESIGN
# (parallel_tree_learner.h:26 — every rank holds all rows, only split
# records cross the wire), and outputs are replicated (pmean'd tree).
_FP_RULES: Tuple[ShardRule, ...] = (
    ShardRule("bins_features_sharded", r"in/0/int32\[16,512\]",
              "P(feature, None)"),
    ShardRule("per_feature_meta", r"in/[12348]/\w+\[16\]", "P(feature)"),
    ShardRule("rows_replicated_by_design",
              r"in/(5|6|7|24)/float32\[512\]", "replicated"),
    ShardRule("tree_outputs_replicated", r"(in|out)/.*", "replicated"),
)

# law notes, all measured on the 8-device host platform:
# - rs entries: send const for D >= 2 (each device ships its full
#   owned-block histogram once), reduce_scatter out exactly prop. 1/D;
#   floor 2 because use_rs needs axis_size > 1 (D=1 falls back to the
#   psum path — still pinned exactly via the budget, just outside the
#   law); eqn_tol covers XLA shape-specialized simplification wobble.
# - overflow: rs_exact_ok disables the wire at EVERY D — f32 psum
#   fallback, flat.
# - voting: elected int16 wire flat at every D and strictly under the
#   all-feature rounds_quant_rs wire (the whole point of the
#   election).
# - feature_parallel: record-only wire, non-increasing in D (a small
#   affine 1/D term from the per-rank bookkeeping).
SCALE_ENTRIES: Dict[str, ScaleSpec] = {
    "rounds_quant_rs": ScaleSpec(
        law="1/D", floor=2, allows_all_gather=True, eqn_tol=32,
        symbols={"N": 128}, rules=_ROUNDS_RULES,
    ),
    "rounds_quant_rs_int32": ScaleSpec(
        law="1/D", floor=2, allows_all_gather=True, eqn_tol=32,
        symbols={"N": 2048}, rules=_ROUNDS_RULES,
    ),
    "rounds_quant_rs_overflow": ScaleSpec(
        law="const", symbols={"N": 131072}, rules=_ROUNDS_RULES,
    ),
    "rounds_voting": ScaleSpec(
        law="elected", baseline="rounds_quant_rs",
        symbols={"N": 128}, rules=_ROUNDS_RULES,
    ),
    "feature_parallel": ScaleSpec(
        law="bounded", allows_all_gather=True, axis="feature",
        rules=_FP_RULES,
    ),
}


# --------------------------------------------------------- summarizer
def _render_spec(pspec, ndim: int) -> str:
    """shard_map PartitionSpec -> "P(None, data)" style string;
    an array with NO bound axes renders as "replicated" (rank-blind:
    that is the property the rules declare)."""
    axes = [
        () if ax is None else ax if isinstance(ax, tuple) else (ax,)
        for ax in tuple(pspec) + (None,) * (ndim - len(pspec))
    ]
    if not any(axes):
        return "replicated"
    return "P(" + ", ".join("+".join(ax) if ax else "None"
                            for ax in axes) + ")"


def _canonical_dims(shape, symbols: Dict[str, int], n_devices: int) -> str:
    out = []
    for dim in shape:
        sym = next((s for s, rows in symbols.items()
                    if int(dim) == rows * n_devices), None)
        out.append(sym if sym is not None else str(int(dim)))
    return ",".join(out)


def extract_shardings(closed, spec: ScaleSpec,
                      n_devices: int) -> Tuple[Tuple[str, str], ...]:
    """(canonical name, rendered spec) for every in/out of every
    top-level shard_map eqn. Canonical names are
    "in/<i>/<dtype>[dims]" with declared symbols substituted
    (N = rows x D), so one rule table covers the whole ladder."""
    items: List[Tuple[str, str]] = []
    smaps = [e for e in closed.jaxpr.eqns
             if e.primitive.name == "shard_map"]
    for k, eqn in enumerate(smaps):
        prefix = "" if len(smaps) == 1 else f"smap{k}/"
        for kind, vs, nm in (("in", eqn.invars, eqn.params["in_specs"]),
                             ("out", eqn.outvars, eqn.params["out_specs"])):
            for i, (v, pspec) in enumerate(zip(vs, nm)):
                aval = getattr(v, "aval", None)
                if aval is None or not hasattr(aval, "shape"):
                    continue
                dims = _canonical_dims(aval.shape, spec.symbols, n_devices)
                name = f"{prefix}{kind}/{i}/{aval.dtype}[{dims}]"
                items.append((name, _render_spec(pspec, len(aval.shape))))
    return tuple(items)


def summarize_scale(closed, spec: ScaleSpec,
                    n_devices: int) -> ScaleSummary:
    """One (entry, D) trace -> the numbers the contracts read."""
    from .cost_audit import _COLLECTIVE_PRIMS

    census: Counter = Counter()
    rs_out = 0
    eqns = 0
    for eqn in iter_eqns(closed):
        eqns += 1
        p = eqn.primitive.name
        if p in _COLLECTIVE_PRIMS:
            census[p] += 1
        if p == "reduce_scatter":
            for v in eqn.outvars:
                nb = _aval_bytes(getattr(v, "aval", None))
                if nb is not None:
                    rs_out += nb
    return ScaleSummary(
        census=dict(census),
        send_bytes=sum(w.nbytes for w in collect_wire(closed)),
        rs_shard_bytes=rs_out,
        eqn_count=eqns,
        shardings=extract_shardings(closed, spec, n_devices),
    )


# ----------------------------------------------------------- contracts
def _fmt_census(c: Dict[str, int]) -> str:
    return "{" + ", ".join(f"{k}:{v}" for k, v in sorted(c.items())) + "}"


def _check_census(spec: ScaleSpec,
                  summaries: Dict[int, ScaleSummary]) -> List[Contract]:
    out: List[Contract] = []
    rungs = sorted(d for d in summaries if d >= spec.floor)
    censuses = {d: summaries[d].census for d in rungs}
    ref = censuses[rungs[0]]
    bad = [d for d in rungs if censuses[d] != ref]
    out.append(Contract(
        "census_D_invariant", not bad,
        (f"D>={spec.floor}: {_fmt_census(ref)} at every rung "
         f"{rungs}" if not bad else
         f"collective census varies with D: " + "; ".join(
             f"D={d}: {_fmt_census(censuses[d])}" for d in rungs)
         + " — a per-device collective crept into a mesh-sized loop?"),
    ))
    if not spec.allows_all_gather:
        offenders = {d: s.census.get("all_gather", 0)
                     for d, s in sorted(summaries.items())
                     if s.census.get("all_gather", 0)}
        out.append(Contract(
            "no_undeclared_all_gather", not offenders,
            "entry declares no all_gather; none found" if not offenders
            else f"undeclared all_gather eqn(s): {offenders} — "
            "gathering replicates a sharded array onto every device",
        ))
    return out


def _check_law(name: str, spec: ScaleSpec,
               summaries: Dict[int, ScaleSummary],
               baseline: Optional[Dict[int, ScaleSummary]],
               baseline_floor: int) -> List[Contract]:
    out: List[Contract] = []
    rungs = sorted(d for d in summaries if d >= spec.floor)
    send = {d: summaries[d].send_bytes for d in rungs}
    label = f"wire_law_{spec.law}"
    if spec.law in ("const", "elected"):
        flat = len(set(send.values())) == 1
        out.append(Contract(
            label, flat,
            f"per-device send bytes flat at {send[rungs[0]]} B over "
            f"D={rungs}" if flat else
            f"send bytes vary with D: {send} — payload no longer "
            "independent of mesh size",
        ))
    elif spec.law == "1/D":
        shard = {d: summaries[d].rs_shard_bytes for d in rungs}
        prods = {d: shard[d] * d for d in rungs}
        ok = (len(set(prods.values())) == 1 and all(shard.values())
              and len(set(send.values())) == 1)
        out.append(Contract(
            label, ok,
            (f"reduce_scatter shard bytes exactly prop. 1/D "
             f"({shard}, DxB={prods[rungs[0]]} const) and send flat "
             f"at {send[rungs[0]]} B" if ok else
             f"1/D law broken: shard bytes {shard} (DxB {prods}), "
             f"send {send} — per-shard histogram slice no longer "
             "shrinks with the mesh"),
        ))
    elif spec.law == "bounded":
        pairs = list(zip(rungs, rungs[1:]))
        ok = all(send[a] >= send[b] for a, b in pairs)
        out.append(Contract(
            label, ok,
            f"send bytes non-increasing in D: {send}" if ok else
            f"send bytes GROW with D: {send} — wire scales with the "
            "pod",
        ))
    else:
        out.append(Contract(label, False,
                            f"unknown scaling law {spec.law!r}"))
    if spec.law == "elected":
        if baseline is None:
            out.append(Contract(
                "elected_undercuts_baseline", False,
                f"baseline {spec.baseline!r} not measured this run",
            ))
        else:
            common = sorted(d for d in summaries
                            if d in baseline
                            and d >= max(spec.floor, baseline_floor))
            worse = {d: (summaries[d].send_bytes,
                         baseline[d].send_bytes)
                     for d in common
                     if summaries[d].send_bytes
                     >= baseline[d].send_bytes}
            out.append(Contract(
                "elected_undercuts_baseline", not worse and bool(common),
                (f"elected wire under {spec.baseline}'s all-feature "
                 f"wire at every common rung {common} "
                 f"({summaries[common[0]].send_bytes} < "
                 f"{baseline[common[0]].send_bytes} B)"
                 if common and not worse else
                 f"elected wire does NOT undercut {spec.baseline}: "
                 f"{worse or 'no common rungs'} — the election stopped "
                 "paying for itself"),
            ))
    return out


def _check_eqns(spec: ScaleSpec,
                summaries: Dict[int, ScaleSummary]) -> Contract:
    rungs = sorted(d for d in summaries if d >= spec.floor)
    counts = {d: summaries[d].eqn_count for d in rungs}
    spread = max(counts.values()) - min(counts.values())
    ok = spread <= spec.eqn_tol
    return Contract(
        "eqns_D_invariant", ok,
        f"eqn spread {spread} <= tol {spec.eqn_tol} over D={rungs} "
        f"({counts})" if ok else
        f"eqn count scales with D: {counts} (spread {spread} > tol "
        f"{spec.eqn_tol}) — program size grows with the pod",
    )


def _check_shardings(spec: ScaleSpec,
                     summaries: Dict[int, ScaleSummary]) -> Contract:
    """First-match-wins over the declared rule table, every array must
    match a rule, every rule must match at least one array (a stale
    rule proves nothing), and the matched spec must equal the
    declaration."""
    problems: List[str] = []
    used = set()
    for d, s in sorted(summaries.items()):
        for arr_name, got in s.shardings:
            rule = next((r for r in spec.rules
                         if re.fullmatch(r.pattern, arr_name)), None)
            if rule is None:
                problems.append(
                    f"D={d}: {arr_name} matches no sharding rule")
                continue
            used.add(rule.label)
            if got != rule.expected:
                problems.append(
                    f"D={d}: {arr_name} is {got}, rule "
                    f"'{rule.label}' declares {rule.expected}")
    stale = [r.label for r in spec.rules if r.label not in used]
    if spec.rules and summaries:
        problems += [f"rule '{lbl}' matched nothing (stale table?)"
                     for lbl in stale]
    ok = not problems
    return Contract(
        "sharding_rules", ok,
        f"{len(spec.rules)} rules verified against "
        f"{len(next(iter(summaries.values())).shardings)} arrays at "
        f"every rung" if ok else
        "; ".join(problems[:6]) + ("" if len(problems) <= 6 else
                                   f" (+{len(problems) - 6} more)"),
    )


def _check_budget(pinned: Optional[Dict[str, Any]],
                  summaries: Dict[int, ScaleSummary]) -> Contract:
    if pinned is None:
        return Contract(
            "scale_budget", False,
            "no checked-in scale budget — run "
            "`python -m lightgbm_tpu.analysis --refresh-budgets`",
        )
    problems: List[str] = []
    for d, s in sorted(summaries.items()):
        pin = pinned.get(str(d))
        if pin is None:
            problems.append(f"no pin for D={d} — run --refresh-budgets")
            continue
        got = {"census": s.census, "send_bytes": s.send_bytes,
               "rs_shard_bytes": s.rs_shard_bytes,
               "eqn_count": s.eqn_count}
        for key in _BUDGET_KEYS:
            if got[key] != pin.get(key):
                problems.append(
                    f"D={d} {key}: {got[key]} != pinned "
                    f"{pin.get(key)}")
    ok = not problems
    return Contract(
        "scale_budget", ok,
        f"census/send/shard/eqns EXACT at D={sorted(summaries)}"
        if ok else "; ".join(problems[:6])
        + ("" if len(problems) <= 6 else f" (+{len(problems) - 6} more)"),
    )


def audit_scale(name: str, spec: ScaleSpec,
                summaries: Dict[int, ScaleSummary],
                pinned: Optional[Dict[str, Any]],
                baseline: Optional[Dict[int, ScaleSummary]] = None,
                ) -> AuditResult:
    """Pure contract evaluation over pre-computed per-rung summaries —
    tests drive this directly with synthetic summaries (red paths:
    census growth, widened payload, replicated per-row array)."""
    baseline_floor = (SCALE_ENTRIES[spec.baseline].floor
                      if spec.baseline in SCALE_ENTRIES else 1)
    contracts = (
        _check_census(spec, summaries)
        + _check_law(name, spec, summaries, baseline, baseline_floor)
        + [_check_eqns(spec, summaries),
           _check_shardings(spec, summaries),
           _check_budget(pinned, summaries)]
    )
    return AuditResult(name, all(c.ok for c in contracts), contracts, 0)


# -------------------------------------------------------------- runner
def load_budgets() -> Dict[str, Dict[str, Any]]:
    if _BUDGET_PATH.exists():
        return json.loads(_BUDGET_PATH.read_text())
    return {}


def _pins_from(summaries: Dict[int, ScaleSummary]) -> Dict[str, Any]:
    return {
        str(d): {
            "census": {k: v for k, v in sorted(s.census.items())},
            "send_bytes": s.send_bytes,
            "rs_shard_bytes": s.rs_shard_bytes,
            "eqn_count": s.eqn_count,
        }
        for d, s in sorted(summaries.items())
    }


def _measure(name: str, ladder: Sequence[int]) -> Dict[int, ScaleSummary]:
    spec = SCALE_ENTRIES[name]
    return {
        d: summarize_scale(build_entry(name, n_devices=d), spec, d)
        for d in ladder
    }


def run_scale_audits(names: Optional[Sequence[str]] = None,
                     ladder: Sequence[int] = LADDER,
                     update_budget: bool = False) -> List[AuditResult]:
    """Audit the named mesh entries (default: all of them) over the
    rung ladder. update_budget rewrites the audited entries' pins for
    the measured rungs (refresh_scale_budget wraps this for the CLI
    diff)."""
    mesh_names = mesh_entry_names()
    if names is not None:
        unknown = set(names) - set(SCALE_ENTRIES)
        if unknown:
            raise KeyError(
                f"unknown scale-audit entr"
                f"{'y' if len(unknown) == 1 else 'ies'} {sorted(unknown)}; "
                f"known: {sorted(SCALE_ENTRIES)}"
            )
    audited = [n for n in SCALE_ENTRIES if names is None or n in names]
    out: List[AuditResult] = []
    # registry consistency: a new mesh entry without a declared
    # ScaleSpec (or a spec for a dead entry) must fail loudly, not
    # silently skip the ladder
    if set(SCALE_ENTRIES) != set(mesh_names):
        missing = sorted(set(mesh_names) - set(SCALE_ENTRIES))
        orphan = sorted(set(SCALE_ENTRIES) - set(mesh_names))
        out.append(AuditResult("scale_registry", False, [Contract(
            "specs_cover_mesh_entries", False,
            f"mesh entries without a ScaleSpec: {missing}; specs for "
            f"dead entries: {orphan}",
        )], 0))
    budgets = load_budgets()
    measured: Dict[str, Dict[int, ScaleSummary]] = {}
    for name in audited:
        measured[name] = _measure(name, ladder)
    new_budgets = {k: dict(v) for k, v in budgets.items()}
    if update_budget:
        for name in audited:
            new_budgets[name] = _pins_from(measured[name])
        new_budgets = {k: v for k, v in new_budgets.items()
                       if k in SCALE_ENTRIES}
        _BUDGET_PATH.write_text(
            json.dumps(new_budgets, indent=2, sort_keys=True) + "\n"
        )
    for name in audited:
        spec = SCALE_ENTRIES[name]
        baseline = None
        if spec.baseline is not None:
            if spec.baseline not in measured:
                # measured this run even when filtered out — an
                # undercut contract against a stale number proves
                # nothing (same posture as cost_audit drop pairs)
                measured[spec.baseline] = _measure(spec.baseline, ladder)
            baseline = measured[spec.baseline]
        out.append(audit_scale(
            name, spec, measured[name],
            new_budgets.get(name), baseline,
        ))
    return out


def refresh_scale_budget() -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Rewrite scale_budget.json from current full-ladder traces;
    returns (old, new) for the --refresh-budgets diff."""
    old = load_budgets()
    run_scale_audits(ladder=LADDER, update_budget=True)
    return old, load_budgets()


def format_scale_diff(old: Dict[str, Any], new: Dict[str, Any]) -> str:
    lines: List[str] = []
    for name in sorted(set(old) | set(new)):
        o, n = old.get(name), new.get(name)
        if o == n:
            lines.append(f"  {name}: unchanged")
            continue
        if n is None:
            lines.append(f"- {name}: removed (entry no longer exists)")
            continue
        for d in sorted(set(o or {}) | set(n), key=int):
            op, np_ = (o or {}).get(d), n.get(d)
            if op == np_:
                continue
            for key in _BUDGET_KEYS:
                ov = (op or {}).get(key)
                nv = (np_ or {}).get(key)
                if ov != nv:
                    lines.append(f"~ {name}[D={d}].{key}: {ov} -> {nv}")
    return "\n".join(lines) if lines else "  (no budgets)"
