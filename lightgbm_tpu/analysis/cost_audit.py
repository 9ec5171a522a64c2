"""XLA cost/memory auditor + collective wire-bytes accounting.

The jaxpr auditor (jaxpr_audit.py) proves STRUCTURAL contracts — which
primitives appear and with what dtypes. This pass goes one layer lower
and makes the *performance* contract machine-checkable: it
lowers-and-compiles the same hot entry points (jaxpr_audit.ENTRIES) on
the CPU backend and checks the compiled executable's
``cost_analysis()`` / ``memory_analysis()`` against checked-in budgets
(``cost_budget.json``):

- **flops** and **bytes accessed** — a fusion break or an
  accidentally-materialized intermediate shows up here long before a
  chip benchmark can (the auditor runs anywhere);
- **peak temp / output allocation** — the HBM-blowup guard: a new
  buffer the size of the bin matrix fails the budget instead of OOMing
  a chip three PRs later;
- **collective wire bytes** — for every ``psum`` / ``reduce_scatter``
  (``psum_scatter``) / ``all_gather`` / ... equation in an entry's
  jaxpr, payload bytes = prod(shape) x dtype.itemsize per operand,
  summed and asserted against a per-entry budget. Wire budgets are
  EXACT (no headroom): when ROADMAP 3a flips the quant histogram wire
  to int16 (the reference halves socket bytes the same way,
  include/LightGBM/bin.h:63-81), ``--refresh-budgets`` pins the halved
  number and any regression back to a wider payload fails the gate.

Budget refresh: ``python -m lightgbm_tpu.analysis --refresh-budgets``
rewrites cost_budget.json from current compiles (+25% headroom on the
cost metrics, exact wire bytes) and prints an old->new diff for
review. A missing budget is a FAILURE, not a skip — same posture as
jaxpr_audit.within_budget.

CPU-backend caveats: cost numbers are CPU-lowering numbers — useful as
a *regression ratchet*, not as TPU-cycle predictions. Entries that
contain pallas TPU kernels (``pallas_interpret=True`` in the entry
table) are traced under the pallas interpreter so XLA:CPU can compile
them; their budgets describe the interpreted lowering. Wire bytes are
backend-independent (read off the jaxpr, per-shard shapes).
"""

from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

from .jaxpr_audit import (
    AuditResult,
    Contract,
    ENTRIES,
    build_entry,
    iter_eqns,
)

_BUDGET_PATH = Path(__file__).with_name("cost_budget.json")
# compiled-cost metrics get this headroom on refresh (XLA lowering
# drifts a little across versions); wire bytes are pinned EXACT
_BUDGET_HEADROOM = 1.25
# budgeted keys read from cost_analysis()/memory_analysis()
_COST_KEYS = ("flops", "bytes_accessed", "temp_bytes", "output_bytes")

# cross-device collectives whose operand payload crosses ICI/DCN.
# lax.psum_scatter lowers to the `reduce_scatter` primitive.
_COLLECTIVE_PRIMS = {
    "psum", "pmax", "pmin", "reduce_scatter", "all_gather",
    "all_to_all", "ppermute", "pbroadcast",
}


class WireRecord(NamedTuple):
    prim: str
    shape: Tuple[int, ...]
    dtype: str
    nbytes: int


class CostSummary(NamedTuple):
    flops: int
    bytes_accessed: int
    temp_bytes: int
    output_bytes: int
    argument_bytes: int
    wire: Tuple[WireRecord, ...]

    @property
    def wire_bytes(self) -> int:
        return sum(w.nbytes for w in self.wire)

    def metric(self, key: str) -> int:
        return self.wire_bytes if key == "wire_bytes" else getattr(self, key)


# ---------------------------------------------------------------- wire
def _aval_bytes(aval) -> Optional[int]:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return None
    return int(math.prod(shape)) * int(dtype.itemsize)


def collect_wire(closed) -> Tuple[WireRecord, ...]:
    """Every collective equation in a ClosedJaxpr (via the shared
    jaxpr_audit.iter_eqns flattening, so sub-jaxpr discovery matches
    the structural audit exactly) with its payload bytes. Shapes inside
    shard_map bodies are PER-SHARD, so the account is per-device
    ICI/DCN bytes — the quantity the wire budget bounds."""
    out: List[WireRecord] = []
    for eqn in iter_eqns(closed):
        if eqn.primitive.name not in _COLLECTIVE_PRIMS:
            continue
        for v in eqn.invars:
            aval = getattr(v, "aval", None)
            nb = _aval_bytes(aval) if aval is not None else None
            if nb is not None:
                out.append(WireRecord(
                    eqn.primitive.name,
                    tuple(int(d) for d in aval.shape),
                    str(aval.dtype), nb,
                ))
    return tuple(out)


# ------------------------------------------------------------- compile
def compile_entry(name: str) -> CostSummary:
    """Lower-and-compile one entry on the current (CPU) backend and
    read its compiled cost/memory analysis + jaxpr wire account. The
    trace comes from jaxpr_audit.build_entry's memo (pallas entries
    under the interpreter so XLA:CPU can compile them), so a strict
    run traces each entry once across both passes."""
    import jax
    from jax.extend.core import jaxpr_as_fun

    closed = build_entry(name, ENTRIES[name].pallas_interpret)
    fn = jax.jit(jaxpr_as_fun(closed))
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in closed.in_avals]
    compiled = fn.lower(*args).compile()
    props = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    return CostSummary(
        flops=int(math.ceil(props.get("flops", 0.0))),
        bytes_accessed=int(math.ceil(props.get("bytes accessed", 0.0))),
        temp_bytes=int(getattr(ma, "temp_size_in_bytes", 0)),
        output_bytes=int(getattr(ma, "output_size_in_bytes", 0)),
        argument_bytes=int(getattr(ma, "argument_size_in_bytes", 0)),
        wire=collect_wire(closed),
    )


# ------------------------------------------------------------ contracts
def _fmt_bytes(n: int) -> str:
    return f"{n} B" if n < 4096 else f"{n} B ({n / 2**20:.2f} MiB)"


def audit_cost(summary: CostSummary, budget: Optional[Dict[str, Any]],
               name: str = "adhoc",
               wire_dtype: Optional[str] = None) -> AuditResult:
    """Check one entry's CostSummary against its checked-in budget
    (tests drive this directly with synthetic budgets, red-to-green)."""
    contracts: List[Contract] = []
    if budget is None:
        contracts.append(Contract(
            "cost_budget", False,
            "no checked-in cost budget — run "
            "`python -m lightgbm_tpu.analysis --refresh-budgets`",
        ))
    else:
        for key in _COST_KEYS:
            cap = budget.get(key)
            got = summary.metric(key)
            if cap is None:
                contracts.append(Contract(
                    key, False,
                    f"{got} but no {key!r} budget — run --refresh-budgets",
                ))
            else:
                contracts.append(Contract(
                    key, got <= int(cap),
                    f"{got} <= budget {cap}" if got <= int(cap)
                    else f"{got} EXCEEDS budget {cap} (fusion break / "
                    "materialized intermediate / allocation blowup?)",
                ))
        cap = budget.get("wire_bytes")
        got = summary.wire_bytes
        breakdown = ", ".join(
            f"{w.prim}[{w.dtype}{list(w.shape)}]={w.nbytes}B"
            for w in summary.wire
        ) or "no collectives"
        if cap is None:
            contracts.append(Contract(
                "wire_bytes", False,
                f"{got} wire bytes but no budget — run --refresh-budgets",
            ))
        else:
            contracts.append(Contract(
                "wire_bytes", got <= int(cap),
                (f"{_fmt_bytes(got)} <= budget {cap} ({breakdown})"
                 if got <= int(cap)
                 else f"{_fmt_bytes(got)} EXCEEDS wire budget {cap} — "
                 f"collective payload widened? ({breakdown})"),
            ))
    if wire_dtype is not None:
        # the dtype half of the wire contract rides here too so a
        # same-bytes dtype swap (int32 -> f32 at half the rows) cannot
        # sneak past the byte count
        bad = sorted({
            w.dtype for w in summary.wire
            if w.prim == "reduce_scatter" and w.dtype != wire_dtype
        })
        contracts.append(Contract(
            f"wire_{wire_dtype}", not bad,
            f"reduce_scatter payloads all {wire_dtype}" if not bad
            else f"reduce_scatter payload dtype(s) {bad} != {wire_dtype}",
        ))
    return AuditResult(
        name, all(c.ok for c in contracts), contracts, 0,
    )


# cross-entry DROP contracts (ISSUE 12 satellite): entry -> baseline
# whose MEASURED bytes_accessed it must strictly undercut. The
# headroomed per-entry budget only stops regressions; this pins the
# claimed improvement itself — the int-packed default path (3 integer
# channels) must access fewer bytes than the 5-channel bf16x2 path it
# replaces, or the perf story is fiction.
# The fused pair carries the structural proof: the interpreted kernel
# lowering accumulates nat_ch channel rows, so 3 vs 5 channels is a
# guaranteed gap. The serial pair is NOT pinned — the CPU einsum
# fallback collapses bf16x2 to 3 channels before contracting, leaving
# only a sliver of difference there (the rounds_serial_packed entry
# still budget-ratchets on its own).
_DROP_PAIRS: Dict[str, str] = {
    "hist_round_fused": "hist_round_fused_bf16",
    # the routing-only variant of the same kernel must stay a fraction
    # of the fused pass: it exists to drop the histogram's bytes
    "hist_round_fused_route": "hist_round_fused",
}

# same contract shape on the WIRE account (ISSUE 14 satellite): the
# voting-parallel entry's collective payload (votes + elected-columns
# psum) must land strictly below the all-feature reduce-scatter wire of
# the plain quantized data-parallel entry — the whole point of the
# election is moving less histogram across the mesh, and both sides are
# measured this run off the same jaxpr walker.
_WIRE_DROP_PAIRS: Dict[str, str] = {"rounds_voting": "rounds_quant_rs"}


def audit_bytes_drop(name: str, got: int, base: str, ref: int,
                     metric: str = "bytes") -> Contract:
    """`name` must show strictly fewer `metric` (compiled bytes
    accessed, or collective wire bytes) than `base` (both measured THIS
    run — no stale budget on either side)."""
    ok = got < ref
    return Contract(
        f"{metric}_drop_vs_{base}", ok,
        (f"{_fmt_bytes(got)} < {base}'s {_fmt_bytes(ref)} "
         f"({got / ref:.0%})" if ok else
         f"{_fmt_bytes(got)} does NOT undercut {base}'s "
         f"{_fmt_bytes(ref)} — the narrow path stopped being "
         "narrower"),
    )


# -------------------------------------------------------------- runner
def load_budgets() -> Dict[str, Dict[str, int]]:
    if _BUDGET_PATH.exists():
        return json.loads(_BUDGET_PATH.read_text())
    return {}


def _budget_from(summary: CostSummary) -> Dict[str, int]:
    out = {
        key: int(math.ceil(summary.metric(key) * _BUDGET_HEADROOM))
        for key in _COST_KEYS
    }
    out["wire_bytes"] = summary.wire_bytes  # exact: the halving proof
    return out


def run_cost_audits(names: Optional[Sequence[str]] = None
                    ) -> List[AuditResult]:
    if names is not None:
        unknown = set(names) - set(ENTRIES)
        if unknown:
            raise KeyError(
                f"unknown cost-audit entr"
                f"{'y' if len(unknown) == 1 else 'ies'} {sorted(unknown)}; "
                f"known: {sorted(ENTRIES)}"
            )
    budgets = load_budgets()
    out: List[AuditResult] = []
    summaries: Dict[str, CostSummary] = {}
    audited = [n for n in ENTRIES if names is None or n in names]
    for name in audited:
        summaries[name] = compile_entry(name)
    for name in audited:
        res = audit_cost(
            summaries[name], budgets.get(name), name,
            wire_dtype=ENTRIES[name].wire_dtype,
        )
        for pairs, metric in ((_DROP_PAIRS, "bytes"),
                              (_WIRE_DROP_PAIRS, "wire_bytes")):
            base = pairs.get(name)
            if base is None:
                continue
            # the baseline is measured this run even when the caller
            # filtered it out — a drop contract against a stale number
            # proves nothing
            if base not in summaries:
                summaries[base] = compile_entry(base)
            key = "bytes_accessed" if metric == "bytes" else "wire_bytes"
            c = audit_bytes_drop(
                name, summaries[name].metric(key),
                base, summaries[base].metric(key), metric=metric,
            )
            res = AuditResult(
                name, res.ok and c.ok, res.contracts + [c], 0,
            )
        out.append(res)
    return out


def refresh_budgets(names: Optional[Sequence[str]] = None
                    ) -> Tuple[Dict[str, Dict[str, int]],
                               Dict[str, Dict[str, int]]]:
    """Rewrite cost_budget.json from current compiles; returns
    (old, new) for diff display. Refreshing a subset keeps the other
    entries' budgets untouched."""
    old = load_budgets()
    new = {k: dict(v) for k, v in old.items()}
    for name in ENTRIES:
        if names is not None and name not in names:
            continue
        new[name] = _budget_from(compile_entry(name))
    # drop budgets for entries that no longer exist (orphan keys would
    # fail the budget/entry consistency meta-test)
    new = {k: v for k, v in new.items() if k in ENTRIES}
    _BUDGET_PATH.write_text(
        json.dumps(new, indent=2, sort_keys=True) + "\n"
    )
    return old, new


def format_budget_diff(old: Dict[str, Dict[str, int]],
                       new: Dict[str, Dict[str, int]]) -> str:
    """Old->new per-metric diff for --refresh-budgets review."""
    lines: List[str] = []
    for name in sorted(set(old) | set(new)):
        o, n = old.get(name), new.get(name)
        if o == n:
            lines.append(f"  {name}: unchanged")
            continue
        if n is None:
            lines.append(f"- {name}: removed (entry no longer exists)")
            continue
        for key in list(_COST_KEYS) + ["wire_bytes"]:
            ov = (o or {}).get(key)
            nv = n.get(key)
            if ov == nv:
                continue
            delta = ""
            if isinstance(ov, int) and ov:
                delta = f" ({(nv - ov) / ov:+.1%})"
            lines.append(f"~ {name}.{key}: {ov} -> {nv}{delta}")
    return "\n".join(lines) if lines else "  (no budgets)"
