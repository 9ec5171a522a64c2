"""Jaxpr invariant auditor: machine-checkable contracts on hot paths.

Abstractly traces the fused round kernel, the data-parallel grower and
the quantized reduce-scatter wire (no data, no compile — jaxpr
construction only, a couple of seconds on CPU) and asserts contracts
that every perf/correctness regression so far would have tripped:

- the quantized wire: `reduce_scatter` present, every wire operand
  exactly `QUANT_WIRE_DTYPE` (int16 — the narrowest exact payload,
  histogram.rs_wire_dtype; a second entry pins the int32 step-down
  when the int16 bound trips);
- the overflow gate (ADVICE r5, histogram.rs_exact_ok): past the
  2^31 global / 2^24 per-shard exactness bounds the wire must VANISH
  and the f32 psum fallback take over;
- no host callbacks (`pure_callback`/`io_callback`/...) inside device
  loops — each one is a device->host round trip per iteration;
- no float64 anywhere (dtype widening guard — the package is f32/
  int32 end to end);
- flattened jaxpr size stays under a checked-in budget
  (`jaxpr_budget.json`) — the executable-bloat guard (a 152 MB
  jit_step once shipped because a bin matrix became a constant).

Also hosts the `_OBJ_FOLD_ATTRS` exhaustiveness audit (ADVICE r5
item 3): a static scan proving no objective class stores a device
array outside the fused step's rebind list.

Importing this module imports jax; run on CPU with
`--xla_force_host_platform_device_count=8` (the CLI sets this up).
"""

from __future__ import annotations

import json
import math
from collections import Counter
from pathlib import Path
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence

_BUDGET_PATH = Path(__file__).with_name("jaxpr_budget.json")
# a fresh entry's budget = ceil(current size * this headroom)
_BUDGET_HEADROOM = 1.25

_CALLBACK_PRIMS = {
    "pure_callback", "io_callback", "debug_callback", "callback",
    "host_callback", "outside_call",
}


class JaxprSummary(NamedTuple):
    prim_counts: Dict[str, int]
    eqn_count: int
    dtypes: frozenset
    # operand dtype of every reduce_scatter eqn (the collective wire)
    wire_dtypes: tuple


class Contract(NamedTuple):
    name: str
    ok: bool
    detail: str


class AuditResult(NamedTuple):
    name: str
    ok: bool
    contracts: List[Contract]
    eqn_count: int

    def format(self) -> str:
        head = "PASS" if self.ok else "FAIL"
        size = f" ({self.eqn_count} eqns)" if self.eqn_count else ""
        lines = [f"[{head}] {self.name}{size}"]
        for c in self.contracts:
            mark = "ok " if c.ok else "XX "
            lines.append(f"    {mark}{c.name}: {c.detail}")
        return "\n".join(lines)


def iter_eqns(closed):
    """Every equation of a ClosedJaxpr, recursing into call/
    control-flow/pallas sub-jaxprs discovered through eqn params. The
    ONE flattening walker — summarize() here and cost_audit's wire
    accounting both consume it, so sub-jaxpr discovery cannot drift
    between the structural and the byte-accounting views."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    stack = [closed.jaxpr]
    while stack:
        jaxpr = stack.pop()
        for eqn in jaxpr.eqns:
            yield eqn
            for p in eqn.params.values():
                for sub in (p if isinstance(p, (list, tuple)) else [p]):
                    if isinstance(sub, ClosedJaxpr):
                        stack.append(sub.jaxpr)
                    elif isinstance(sub, Jaxpr):
                        stack.append(sub)


def summarize(closed) -> JaxprSummary:
    """Flatten a ClosedJaxpr into the primitive/dtype statistics the
    contracts read."""
    prims: Counter = Counter()
    dtypes: set = set()
    wire: List[str] = []
    for eqn in iter_eqns(closed):
        prims[eqn.primitive.name] += 1
        for v in list(eqn.invars) + list(eqn.outvars):
            dt = getattr(getattr(v, "aval", None), "dtype", None)
            if dt is not None:
                dtypes.add(str(dt))
        if eqn.primitive.name == "reduce_scatter":
            wire.append(str(eqn.invars[0].aval.dtype))
    return JaxprSummary(
        dict(prims), sum(prims.values()), frozenset(dtypes), tuple(wire)
    )


# ---------------------------------------------------------------- contracts
ContractFn = Callable[[JaxprSummary], Contract]


def has_prim(name: str, why: str = "") -> ContractFn:
    def check(s: JaxprSummary) -> Contract:
        n = s.prim_counts.get(name, 0)
        return Contract(
            f"has_{name}", n > 0,
            f"{n} {name} eqn(s)" + (f" — {why}" if why else ""),
        )
    return check


def lacks_prim(name: str, why: str = "") -> ContractFn:
    def check(s: JaxprSummary) -> Contract:
        n = s.prim_counts.get(name, 0)
        return Contract(
            f"no_{name}", n == 0,
            (f"absent" if n == 0 else f"{n} present")
            + (f" — {why}" if why else ""),
        )
    return check


def wire_dtype(dtype: str) -> ContractFn:
    """Every reduce_scatter operand has exactly this dtype: the
    quantized histogram wire must never widen (f32/f64 would double the
    ICI/DCN payload) NOR silently narrow without the budget flip. The
    expected dtype is `QUANT_WIRE_DTYPE` below — ROADMAP 3a's int16
    wire lands by flipping that one constant and refreshing the
    wire-bytes budget (cost_audit.py)."""
    def check(s: JaxprSummary) -> Contract:
        bad = [d for d in s.wire_dtypes if d != dtype]
        return Contract(
            f"wire_{dtype}", not bad,
            f"wire dtypes {list(s.wire_dtypes)}"
            + (f" — expected {dtype}, got: {bad}" if bad else ""),
        )
    return check


def no_host_callbacks() -> ContractFn:
    def check(s: JaxprSummary) -> Contract:
        found = {
            k: v for k, v in s.prim_counts.items() if k in _CALLBACK_PRIMS
        }
        return Contract(
            "no_host_callbacks", not found,
            "none" if not found else f"host callbacks in trace: {found}",
        )
    return check


def no_f64() -> ContractFn:
    def check(s: JaxprSummary) -> Contract:
        bad = sorted(d for d in s.dtypes if "64" in d and d != "int64")
        return Contract(
            "no_f64", not bad,
            "f32/int32 end to end" if not bad else f"widened dtypes: {bad}",
        )
    return check


def within_budget(budget: Optional[int]) -> ContractFn:
    def check(s: JaxprSummary) -> Contract:
        if budget is None:
            return Contract(
                "eqn_budget", False,
                f"{s.eqn_count} eqns but no checked-in budget — run "
                "`python -m lightgbm_tpu.analysis --update-budget`",
            )
        return Contract(
            "eqn_budget", s.eqn_count <= budget,
            f"{s.eqn_count} eqns <= budget {budget}"
            if s.eqn_count <= budget
            else f"{s.eqn_count} eqns EXCEEDS budget {budget} "
            "(executable bloat — did a constant get baked in, or a "
            "loop unroll?)",
        )
    return check


def audit_jaxpr(closed, contracts: Sequence[ContractFn],
                name: str = "adhoc") -> AuditResult:
    """Run contracts against an already-built ClosedJaxpr (tests use
    this to prove each contract red-to-green on broken fixtures)."""
    s = summarize(closed)
    results = [c(s) for c in contracts]
    return AuditResult(
        name, all(c.ok for c in results), results, s.eqn_count
    )


# ---------------------------------------------------------------- entries
# the forced host platform every audit mesh is carved from (the ONE
# place the XLA_FLAGS bootstrap size is declared — __main__ and
# tests/conftest.py both force this count before jax initializes)
HOST_DEVICE_COUNT = 8


def _mesh(n: int = HOST_DEVICE_COUNT, axis_name: str = "data"):
    """1-D audit mesh over the first `n` of the forced 8 host CPU
    devices — sub-meshes are how scale_audit re-traces every
    mesh-bearing entry at the D ∈ {1, 2, 4, 8} ladder without touching
    the backend bootstrap. Loud error below n devices: a silently
    smaller mesh would re-pin every scaling budget at the wrong D."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"jaxpr audit needs a {n}-device mesh but the backend has "
            f"{len(devs)}; run under XLA_FLAGS="
            f"--xla_force_host_platform_device_count={HOST_DEVICE_COUNT} "
            "(python -m lightgbm_tpu.analysis and tests/conftest.py "
            "both set this up)"
        )
    return Mesh(np.asarray(devs[:n]), (axis_name,))


def _trace_rounds_dp(quant: bool, levels: int, local_rows: int,
                     voting_k: int = 0,
                     n_devices: int = HOST_DEVICE_COUNT):
    """Abstract shard_map trace of the rounds grower over the data
    mesh — the exact wiring DataParallelGrower builds (shapes only; no
    arrays exist, so `local_rows` can model pod scale for free).
    voting_k>0 turns on the per-round GlobalVoting election
    (tree_learner=voting): only the elected columns cross the mesh.
    `n_devices` carves a sub-mesh of the forced host platform; LOCAL
    rows are held fixed so global rows scale with the mesh — the
    weak-scaling axis the scale auditor's wire laws are written
    against."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..config import Config
    from ..learner.grower import GrowerSpec, make_split_params
    from ..learner.rounds import grow_tree_rounds
    from ..parallel.data_parallel import _tree_arrays_structure

    mesh = _mesh(n_devices)
    n = int(mesh.devices.size)
    L, B, G = 31, 64, 8
    N = local_rows * n
    spec = GrowerSpec(
        num_leaves=L, num_bins=B, max_depth=-1, axis_name="data",
        axis_size=n, rounds_slots=8, quant=quant,
        quant_levels=levels if quant else 0, has_cat=False,
        voting_k=voting_k,
    )
    params = make_split_params(Config({}))
    mk = lambda s, d: jax.ShapeDtypeStruct(s, d)  # noqa: E731

    def fn(bins_fm, nan_bin, num_bins, mono, is_cat, grad, hess, mask,
           feat_mask, params, gh_scale):
        return grow_tree_rounds(
            bins_fm, nan_bin, num_bins, mono, is_cat, grad, hess, mask,
            feat_mask, params, spec,
            gh_scale=gh_scale if quant else None,
        )

    row, rep = P("data"), P()
    sm = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(None, "data"), rep, rep, rep, rep, row, row, row,
                  rep, rep, rep),
        out_specs=(
            jax.tree.map(lambda _: rep, _tree_arrays_structure(spec)),
            row,
        ),
        check_vma=False,
    )
    return jax.make_jaxpr(sm)(
        mk((G, N), jnp.int32), mk((G,), jnp.int32), mk((G,), jnp.int32),
        mk((G,), jnp.int32), mk((G,), jnp.bool_), mk((N,), jnp.float32),
        mk((N,), jnp.float32), mk((N,), jnp.float32), mk((G,), jnp.bool_),
        params, mk((2,), jnp.float32),
    )


def _trace_rounds_serial():
    import jax
    import jax.numpy as jnp

    from ..config import Config
    from ..learner.grower import GrowerSpec, make_split_params
    from ..learner.rounds import grow_tree_rounds

    L, B, G, N = 31, 64, 8, 4096
    spec = GrowerSpec(num_leaves=L, num_bins=B, max_depth=-1,
                      rounds_slots=8, has_cat=False)
    params = make_split_params(Config({}))
    mk = lambda s, d: jax.ShapeDtypeStruct(s, d)  # noqa: E731
    return jax.make_jaxpr(
        lambda b, nb, numb, mono, cat, g, h, m, fm, p: grow_tree_rounds(
            b, nb, numb, mono, cat, g, h, m, fm, p, spec
        )
    )(
        mk((G, N), jnp.int32), mk((G,), jnp.int32), mk((G,), jnp.int32),
        mk((G,), jnp.int32), mk((G,), jnp.bool_), mk((N,), jnp.float32),
        mk((N,), jnp.float32), mk((N,), jnp.float32), mk((G,), jnp.bool_),
        params,
    )


def _trace_rounds_serial_packed():
    """The int-packed DEFAULT training path (ISSUE 12 tentpole):
    serial rounds grower with quant=True / 256 internal levels and a
    gh_scale input — exactly what boosting._grow_int_packed builds when
    tpu_hist_dtype resolves to int16. 3 histogram channels instead of
    bf16x2's 5; cost_audit pins the bytes-accessed DROP vs
    rounds_serial."""
    import jax
    import jax.numpy as jnp

    from ..config import Config
    from ..learner.grower import GrowerSpec, make_split_params
    from ..learner.rounds import grow_tree_rounds

    L, B, G, N = 31, 64, 8, 4096
    spec = GrowerSpec(num_leaves=L, num_bins=B, max_depth=-1,
                      rounds_slots=8, quant=True, quant_levels=256,
                      has_cat=False)
    params = make_split_params(Config({}))
    mk = lambda s, d: jax.ShapeDtypeStruct(s, d)  # noqa: E731
    return jax.make_jaxpr(
        lambda b, nb, numb, mono, cat, g, h, m, fm, p, sc:
        grow_tree_rounds(
            b, nb, numb, mono, cat, g, h, m, fm, p, spec, gh_scale=sc
        )
    )(
        mk((G, N), jnp.int32), mk((G,), jnp.int32), mk((G,), jnp.int32),
        mk((G,), jnp.int32), mk((G,), jnp.bool_), mk((N,), jnp.float32),
        mk((N,), jnp.float32), mk((N,), jnp.float32), mk((G,), jnp.bool_),
        params, mk((2,), jnp.float32),
    )


def _trace_hist_round(quant: bool = True, route_only: bool = False):
    """The fused partition+histogram pallas kernel (_round_kernel) —
    traced abstractly; pallas_call jaxpr construction is platform-free
    even though compilation needs a TPU. quant=True is the 3-channel
    int-packed layout, quant=False the 5-channel bf16x2 hi/lo split —
    cost_audit pins the bytes-accessed DROP between the pair.
    route_only=True is the same kernel body stopped after the partition
    decision (histogram.route_round: the round that spends the last of
    the leaf budget), which reads no gradients and writes no
    histogram."""
    import jax
    import jax.numpy as jnp

    from ..learner.histogram import HIST_BLK, hist_round, route_round

    S, G, B, N = 8, 8, 64, HIST_BLK * 2
    mk = lambda s, d: jax.ShapeDtypeStruct(s, d)  # noqa: E731
    if route_only:
        def call(b, g, p, prm, coh):
            return route_round(b, p, prm, coh, S, B)
    else:
        def call(b, g, p, prm, coh):
            return hist_round(b, g, p, prm, coh, S, B, quant=quant)
    return jax.make_jaxpr(call)(
        mk((G, N), jnp.int32), mk((8, N), jnp.float32), mk((N,), jnp.int32),
        mk((S, 16), jnp.int32), mk((S, G), jnp.float32),
    )


def _trace_serving_forest():
    """Abstract trace of the serving predictor (serving/forest.py
    forest_apply) — the scoring entry point's jaxpr from shapes alone:
    8 trees x 31 nodes, categorical path on, 256 rows x 16 features."""
    import jax
    import jax.numpy as jnp

    from ..serving.forest import forest_apply

    T, M, L, W, Ck, K, N, F = 8, 31, 32, 4, 1, 1, 256, 16
    mk = lambda s, d: jax.ShapeDtypeStruct(s, d)  # noqa: E731
    tables = {
        "pack": mk((9, T * M), jnp.float32),
        "catw": mk((W,), jnp.int32),
        "leaf_value": mk((T, L), jnp.float32),
        "leaf_const": mk((T, L), jnp.float32),
        "leaf_nf": mk((T, L), jnp.int32),
        "leaf_feat": mk((T, L, Ck), jnp.int32),
        "leaf_coeff": mk((T, L, Ck), jnp.float32),
        "init_node": mk((T,), jnp.int32),
        "class_onehot": mk((T, K), jnp.float32),
    }
    return jax.make_jaxpr(
        lambda t, X, w: forest_apply(t, X, w, has_cat=True, linear=False)
    )(tables, mk((N, F), jnp.float32), mk((T,), jnp.float32))


def _forest_table_shapes(T, M, L, W, Ck, K):
    import jax
    import jax.numpy as jnp

    mk = lambda s, d: jax.ShapeDtypeStruct(s, d)  # noqa: E731
    return {
        "pack": mk((9, T * M), jnp.float32),
        "catw": mk((W,), jnp.int32),
        "leaf_value": mk((T, L), jnp.float32),
        "leaf_const": mk((T, L), jnp.float32),
        "leaf_nf": mk((T, L), jnp.int32),
        "leaf_feat": mk((T, L, Ck), jnp.int32),
        "leaf_coeff": mk((T, L, Ck), jnp.float32),
        "init_node": mk((T,), jnp.int32),
        "class_onehot": mk((T, K), jnp.float32),
    }


def _trace_serving_stack():
    """Abstract trace of the fleet's stacked predictor
    (serving/forest.py stacked_forest_apply): 4 resident slots of the
    serving_forest family, the slot a traced scalar — the executable
    every tenant of a shape family shares."""
    import jax
    import jax.numpy as jnp

    from ..serving.forest import stacked_forest_apply

    S, T, M, L, W, Ck, K, N, F = 4, 8, 31, 32, 4, 1, 1, 256, 16
    mk = lambda s, d: jax.ShapeDtypeStruct(s, d)  # noqa: E731
    tables = _forest_table_shapes(T, M, L, W, Ck, K)
    stack = {
        k: jax.ShapeDtypeStruct((S,) + v.shape, v.dtype)
        for k, v in tables.items()
    }
    return jax.make_jaxpr(
        lambda st, s, X, w: stacked_forest_apply(
            st, s, X, w, has_cat=True, linear=False
        )
    )(stack, mk((), jnp.int32), mk((N, F), jnp.float32),
      mk((T,), jnp.float32))


def _trace_serving_contrib():
    """Abstract trace of the device TreeSHAP entry (serving/forest.py
    contrib_apply): 8 trees x 15 nodes, path dims quantized to 8 edges
    / 4 unique features, 64 rows x 16 features."""
    import jax
    import jax.numpy as jnp

    from ..serving.forest import contrib_apply

    T, M, L, W, Ck, K, N, F = 8, 15, 16, 4, 1, 1, 64, 16
    E, P = 8, 4
    mk = lambda s, d: jax.ShapeDtypeStruct(s, d)  # noqa: E731
    tables = _forest_table_shapes(T, M, L, W, Ck, K)
    ctables = {
        "nodes": mk((T, L, E), jnp.int32),
        "dirs": mk((T, L, E), jnp.float32),
        "slot_oh": mk((T, L, E, P), jnp.float32),
        "zero": mk((T, L, P), jnp.float32),
        "feat": mk((T, L, P), jnp.int32),
        "expect": mk((T,), jnp.float32),
        "tree_class": mk((T,), jnp.int32),
    }
    return jax.make_jaxpr(
        lambda t, c, X, w: contrib_apply(t, c, X, w, has_cat=True)
    )(tables, ctables, mk((N, F), jnp.float32), mk((T,), jnp.float32))


def _trace_feature_parallel(n_devices: int = HOST_DEVICE_COUNT):
    """Abstract shard_map trace of the feature-parallel flat grower
    over a ("feature",) mesh — the exact wiring FeatureParallelGrower
    builds (parallel/feature_parallel.py): rows replicated, the bin
    matrix and per-feature tables sharded on the feature axis, split
    records all-gathered (SyncUpGlobalBestSplit) and the winning
    shard's per-row decision broadcast with one psum. 16 features pad
    evenly onto every rung of the D ladder."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..config import Config
    from ..learner.grower import GrowerSpec, grow_tree, make_split_params
    from ..parallel.data_parallel import _tree_arrays_structure

    mesh = _mesh(n_devices, axis_name="feature")
    L, B, F, N = 15, 64, 16, 512
    spec = GrowerSpec(num_leaves=L, num_bins=B, max_depth=-1,
                      partition="flat", feature_axis="feature",
                      rounds_slots=0, has_cat=False)
    params = make_split_params(Config({}))
    mk = lambda s, d: jax.ShapeDtypeStruct(s, d)  # noqa: E731

    def fn(bins, nan_bin, num_bins, mono, is_cat, grad, hess, mask,
           feat_mask, params, valid):
        tree, row_leaf = grow_tree(
            bins, nan_bin, num_bins, mono, is_cat, grad, hess, mask,
            feat_mask, params, spec, valid=valid,
        )
        tree = jax.tree.map(
            lambda a: jax.lax.pmean(a, "feature")
            if jnp.issubdtype(a.dtype, jnp.floating) else a,
            tree,
        )
        return tree, row_leaf

    fshard, rep = P("feature"), P()
    sm = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P("feature", None), fshard, fshard, fshard, fshard,
                  rep, rep, rep, fshard, rep, rep),
        out_specs=(
            jax.tree.map(lambda _: rep, _tree_arrays_structure(spec)),
            rep,
        ),
        check_vma=False,
    )
    return jax.make_jaxpr(sm)(
        mk((F, N), jnp.int32), mk((F,), jnp.int32), mk((F,), jnp.int32),
        mk((F,), jnp.int32), mk((F,), jnp.bool_), mk((N,), jnp.float32),
        mk((N,), jnp.float32), mk((N,), jnp.float32), mk((F,), jnp.bool_),
        params, mk((N,), jnp.float32),
    )


def _trace_online_holdout():
    """Online promotion gate holdout evaluator (online/gate.py):
    auc + binary_logloss DeviceEvalSet over a 256-row shard with
    deterministic arange-parity labels — the gate's verdict arithmetic
    as one traced fn(score)->(m,)."""
    from ..online.gate import trace_holdout_eval

    return trace_holdout_eval(n=256, num_class=1)


class _Entry(NamedTuple):
    builder: Callable[[], Any]
    contracts: Callable[[Optional[int]], List[ContractFn]]
    doc: str
    # expected collective wire payload dtype (None: entry has no
    # quantized histogram wire). The one-line flip for ROADMAP 3a.
    wire_dtype: Optional[str] = None
    # entry contains pallas kernels: the cost auditor must trace it
    # under the pallas interpreter to compile on the CPU backend
    pallas_interpret: bool = False
    # mesh-bearing entries: builder parameterized by device count, so
    # scale_audit (Pass 7) can re-trace the same wiring at the
    # D ∈ {1, 2, 4, 8} ladder. `builder` stays the full-mesh (D=8)
    # trace every other pass reads; build_entry shares the memo.
    mesh_builder: Optional[Callable[[int], Any]] = None


# the quantized data-parallel histogram wire dtype (reference halves
# socket bytes with int16/int32 packing, include/LightGBM/bin.h:63-81;
# ROADMAP 3a landed: histogram.rs_wire_dtype picks the NARROWEST exact
# payload — int16 while the mesh-wide hessian worst case stays under
# 2^15, int32 up to the 2^31/2^24 bounds, f32 psum past those. The
# wire-bytes halving is pinned by cost_audit's exact wire budget.)
QUANT_WIRE_DTYPE = "int16"

# levels=16, 128 local rows: 128*8*16 = 16384 < 2^15 — the int16 wire
# must engage (256 local rows would hit exactly 2^15 and step down)
_RS_OK = dict(quant=True, levels=16, local_rows=128)
# levels=16, 2048 local rows: 2048*8*16 = 262k >= 2^15 but < 2^31 and
# 2048*16 = 32k < 2^24 — the wire steps down to int32, not psum
_RS_INT32 = dict(quant=True, levels=16, local_rows=2048)
# levels=256, 131072 local rows: 131072*256 = 33.5M > 2^24 — the
# per-shard exactness bound trips and the wire must fall back to psum
_RS_OVERFLOW = dict(quant=True, levels=256, local_rows=131072)

# chunk length traced for the fused_chunk_scan entry, and the second
# length the C-invariance audit compares against. Both must be real
# config.DEFAULT_CHUNK_LADDER rungs so the audited executables are the
# ones training actually dispatches.
_CHUNK_SCAN_C = 4
_CHUNK_SCAN_C_ALT = 16


def _trace_chunk_scan(length: int = _CHUNK_SCAN_C):
    """One C-round fused chunk dispatch (boosting.trace_fused_chunk):
    the whole boosting inner loop — gradients, growth, score updates,
    device metrics — scanned on device. The mega-entry of ROADMAP item
    2; budgets must NOT scale with C (scan body counted once)."""
    from ..boosting import trace_fused_chunk

    return trace_fused_chunk(length)


def _trace_streamed_construct():
    """The per-chunk device step of the out-of-core construct
    (data/prefetch.py chunk_update_step): dynamic_update_slice of one
    (G, chunk_rows) int32 chunk into the (G, Np) resident bin matrix
    at a traced row offset. Everything else on that path (spool reads,
    crc checks, binning, padding) is host work on the reader thread —
    this is the entire device-side surface, so it must stay
    callback-free and f64-free."""
    import jax
    import jax.numpy as jnp

    from ..data.prefetch import chunk_update_step

    G, NP, CR = 8, 8192, 2048
    mk = lambda s, d: jax.ShapeDtypeStruct(s, d)  # noqa: E731
    return jax.make_jaxpr(chunk_update_step)(
        mk((G, NP), jnp.int32), mk((G, CR), jnp.int32),
        mk((), jnp.int32),
    )


ENTRIES: Dict[str, _Entry] = {
    "fused_chunk_scan": _Entry(
        _trace_chunk_scan,
        lambda budget: [
            has_prim("scan",
                     "the C-round boosting loop is device control flow"),
            no_host_callbacks(),
            no_f64(),
            lacks_prim("reduce_scatter",
                       "single device; the chunk carries no mesh wire"),
            within_budget(budget),
        ],
        "chunk-scan fused boosting dispatch (boosting.fused_dispatch): "
        f"{_CHUNK_SCAN_C} rounds of gradients+growth+score+metrics as "
        "one lax.scan — the host-evicted inner loop, held to the same "
        "callback/f64/budget contracts as every other entry",
    ),
    "rounds_quant_rs": _Entry(
        lambda: _trace_rounds_dp(**_RS_OK),
        lambda budget: [
            has_prim("reduce_scatter",
                     "the quantized histogram wire (bin.h:63-81)"),
            wire_dtype(QUANT_WIRE_DTYPE),
            no_host_callbacks(),
            no_f64(),
            within_budget(budget),
        ],
        "quantized data-parallel grower inside the exactness bounds: "
        f"{QUANT_WIRE_DTYPE} reduce-scatter wire end to end",
        wire_dtype=QUANT_WIRE_DTYPE,
        mesh_builder=lambda d: _trace_rounds_dp(**_RS_OK, n_devices=d),
    ),
    "rounds_quant_rs_int32": _Entry(
        lambda: _trace_rounds_dp(**_RS_INT32),
        lambda budget: [
            has_prim("reduce_scatter",
                     "the wire survives past the int16 bound"),
            wire_dtype("int32"),
            no_host_callbacks(),
            no_f64(),
            within_budget(budget),
        ],
        "quantized grower past the int16 bound but inside int32 "
        "exactness: wire steps down to int32, not psum",
        wire_dtype="int32",
        mesh_builder=lambda d: _trace_rounds_dp(**_RS_INT32, n_devices=d),
    ),
    "rounds_quant_rs_overflow": _Entry(
        lambda: _trace_rounds_dp(**_RS_OVERFLOW),
        lambda budget: [
            lacks_prim("reduce_scatter",
                       "past 2^24 per-shard the int32 wire would be "
                       "inexact; rs_exact_ok must disable it"),
            has_prim("psum", "the f32 fallback wire"),
            no_host_callbacks(),
        ],
        "quantized grower past the exactness bound: overflow gate "
        "engaged, f32 psum fallback",
        mesh_builder=lambda d: _trace_rounds_dp(**_RS_OVERFLOW,
                                                n_devices=d),
    ),
    "rounds_voting": _Entry(
        lambda: _trace_rounds_dp(**_RS_OK, voting_k=2),
        lambda budget: [
            has_prim("psum",
                     "vote tally + elected-column payload cross the "
                     "mesh (rounds.vote_reduce)"),
            lacks_prim("reduce_scatter",
                       "voting replaces the full-width owned-block "
                       "wire; the elected ~2k columns ride psum"),
            no_host_callbacks(),
            no_f64(),
            within_budget(budget),
        ],
        "voting-parallel rounds grower (tree_learner=voting): per-round "
        "top-k election, only the elected bundle columns cross the mesh "
        "— int16 payload while the quantized sums provably fit; "
        "cost_audit pins the wire-bytes DROP vs rounds_quant_rs",
        mesh_builder=lambda d: _trace_rounds_dp(**_RS_OK, voting_k=2,
                                                n_devices=d),
    ),
    "feature_parallel": _Entry(
        _trace_feature_parallel,
        lambda budget: [
            has_prim("all_gather",
                     "SyncUpGlobalBestSplit: per-rank best records "
                     "gathered, winner picked identically everywhere"),
            has_prim("psum",
                     "the winning shard broadcasts its per-row split "
                     "decision (one bit-vector per split)"),
            lacks_prim("reduce_scatter",
                       "feature-parallel moves NO histograms — only "
                       "split records and one row bit-vector"),
            no_host_callbacks(),
            no_f64(),
            within_budget(budget),
        ],
        "feature-parallel flat grower (tree_learner=feature, "
        "parallel_tree_learner.h:26): rows replicated, features "
        "sharded, record-only wire — the second mesh axis ROADMAP 5's "
        "2D rows x features sharding composes from",
        mesh_builder=_trace_feature_parallel,
    ),
    "rounds_serial": _Entry(
        _trace_rounds_serial,
        lambda budget: [
            no_host_callbacks(),
            no_f64(),
            lacks_prim("reduce_scatter", "no mesh, no collective"),
            within_budget(budget),
        ],
        "single-device rounds grower: pure device loop",
    ),
    "rounds_serial_packed": _Entry(
        _trace_rounds_serial_packed,
        lambda budget: [
            no_host_callbacks(),
            no_f64(),
            lacks_prim("reduce_scatter", "no mesh, no collective"),
            within_budget(budget),
        ],
        "int-packed default path (tpu_hist_dtype=int16): 3-channel "
        "integer histograms + scale recovery, single device",
    ),
    "hist_round_fused": _Entry(
        _trace_hist_round,
        lambda budget: [
            has_prim("pallas_call", "the fused _round_kernel"),
            no_host_callbacks(),
            no_f64(),
            within_budget(budget),
        ],
        "fused partition+histogram kernel (pallas_hist._round_kernel), "
        "3-channel int-packed layout",
        pallas_interpret=True,
    ),
    "hist_round_fused_bf16": _Entry(
        lambda: _trace_hist_round(quant=False),
        lambda budget: [
            has_prim("pallas_call", "the fused _round_kernel"),
            no_host_callbacks(),
            no_f64(),
            within_budget(budget),
        ],
        "fused round kernel, 5-channel bf16x2 hi/lo layout — the "
        "baseline the int-packed pair must undercut",
        pallas_interpret=True,
    ),
    "hist_round_fused_route": _Entry(
        lambda: _trace_hist_round(route_only=True),
        lambda budget: [
            has_prim("pallas_call", "_round_kernel, route_only"),
            no_host_callbacks(),
            no_f64(),
            within_budget(budget),
        ],
        "the fused round kernel's routing half alone (histogram."
        "route_round): the new row->leaf vector of the round that spends "
        "the last of the leaf budget — no gradient input, no histogram "
        "block, a fraction of the fused pass's bytes and flops",
        pallas_interpret=True,
    ),
    "serving_forest": _Entry(
        _trace_serving_forest,
        lambda budget: [
            no_host_callbacks(),
            no_f64(),
            has_prim("while", "depth-stepped lockstep traversal"),
            within_budget(budget),
        ],
        "serving predictor (serving/forest.py): f32/int32 scoring "
        "jaxpr, no callbacks, bounded size",
    ),
    "serving_fleet_stack": _Entry(
        _trace_serving_stack,
        lambda budget: [
            no_host_callbacks(),
            no_f64(),
            has_prim("while", "depth-stepped lockstep traversal"),
            within_budget(budget),
        ],
        "fleet stacked predictor (serving/forest.py "
        "stacked_forest_apply): slot-indexed scoring over (S, ...) "
        "stacked tables, the executable a shape family shares",
    ),
    "serving_contrib": _Entry(
        _trace_serving_contrib,
        lambda budget: [
            no_host_callbacks(),
            no_f64(),
            has_prim("scatter-add",
                     "per-leaf deltas land on feature columns"),
            within_budget(budget),
        ],
        "device TreeSHAP (serving/forest.py contrib_apply): "
        "extend/unwind permutation-weight DP over (row, tree, leaf) "
        "lanes, host shap.py parity",
    ),
    "online_holdout_eval": _Entry(
        _trace_online_holdout,
        lambda budget: [
            no_host_callbacks(),
            no_f64(),
            within_budget(budget),
        ],
        "online promotion-gate holdout evaluator (online/gate.py): "
        "device metrics over the candidate's raw margins — the gate "
        "verdict must stay callback-free and f32",
    ),
    "streamed_construct": _Entry(
        _trace_streamed_construct,
        lambda budget: [
            has_prim("dynamic_update_slice",
                     "each chunk lands at its row offset in the "
                     "resident bin matrix"),
            no_host_callbacks(),
            no_f64(),
            within_budget(budget),
        ],
        "out-of-core per-chunk device step (data/prefetch.py "
        "chunk_update_step): one int32 chunk written into the "
        "(G, Np) resident matrix — the only device work on the "
        "streamed construct path; the disk reads/binning stay on the "
        "prefetch reader thread (docs/DATA_PLANE.md)",
    ),
}


# ------------------------------------------------------- fold-attr audit
_DATASET_DEVICE_CALLS = (
    "device_label", "device_weight", "rank_layout", "rank_part",
    "lambdarank_arrays", "layout_arrays", "ndcg_arrays", "map_arrays",
)


def audit_fold_attrs() -> AuditResult:
    """_OBJ_FOLD_ATTRS exhaustiveness (ADVICE r5 item 3): statically
    prove no objective class assigns a device array to an attribute
    outside the fused step's rebind list — an unlisted one would be
    baked into the memoized executable and silently shared across cv
    folds. Pure AST; no jax import."""
    import ast

    from .. import objectives as _obj_mod
    from ..boosting import _OBJ_FOLD_ATTRS, _OBJ_FOLD_EXEMPT

    src = Path(_obj_mod.__file__).read_text()
    tree = ast.parse(src)

    def is_device_expr(node: ast.AST) -> bool:
        for n in ast.walk(node):
            if isinstance(n, ast.Call):
                parts: List[str] = []
                f = n.func
                while isinstance(f, ast.Attribute):
                    parts.append(f.attr)
                    f = f.value
                if isinstance(f, ast.Name):
                    parts.append(f.id)
                d = ".".join(reversed(parts))
                if d.startswith("jnp.") or d.startswith("jax.numpy."):
                    return True
                if d in ("jax.device_put",) or d.startswith("jax.random."):
                    return True
                # the data set's resident device arrays (label, weight,
                # the ranking layout and what is derived from it)
                if d.rsplit(".", 1)[-1] in _DATASET_DEVICE_CALLS:
                    return True
        return False

    device_attrs: Dict[str, int] = {}
    for n in ast.walk(tree):
        if isinstance(n, ast.Assign) and len(n.targets) == 1:
            t = n.targets[0]
            if (
                isinstance(t, ast.Attribute)
                and isinstance(t.value, ast.Name)
                and t.value.id == "self"
                and is_device_expr(n.value)
            ):
                device_attrs.setdefault(t.attr, n.lineno)
    unlisted = {
        a: ln for a, ln in sorted(device_attrs.items())
        if a not in _OBJ_FOLD_ATTRS and a not in _OBJ_FOLD_EXEMPT
    }
    ok = not unlisted
    detail = (
        f"device attrs {sorted(device_attrs)} all in _OBJ_FOLD_ATTRS "
        f"(+exempt {sorted(_OBJ_FOLD_EXEMPT)})"
        if ok
        else "objective attrs hold device arrays OUTSIDE the fused "
        "rebind list (would silently share fold data across cached "
        "steps): "
        + ", ".join(f"{a} (objectives.py:{ln})" for a, ln in unlisted.items())
        + " — add to _OBJ_FOLD_ATTRS or _OBJ_FOLD_EXEMPT (with a "
        "gating reason)"
    )
    return AuditResult(
        "obj_fold_attrs", ok,
        [Contract("fold_attrs_exhaustive", ok, detail)], 0,
    )


# -------------------------------------------------- fault-injection audit
def audit_faultinject() -> AuditResult:
    """Fault injection must cost nothing when disarmed and stay
    invisible to traced code when armed (docs/RESILIENCE.md):

    1. pure-AST: every ``fault_point()`` call site lives in a
       whitelisted HOST-side module (engine loop, serving dispatcher /
       transport) — a call in kernel or traced code would bake a host
       callback (or a retrace) into the hot path;
    2. trace proof: building the serving entry with a fault plan ARMED
       (cache bypassed) yields a jaxpr with the identical equation
       count and no host callbacks — arming adds zero device work.
    """
    import ast

    from ..resilience import faultinject as _fi

    pkg_root = Path(__file__).resolve().parents[1]
    allowed = {
        "resilience/faultinject.py",  # the definition itself
        "engine.py",                  # per-round host loop
        "serving/dispatch.py",        # host side of the device call
        "serving/server.py",          # request transport
        "serving/fleet.py",           # HBM paging (fleet_page site)
        "serving/gateway.py",         # gw_* request/drain sites
        "online/loop.py",             # loop_* phase sites per cycle
    }
    sites: List[str] = []
    offenders: List[str] = []
    for py in sorted(pkg_root.rglob("*.py")):
        rel = py.relative_to(pkg_root).as_posix()
        src = py.read_text()
        if "fault_point" not in src:
            continue
        for n in ast.walk(ast.parse(src)):
            if isinstance(n, ast.Call):
                f = n.func
                fname = (f.attr if isinstance(f, ast.Attribute)
                         else getattr(f, "id", ""))
                if fname == "fault_point":
                    sites.append(f"{rel}:{n.lineno}")
                    if rel not in allowed:
                        offenders.append(f"{rel}:{n.lineno}")
    c_sites = Contract(
        "fault_sites_host_only", not offenders,
        f"{len(sites)} fault_point site(s) all in host-side modules "
        f"{sorted(allowed)}" if not offenders else
        "fault_point called outside the host-side whitelist (would "
        "put a fault hook into traced/kernel code): "
        + ", ".join(offenders),
    )

    baseline = summarize(build_entry("serving_forest"))
    prev_plan = _fi._PLAN
    _fi.arm("device_put:999999:raise;serve_request:999999:raise")
    try:
        armed = summarize(ENTRIES["serving_forest"].builder())
    finally:
        _fi._PLAN = prev_plan  # restore whatever the caller had armed
    c_eqns = Contract(
        "armed_trace_identical", armed.eqn_count == baseline.eqn_count,
        f"serving trace has {armed.eqn_count} eqns armed vs "
        f"{baseline.eqn_count} disarmed"
        + ("" if armed.eqn_count == baseline.eqn_count else
           " — an armed fault plan must not change the traced program"),
    )
    c_cb = no_host_callbacks()(armed)
    ok = all(c.ok for c in (c_sites, c_eqns, c_cb))
    return AuditResult(
        "faultinject", ok, [c_sites, c_eqns, c_cb], armed.eqn_count
    )


# ------------------------------------------- chunk-scan C-invariance audit
def audit_chunk_invariance() -> AuditResult:
    """The scan body is traced ONCE: the chunk jaxpr's flattened eqn
    count must be identical across ladder rungs (scan length is a jaxpr
    param). Accidental unrolling — a Python loop over rounds, a
    shape-dependent branch on the rung — would scale eqns with C and
    silently void the committed fused_chunk_scan budgets, which are
    pinned at C=%d and must cover every rung.""" % _CHUNK_SCAN_C
    from ..boosting import trace_fused_chunk

    a = summarize(trace_fused_chunk(_CHUNK_SCAN_C))
    b = summarize(trace_fused_chunk(_CHUNK_SCAN_C_ALT))
    ok = a.eqn_count == b.eqn_count
    c = Contract(
        "eqns_independent_of_C", ok,
        f"{a.eqn_count} eqns at C={_CHUNK_SCAN_C} vs {b.eqn_count} at "
        f"C={_CHUNK_SCAN_C_ALT}"
        + ("" if ok else
           " — the scan body unrolled; budgets no longer cover all "
           "ladder rungs"),
    )
    return AuditResult("chunk_c_invariance", ok, [c], a.eqn_count)


# ------------------------------------------------------------------ runner
# entry traces are pure functions of checked-in shapes, and the strict
# gate reads each one at least twice (jaxpr pass + cost pass, several
# seconds per rounds trace) — memoize per (entry, interpret-mode,
# mesh size) so the scale auditor's D=8 rung shares the trace the
# jaxpr/cost passes already paid for
_CLOSED_CACHE: Dict[Any, Any] = {}


def mesh_entry_names() -> List[str]:
    """Entries that trace through a device mesh (the scale auditor's
    universe: anything whose collectives/shardings can vary with D)."""
    return [n for n, e in ENTRIES.items() if e.mesh_builder is not None]


def build_entry(name: str, pallas_interpret: bool = False,
                n_devices: Optional[int] = None):
    """Entry ClosedJaxpr, memoized. With pallas_interpret the trace
    runs under the pallas interpreter (histogram._interpret_pallas
    reads the env var at trace time) so XLA:CPU can later compile it —
    the cost auditor's path for pallas entries. The env var is forced
    BOTH ways: an ambient LGBM_TPU_PALLAS_INTERPRET=1 (the pallas
    debugging knob) must not leak an interpreted trace into the
    non-interpreted budget comparison.

    n_devices retraces a mesh-bearing entry on a sub-mesh of the
    forced host platform (the scale auditor's D-ladder). None means
    the entry's default mesh; for mesh entries that is
    HOST_DEVICE_COUNT, and the cache key normalizes the two spellings
    to one slot so passes share the full-mesh trace."""
    import os

    entry = ENTRIES[name]
    if n_devices is not None and entry.mesh_builder is None:
        raise ValueError(
            f"entry {name!r} has no mesh; n_devices={n_devices} is "
            "meaningless (only mesh_entry_names() entries retrace on "
            "the D-ladder)")
    n = n_devices
    if entry.mesh_builder is not None and n is None:
        n = HOST_DEVICE_COUNT
    key = (name, bool(pallas_interpret), n)
    if key in _CLOSED_CACHE:
        return _CLOSED_CACHE[key]
    env_key = "LGBM_TPU_PALLAS_INTERPRET"
    old = os.environ.get(env_key)
    if pallas_interpret:
        os.environ[env_key] = "1"
    else:
        os.environ.pop(env_key, None)
    try:
        if n is not None and n != HOST_DEVICE_COUNT:
            closed = entry.mesh_builder(n)
        else:
            closed = entry.builder()
    finally:
        if old is None:
            os.environ.pop(env_key, None)
        else:
            os.environ[env_key] = old
    _CLOSED_CACHE[key] = closed
    return closed


def load_budgets() -> Dict[str, int]:
    if _BUDGET_PATH.exists():
        return {
            k: int(v) for k, v in json.loads(_BUDGET_PATH.read_text()).items()
        }
    return {}


def run_audits(names: Optional[Sequence[str]] = None,
               update_budget: bool = False) -> List[AuditResult]:
    _standalone = ("obj_fold_attrs", "faultinject", "chunk_c_invariance")
    if names is not None:
        unknown = set(names) - set(ENTRIES) - set(_standalone)
        if unknown:
            # a typoed entry name must not pass vacuously ("no silent
            # caps" — same posture as within_budget failing on a
            # missing budget)
            raise KeyError(
                f"unknown audit entr{'y' if len(unknown) == 1 else 'ies'} "
                f"{sorted(unknown)}; known: "
                f"{sorted(ENTRIES) + sorted(_standalone)}"
            )
    budgets = load_budgets()
    out: List[AuditResult] = []
    new_budgets = dict(budgets)
    for name, entry in ENTRIES.items():
        if names is not None and name not in names:
            continue
        closed = build_entry(name)
        s = summarize(closed)
        if update_budget:
            new_budgets[name] = int(math.ceil(s.eqn_count * _BUDGET_HEADROOM))
        contracts = entry.contracts(new_budgets.get(name))
        results = [c(s) for c in contracts]
        out.append(AuditResult(
            name, all(c.ok for c in results), results, s.eqn_count
        ))
    if names is None or "obj_fold_attrs" in (names or ()):
        out.append(audit_fold_attrs())
    if names is None or "faultinject" in (names or ()):
        out.append(audit_faultinject())
    if names is None or "chunk_c_invariance" in (names or ()):
        out.append(audit_chunk_invariance())
    if update_budget:
        _BUDGET_PATH.write_text(
            json.dumps(new_budgets, indent=2, sort_keys=True) + "\n"
        )
    return out
