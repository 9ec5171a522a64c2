"""Issued multiply-adds of the histogram kernels per tree, from what the
PROGRAM says about its own schedule (its gauges and counters), not from
the shapes in a trace event's name.

``rooflines/hist_round.py`` reads each call's slot count from a 2-D
output shape ``[slots x 3, features x bins]``. Past 32 columns the
kernels' output is 3-D, and past one bins tile (a wide table,
``histogram.hist_plan``) the feature axis is the grid's leading
dimension and the output ``[blocks x groups, slots x channels, 32 x
bins]``: no shape in the event says how many slots a call had. The
program does: where the fused step is built it sets

- ``lgbmtpu_hist_feature_blocks{kernel}``: feature blocks of one call
  (0: the kernel is not in the program),
- ``lgbmtpu_hist_block_columns{kernel}``: columns of one block's bins
  tile as the kernel's loop groups cover them (whole groups, so
  blocks x columns >= the table's columns: the padded block the kernel
  really multiplies),
- ``lgbmtpu_hist_calls_per_pass{width}``: kernel calls that stream the
  rows in one pass of that width (root, then the slot ladder),

and it counts ``lgbmtpu_grower_rounds_total{width}`` (rounds per ladder
width; ``route`` rounds build no histogram) and
``lgbmtpu_train_trees_total``. Every job of a cell trains the same
trees, so rounds over trees is the schedule of one tree.

ISSUED per tree = 2 x rows x bins x channels x sum over passes of
(calls x slots per call x blocks x block columns), the root pass being
one ``hist_nat_tpu`` call of one slot. The definitions of the two shares
are ``hist_round.py``'s: issued over the operand type's peak over kernel
time (how full this formulation keeps the MXU), and the floor of what
the ALGORITHM needs per call (one read of each row's int32 bins and
gradient triple, or 3 adds per row and column) over kernel time.

Hand numbers (``benchmark/tests/test_bench_blocked.py``): 400,000 x
2,000 columns at 63 bins in 6 blocks of 352 columns (2,112 multiplied),
3 channels, a tree of 1 + 4 x 8 + 16 + 32 + 3 x 48 = 225 slot-passes
issues 2 x 225 x 3 x 400,000 x 2,112 x 63 = 7.185e13 FLOP, 0.365 s at
197 TF/s; one stream needs 400,000 x 8,012 B = 3.20 GB, 3.91 ms at 819
GB/s."""

from __future__ import annotations

import re
from typing import Any, Dict, Mapping, Optional

ROOT, ROUTE = "root", "route"
ROUND_KERNEL, NAT_KERNEL = "hist_round_tpu", "hist_nat_tpu"
# every Pallas call of a round at any width: the histogram kernels and
# the routing pass (what learner.non_hist_ms_per_tree leaves out)
ROUND_CALLS_PATTERN = r"^%?(hist_round_tpu|hist_nat_tpu|route_round_tpu)\b"


def _by_label(family: Optional[Mapping[str, float]]) -> Dict[str, float]:
    """{label value: sample} of a one-label metric family of the
    registry's snapshot ('{width="8"}' -> '8')."""
    out = {}
    for rendered, value in (family or {}).items():
        m = re.search(r'="([^"]*)"', rendered)
        out[m.group(1) if m else ""] = float(value)
    return out


def program_schedule(snapshot: Mapping[str, Mapping[str, float]]
                     ) -> Optional[Dict[str, Any]]:
    """What the program's registry says of its histogram schedule, or
    None where it exports no such gauges (a program from before them)
    or has trained nothing."""
    blocks = _by_label(snapshot.get("lgbmtpu_hist_feature_blocks"))
    cols = _by_label(snapshot.get("lgbmtpu_hist_block_columns"))
    calls = _by_label(snapshot.get("lgbmtpu_hist_calls_per_pass"))
    rounds = _by_label(snapshot.get("lgbmtpu_grower_rounds_total"))
    trees = sum((snapshot.get("lgbmtpu_train_trees_total") or {}).values())
    if not blocks or not cols or not calls or not rounds or not trees:
        return None
    return {
        "columns": {k: int(blocks[k] * cols.get(k, 0)) for k in blocks},
        "calls": {w: int(n) for w, n in calls.items()},
        "rounds_per_tree": {w: n / trees for w, n in rounds.items()},
    }


def slot_columns_per_tree(schedule: Mapping[str, Any]) -> float:
    """Sum over one tree's histogram passes of calls x slots per call x
    the columns each call multiplies."""
    columns = schedule["columns"]
    round_cols = columns.get(ROUND_KERNEL) or columns[NAT_KERNEL]
    total = float(columns[NAT_KERNEL])  # the root: one call, one slot
    for width, per_tree in schedule["rounds_per_tree"].items():
        if width == ROUTE or not per_tree:
            continue
        calls = schedule["calls"][width]
        per_call = -(-int(width) // calls)
        total += per_tree * calls * per_call * round_cols
    return total


def issued_flops_per_tree(schedule: Mapping[str, Any], rows: int,
                          bins: int, channels: int) -> float:
    return 2.0 * rows * bins * channels * slot_columns_per_tree(schedule)


def channels_of(hist_dtype: str) -> int:
    """Gradient rows per slot on the MXU's M axis: grad, hess, count for
    the int-packed layouts; the bf16x2 split carries five."""
    return 3 if hist_dtype in ("int16", "int8") else 5


def read_schedule() -> Optional[Dict[str, Any]]:
    """program_schedule of this process's registry."""
    from lightgbm_tpu.obs.metrics import default_registry

    return program_schedule(default_registry().snapshot())
