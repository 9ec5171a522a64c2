"""Per tree, the chip's idle time inside the program's
``boosting.fused_start`` span, children included: the init-score
statistic (``objective.boost_from_score``), the fused step's memo lookup
or re-trace set-up (``boosting.build_step``) and the loop-state pytree.
One of the five parts of ``engine.host_ms_per_tree``
(``harness/program_spans.py``)."""

from benchmark.harness import program_spans

LAYER, MOVES, SOURCE = "boosting", "train_trees_per_s", "device_trace"
UNIT, BETTER = "ms", "lower"


def read(inp):
    return program_spans.idle_ms_per_tree(inp, "fused_start")
