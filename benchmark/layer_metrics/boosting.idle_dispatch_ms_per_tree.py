"""Per tree, the chip's idle time inside the program's ``fused
dispatch`` span (its ``round: fused step`` launches included): the
host cost of enqueueing the chunks and, where a Booster does not find
its step memoized (``tree_learner=data``), the re-trace, re-lower and
cache loads of the first launch. One of the five parts of
``engine.host_ms_per_tree`` (``harness/program_spans.py``)."""

from benchmark.harness import program_spans

LAYER, MOVES, SOURCE = "boosting", "train_trees_per_s", "device_trace"
UNIT, BETTER = "ms", "lower"


def read(inp):
    return program_spans.idle_ms_per_tree(inp, "dispatch")
