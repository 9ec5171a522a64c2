"""Per tree, the chip's idle time inside the program's ``fused collect
(readback)`` and ``materialize host trees (readback)`` spans: the eval
rows and the trees coming back once the device has finished. One of the
five parts of ``engine.host_ms_per_tree``
(``harness/program_spans.py``)."""

from benchmark.harness import program_spans

LAYER, MOVES, SOURCE = "boosting", "train_trees_per_s", "device_trace"
UNIT, BETTER = "ms", "lower"


def read(inp):
    return program_spans.idle_ms_per_tree(inp, "collect")
