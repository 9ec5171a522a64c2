"""Device time per tree under the program scope ``lgbm.learner.hist``:
the XLA work AROUND the histogram kernels: the root's totals, operands
and what turns the kernels' output into child rows (the kernels' own
events are learner.hist_ms_per_tree and belong to no phase metric).
Phase -> embedded HLO module -> trace: ``harness/device_phases.py``."""

from benchmark.harness import device_phases

LAYER, MOVES, SOURCE = "learner", "train_trees_per_s", "device_trace"
UNIT, BETTER = "ms", "lower"


def read(inp):
    return device_phases.ms_per_tree(inp, "learner.hist")
