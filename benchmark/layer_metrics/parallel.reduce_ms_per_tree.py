"""Device time per tree under the program scope
``lgbm.parallel.reduce``: what crosses the mesh: psum / reduce-scatter
of the slot histograms, the leaf recount, the tree's pmean (nothing on
one chip). Phase -> embedded HLO module -> trace:
``harness/device_phases.py``."""

from benchmark.harness import device_phases

LAYER, MOVES, SOURCE = "parallel", "train_trees_per_s", "device_trace"
UNIT, BETTER = "ms", "lower"


def read(inp):
    return device_phases.ms_per_tree(inp, "parallel.reduce")
