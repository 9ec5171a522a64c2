"""Device time per tree under the program scope
``lgbm.learner.subtract``: sibling subtraction: the parents' row slices,
larger child = parent - smaller. Phase -> embedded HLO module -> trace:
``harness/device_phases.py``."""

from benchmark.harness import device_phases

LAYER, MOVES, SOURCE = "learner", "train_trees_per_s", "device_trace"
UNIT, BETTER = "ms", "lower"


def read(inp):
    return device_phases.ms_per_tree(inp, "learner.subtract")
