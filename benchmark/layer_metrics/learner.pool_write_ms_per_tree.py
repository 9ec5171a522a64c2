"""Device time per tree under the program scope
``lgbm.learner.pool_write``: the round's one write of the histogram
pool: padding the children to 2S rows and the row scatter into the
loop's carry. Phase -> embedded HLO module -> trace:
``harness/device_phases.py``."""

from benchmark.harness import device_phases

LAYER, MOVES, SOURCE = "learner", "train_trees_per_s", "device_trace"
UNIT, BETTER = "ms", "lower"


def read(inp):
    return device_phases.ms_per_tree(inp, "learner.pool_write")
