"""Device time per tree under the program scope ``lgbm.learner.select``:
a round's selection and bookkeeping: top_k over the leaves' best gains,
the tree arrays' scatters, leaf statistics, and whatever of a round no
other phase names. Phase -> embedded HLO module -> trace:
``harness/device_phases.py``."""

from benchmark.harness import device_phases

LAYER, MOVES, SOURCE = "learner", "train_trees_per_s", "device_trace"
UNIT, BETTER = "ms", "lower"


def read(inp):
    return device_phases.ms_per_tree(inp, "learner.select")
