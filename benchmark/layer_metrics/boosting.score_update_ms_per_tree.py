"""Device time per tree under the program scope
``lgbm.boosting.score_update``: the training score's update:
take_small_tpu over the training rows. Phase -> embedded HLO module ->
trace: ``harness/device_phases.py``."""

from benchmark.harness import device_phases

LAYER, MOVES, SOURCE = "boosting", "train_trees_per_s", "device_trace"
UNIT, BETTER = "ms", "lower"


def read(inp):
    return device_phases.ms_per_tree(inp, "boosting.score_update")
