"""Device time per tree under the program scope
``lgbm.metrics.valid_eval``: everything a live valid set costs a round:
its traversal, its score update and the eval row (the AUC's sort, NDCG /
MAP, losses). Phase -> embedded HLO module -> trace:
``harness/device_phases.py``."""

from benchmark.harness import device_phases

LAYER, MOVES, SOURCE = "metrics", "train_trees_per_s", "device_trace"
UNIT, BETTER = "ms", "lower"


def read(inp):
    return device_phases.ms_per_tree(inp, "metrics.valid_eval")
