"""Device time per tree under the program scope ``lgbm.boosting.renew``:
leaf renewal: seg_sum_tpu over the rows' true gradients and the leaf-
value rewrite. Phase -> embedded HLO module -> trace:
``harness/device_phases.py``."""

from benchmark.harness import device_phases

LAYER, MOVES, SOURCE = "boosting", "train_trees_per_s", "device_trace"
UNIT, BETTER = "ms", "lower"


def read(inp):
    return device_phases.ms_per_tree(inp, "boosting.renew")
