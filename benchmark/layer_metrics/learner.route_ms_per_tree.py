"""Device time per tree under the program scope ``lgbm.learner.route``:
routing rows to their children: the routing parameters, the split-column
table and route_round_tpu (every round at width; the routing-only round
everywhere). Phase -> embedded HLO module -> trace:
``harness/device_phases.py``."""

from benchmark.harness import device_phases

LAYER, MOVES, SOURCE = "learner", "train_trees_per_s", "device_trace"
UNIT, BETTER = "ms", "lower"


def read(inp):
    return device_phases.ms_per_tree(inp, "learner.route")
