"""Device time per tree under the program scope
``lgbm.objective.gradients``: the objective's gradients and hessians
(the rank cell: the whole LambdaRank gradient, its marks included).
Phase -> embedded HLO module -> trace: ``harness/device_phases.py``."""

from benchmark.harness import device_phases

LAYER, MOVES, SOURCE = "objective", "train_trees_per_s", "device_trace"
UNIT, BETTER = "ms", "lower"


def read(inp):
    return device_phases.ms_per_tree(inp, "objective.gradients")
