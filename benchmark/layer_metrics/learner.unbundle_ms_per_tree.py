"""Device time per tree under the program scope ``lgbm.learner.unbundle``
(PR 38): what turns a bundled Dataset's EFB bundle histograms into
per-feature candidates inside the split search (learner/bundle.py: the
segment sums over each member's merged range, the most-frequent bin's
mass, the cell tables). Nested in ``lgbm.learner.split_search``, whose
metric keeps the gains and the reductions. Phase -> embedded HLO module
-> trace: ``harness/device_phases.py``. Empty on a Dataset without
bundles."""

from benchmark.harness import device_phases

LAYER, MOVES, SOURCE = "learner", "train_trees_per_s", "device_trace"
UNIT, BETTER = "ms", "lower"


def read(inp):
    return device_phases.ms_per_tree(inp, "learner.unbundle")
