"""Per tree, the chip's idle time while ``lgb.train`` built its Booster:
inside the program's ``engine.booster_init`` span, children included
(``boosting.objective_init``, ``boosting.device_inputs``,
``boosting.score_init`` for the train and each valid set). One of the
five parts of ``engine.host_ms_per_tree``
(``harness/program_spans.py``)."""

from benchmark.harness import program_spans

LAYER, MOVES, SOURCE = "engine", "train_trees_per_s", "device_trace"
UNIT, BETTER = "ms", "lower"


def read(inp):
    return program_spans.idle_ms_per_tree(inp, "booster_init")
