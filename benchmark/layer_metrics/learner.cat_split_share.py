"""Share of the trees' splits that are categorical: ``cat_onehot`` +
``cat_subset`` over all kinds of the program's counter
``lgbmtpu_tree_splits_total{kind}``, which the fused collect ticks once
per split of every host tree it builds. The counter is the process's:
warm-up and window jobs are all trees 1..8 of a fresh ensemble on the
same Dataset, so the process's share is the window's. A round whose
slots hold a categorical split pays the kernels' category-mask
contraction and the sorted-subset scan found it. Nothing from a program
without the counter, or before any tree was built."""

LAYER, MOVES, SOURCE = "learner", "train_trees_per_s", "program_counter"
UNIT, BETTER = "%", "lower"

COUNTER = "lgbmtpu_tree_splits_total"


def read(inp):
    from lightgbm_tpu.obs.metrics import default_registry

    series = default_registry().snapshot().get(COUNTER)
    total = sum(series.values()) if series else 0
    if not total:
        return None
    cat = sum(v for labels, v in series.items() if 'kind="cat_' in labels)
    return 100.0 * cat / total
