"""Share of the trees' splits on a feature that shares an EFB bundle
column with another (PR 38): the program's counter
``lgbmtpu_tree_bundled_splits_total`` over all kinds of
``lgbmtpu_tree_splits_total{kind}``, both ticked by the fused collect
once per split of every host tree. Process lifetime less what the
driver's capability check built before the run (``sparse_jobs``
observes both totals after it): warm-up and window jobs are all trees
1..8 of a fresh ensemble on the same Dataset, so the rest is the
window's share. Nothing from a program without the counter, or before
any tree was built."""

LAYER, MOVES, SOURCE = "learner", "train_trees_per_s", "program_counter"
UNIT, BETTER = "%", "higher"

BUNDLED = "lgbmtpu_tree_bundled_splits_total"
ALL = "lgbmtpu_tree_splits_total"


def read(inp):
    from lightgbm_tpu.obs.metrics import default_registry

    snap = default_registry().snapshot()
    if BUNDLED not in snap or ALL not in snap:
        return None
    before = inp.rec.obs.get("splits_before_run", {})
    bundled = sum(snap[BUNDLED].values()) - before.get(BUNDLED, 0.0)
    total = sum(snap[ALL].values()) - before.get(ALL, 0.0)
    return 100.0 * bundled / total if total > 0 else None
