"""Device time per tree: the union of device-op intervals inside the
window (averaged over the chips) over the trees trained in it."""

LAYER, MOVES, SOURCE = "boosting", "train_trees_per_s", "device_trace"
UNIT, BETTER = "ms", "lower"


def read(inp):
    trees = inp.rec.obs.get("trees")
    if inp.trace is None or not trees:
        return None
    return inp.trace.busy_s() / trees * 1e3
