"""Times per tree that a histogram kernel streams the rows: events of
the device trace whose name matches the histogram kernels' pattern
(``rooflines/hist_round.KERNEL_PATTERN``), over the trees of the window.
Equal to the tree's histogram passes plus the root while no pass needs
more than one call (10 for a 255-leaf tree of the 1, 2, 4, ..., 48, 48,
48, 47 schedule, whose last round routes only); every slot chunk past
the first is one more."""

LAYER, MOVES, SOURCE = "learner", "train_trees_per_s", "device_trace"
UNIT, BETTER = "streams", "lower"


def read(inp):
    trees = inp.rec.obs.get("trees")
    if inp.trace is None or not trees:
        return None
    pattern = inp.plugin("rooflines", "hist_round").KERNEL_PATTERN
    _seconds, events = inp.trace.op_seconds(pattern)
    return events / trees if events else None
