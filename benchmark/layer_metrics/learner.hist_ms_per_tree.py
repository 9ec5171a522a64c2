"""Per tree, the device time of the slot-packed histogram kernels
(``_round_kernel`` + ``_nat_kernel``), summed over their events in the
trace and averaged over the chips."""

LAYER, MOVES, SOURCE = "learner", "train_trees_per_s", "device_trace"
UNIT, BETTER = "ms", "lower"


def read(inp):
    trees = inp.rec.obs.get("trees")
    if inp.trace is None or not trees:
        return None
    pattern = inp.plugin("rooflines", "hist_round").KERNEL_PATTERN
    seconds, events = inp.trace.op_seconds(pattern)
    return seconds / trees * 1e3 if events else None
