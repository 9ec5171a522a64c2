"""Device time per tree outside the round's Pallas calls: the busy union
less the time of the histogram kernels and the routing pass. What is
left is the split search over features x bins per child, the sibling
subtraction and the pool's scatters, the gradients, leaf renewal, the
valid set's traversal and the AUC: XLA fusions without stable names
(device-side marks around the split search: the next tracing issue)."""

LAYER, MOVES, SOURCE = "learner", "train_trees_per_s", "device_trace"
UNIT, BETTER = "ms", "lower"


def read(inp):
    trees = inp.rec.obs.get("trees")
    if inp.trace is None or not trees:
        return None
    pattern = inp.plugin("rooflines", "hist_blocked").ROUND_CALLS_PATTERN
    seconds, events = inp.trace.op_seconds(pattern)
    if not events:
        return None
    return (inp.trace.busy_s() - seconds) / trees * 1e3
