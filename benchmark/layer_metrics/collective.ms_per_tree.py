"""Per tree, the time a chip has a collective in flight (all-reduce,
reduce-scatter, all-gather, ...; ops line and async line of the trace
together), averaged over the chips."""

LAYER, MOVES, SOURCE = "parallel", "train_trees_per_s", "device_trace"
UNIT, BETTER = "ms", "lower"


def read(inp):
    trees = inp.rec.obs.get("trees")
    if inp.trace is None or len(inp.trace.devices) < 2 or not trees:
        return None
    return inp.trace.collective_s() / trees * 1e3
