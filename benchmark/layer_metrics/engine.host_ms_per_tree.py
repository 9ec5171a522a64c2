"""Per tree, the part of a job's wall time in which no op ran on the
device: ``lgb.train``'s Booster set-up, dispatch, readback and tree
materialisation. Job spans and device busy time are both read from the
trace, on one clock."""

LAYER, MOVES, SOURCE = "engine", "train_trees_per_s", "device_trace"
UNIT, BETTER = "ms", "lower"


def read(inp):
    trees = inp.rec.obs.get("trees")
    if inp.trace is None or not trees:
        return None
    jobs = inp.trace.spans_named("job")
    if not jobs:
        return None
    wall = sum(e - s for s, e in jobs) / 1e9
    busy = sum(inp.trace.busy_in(s, e) for s, e in jobs)
    return (wall - busy) / trees * 1e3
