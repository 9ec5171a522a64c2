"""Device time per tree that NO program scope covers and no producer
lends a phase to: loop plumbing, the ``while`` / ``conditional`` ops'
own time, compiler-made copies, events that join no embedded
instruction. Reported, never folded into a neighbour. This reader also
logs the whole table (``harness/device_phases.py``): per phase events,
ms per tree, share of device time, time inherited from producers, the
five ops with most self time, the fusions XLA mixed two phases in, and
XLA's own bytes estimate over the time (a LOG line, not a metric); and
the identity the phase metrics keep with what the benchmark already
measures: phases + unscoped + ``learner.hist_ms_per_tree`` =
``boosting.device_ms_per_tree``."""

from benchmark.harness import cellrun, device_phases

LAYER, MOVES, SOURCE = "boosting", "train_trees_per_s", "device_trace"
UNIT, BETTER = "ms", "lower"


def read(inp):
    trees = inp.rec.obs.get("trees")
    table = device_phases.table_for(inp) if trees else None
    if table is None:
        return None
    for line in device_phases.lines(table, trees):
        cellrun.log(line)
    return table.ns(device_phases.UNSCOPED) / 1e6 / trees
