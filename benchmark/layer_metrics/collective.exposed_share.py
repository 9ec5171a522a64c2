"""Share of the window in which a chip ran a collective and no compute:
collective intervals less the intervals of every other leaf op of that
chip, over the window, averaged over the chips."""

LAYER, MOVES, SOURCE = "parallel", "train_trees_per_s", "device_trace"
UNIT, BETTER = "%", "lower"


def read(inp):
    if inp.trace is None or len(inp.trace.devices) < 2:
        return None
    return 100.0 * inp.trace.exposed_collective_s() / inp.trace.window_s
