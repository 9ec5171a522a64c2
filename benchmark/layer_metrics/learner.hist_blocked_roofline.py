"""The histogram kernels' share of their roofline, at any table width:
per kernel call the least time the chip could take for what the
ALGORITHM needs (the larger of 3 adds per (row, column) over the MXU
peak and one read of each row's int32 bins and gradient triple over 819
GB/s: ``rooflines/hist_round.floor_seconds``) over the kernels' time in
the trace. ``learner.hist_round_roofline``'s definition; listed where
that metric's shape reader finds nothing (3-D outputs), and only where
the program exports the schedule gauges that say a call covers the
whole table (``rooflines/hist_blocked.py``)."""

LAYER, MOVES, SOURCE = "learner", "train_trees_per_s", "device_trace"
UNIT, BETTER = "%", "higher"


def read(inp):
    obs = inp.rec.obs
    if inp.trace is None or "rows" not in obs:
        return None
    if inp.plugin("rooflines", "hist_blocked").read_schedule() is None:
        return None
    roof = inp.plugin("rooflines", "hist_round")
    seconds, events = inp.trace.op_seconds(roof.KERNEL_PATTERN)
    if not events:
        return None
    floor, _bound = roof.floor_seconds(
        obs["rows"] // obs["chips"], obs["features"], inp.peaks,
        obs["hist_dtype"])
    return 100.0 * floor * events / seconds
