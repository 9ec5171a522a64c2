"""The histogram kernels' share of their roofline: the least time the
chip could take for what the ALGORITHM needs per pass (the larger of 3
adds per (row, feature) over the MXU peak and one read of each row's
int32 bins and gradient triple over 819 GB/s; on a v5e the bytes bound
it) over kernel time. ``benchmark/rooflines/hist_round.py`` holds the
arithmetic."""

LAYER, MOVES, SOURCE = "learner", "train_trees_per_s", "device_trace"
UNIT, BETTER = "%", "higher"


def read(inp):
    obs = inp.rec.obs
    if inp.trace is None or "rows" not in obs:
        return None
    roof = inp.plugin("rooflines", "hist_round")
    passes = roof.kernel_passes(inp.trace, obs["features"], obs["bins"])
    if not passes:
        return None
    floor, _bound = roof.floor_seconds(
        obs["rows"] // obs["chips"], obs["features"], inp.peaks,
        obs["hist_dtype"])
    return 100.0 * floor * len(passes) / sum(d for _, d in passes)
