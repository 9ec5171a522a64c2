"""How full the histogram kernels keep the MXU, at any table width:
multiply-adds ISSUED per tree by the one-hot formulation, counted from
the program's own gauges and counters (slots per call, calls per pass,
feature blocks x the columns each block really multiplies, rounds per
ladder width: ``benchmark/rooflines/hist_blocked.py``), times the
window's trees, over the peak of the operand type over the kernels'
time in the trace. The definition of ``learner.hist_round_mxu_share``
without its shape reader. Nothing where the program exports no such
gauges."""

LAYER, MOVES, SOURCE = "learner", "train_trees_per_s", "device_trace"
UNIT, BETTER = "%", "higher"


def read(inp):
    obs = inp.rec.obs
    if inp.trace is None or "rows" not in obs or not obs.get("trees"):
        return None
    blocked = inp.plugin("rooflines", "hist_blocked")
    schedule = blocked.read_schedule()
    if schedule is None:
        return None
    roof = inp.plugin("rooflines", "hist_round")
    seconds, events = inp.trace.op_seconds(roof.KERNEL_PATTERN)
    if not events:
        return None
    flops = obs["trees"] * blocked.issued_flops_per_tree(
        schedule, obs["rows"] // obs["chips"], obs["bins"],
        blocked.channels_of(obs["hist_dtype"]))
    return 100.0 * flops / roof.peak_ops(inp.peaks, obs["hist_dtype"]) \
        / seconds
