"""How full the histogram kernels keep the MXU: multiply-adds ISSUED by
the one-hot formulation (``slots x 3 x rows x features x bins`` per
pass, slots read from each call's shapes in the trace) over the peak of
the kernel's operand type (bf16: 197 TF/s, int8: 393 TOP/s on a v5e)
over kernel time. High here means the schedule is good, not that the
algorithm needs the work: ``learner.hist_round_roofline`` is the share
of what the histogram needs."""

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
    rows = obs["rows"] // obs["chips"]
    flops = sum(roof.issued_flops(rows, obs["features"], obs["bins"], s)
                for s, _ in passes)
    peak = roof.peak_ops(inp.peaks, obs["hist_dtype"])
    return 100.0 * flops / peak / sum(d for _, d in passes)
