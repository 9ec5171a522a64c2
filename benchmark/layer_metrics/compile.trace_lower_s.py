"""Seconds this process spent tracing functions to jaxprs and lowering
them to MLIR, from the durations JAX hands the program's listener
(``analysis.retrace.compile_counters``: ``trace_s + lower_s``, counted
from the driver's first line to the moment this reader runs; the audit
after the window is plain NumPy and traces nothing). Paid on every run,
whatever the persistent cache holds. A program without the duration
counters yields nothing."""

LAYER, MOVES, SOURCE = "compile", "setup_s", "program_counter"
UNIT, BETTER = "s", "lower"


def read(inp):
    from lightgbm_tpu.analysis.retrace import compile_counters

    c = compile_counters()
    if "trace_s" not in c or "lower_s" not in c:
        return None
    return c["trace_s"] + c["lower_s"]
