"""Executables really compiled inside the measured window: backend
compile events (``analysis.retrace.compile_counters``) less
persistent-cache hits. It must be 0: a run with any is not ``correct``
(a shape was not warmed up)."""

LAYER, MOVES, SOURCE = "compile", "setup_s", "program_counter"
UNIT, BETTER = "compiles", "lower"


def read(inp):
    return inp.rec.obs.get("compiles_in_window")
