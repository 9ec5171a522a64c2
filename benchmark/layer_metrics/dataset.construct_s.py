"""Seconds in ``Dataset.construct()`` of the train and valid sets (host
NumPy binning, then nothing on the device yet)."""

LAYER, MOVES, SOURCE = "dataset", "setup_s", "host_clock"
UNIT, BETTER = "s", "lower"


def read(inp):
    return inp.rec.seconds("dataset.construct") or None
