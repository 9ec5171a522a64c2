"""Seconds of the warm-up ``lgb.train``: trace + compile, or a load from
the persistent cache, plus one job's rounds."""

LAYER, MOVES, SOURCE = "compile", "setup_s", "host_clock"
UNIT, BETTER = "s", "lower"


def read(inp):
    return inp.rec.seconds("first_train") or None
