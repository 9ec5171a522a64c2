"""Histogram-reduce payload per tree as the program estimates it when it
dispatches (``lgbmtpu_collective_wire_bytes_total``)."""

LAYER, MOVES, SOURCE = "parallel", "train_trees_per_s", "program_counter"
UNIT, BETTER = "MB", "lower"


def read(inp):
    obs = inp.rec.obs
    if not obs.get("trees") or not obs.get("wire_bytes"):
        return None
    return obs["wire_bytes"] / obs["trees"] / 1e6
