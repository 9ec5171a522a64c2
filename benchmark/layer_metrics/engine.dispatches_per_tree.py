"""Executable launches of the fused loop per tree
(``gbdt.fused_dispatch_count``): 0.25 while every dispatch is a 4-round
scan chunk."""

LAYER, MOVES, SOURCE = "engine", "train_trees_per_s", "program_counter"
UNIT, BETTER = "dispatches", "lower"


def read(inp):
    obs = inp.rec.obs
    if not obs.get("trees") or "fused_dispatches" not in obs:
        return None
    return obs["fused_dispatches"] / obs["trees"]
