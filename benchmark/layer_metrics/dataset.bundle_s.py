"""Seconds of EFB grouping and encoding of the train and valid sets
(PR 38): the program's counter ``lgbmtpu_dataset_bundle_seconds_total``
(timer scope ``dataset.bundle``: conflicts counted on the
bin-construction sample, then every row encoded into the bundle
columns), less what the driver's capability check spent before the run
(``sparse_jobs`` observes the counter after it). Part of
``dataset.construct_s``. Nothing from a program without the counter."""

LAYER, MOVES, SOURCE = "dataset", "setup_s", "program_counter"
UNIT, BETTER = "s", "lower"

COUNTER = "lgbmtpu_dataset_bundle_seconds_total"


def read(inp):
    from lightgbm_tpu.obs.metrics import default_registry

    series = default_registry().snapshot().get(COUNTER)
    if not series:
        return None
    return sum(series.values()) - inp.rec.obs.get(
        "bundle_s_before_run", 0.0)
