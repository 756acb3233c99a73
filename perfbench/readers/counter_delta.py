"""Growth of one counter over the window. ``source``: ``program`` (the
metrics registry; serve cells) or ``bench`` (the harness's own counts:
``compiles_in_window``, ``compile_cache_misses`` during set-up).
``require`` lists counters of the program that have to exist for there to be
a reading at all."""

from ._common import window_counters


def read(params, facts):
    if params.get("source", "program") == "bench":
        return float(facts["bench"][params["counter"]])
    marks = window_counters(facts, params.get("require", ()))
    if marks is None:
        return None
    start, end = marks
    name = params["counter"]
    return float(end.get(name, 0.0) - start.get(name, 0.0))
