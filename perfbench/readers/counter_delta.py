"""Growth of one counter over the window. ``source``: ``program`` (the
metrics registry; serve cells) or ``bench`` (the harness's own counts:
``compiles_in_window``, ``compile_cache_misses`` during set-up)."""


def read(params, facts):
    if params.get("source", "program") == "bench":
        return float(facts["bench"][params["counter"]])
    marks = facts["window"].get("marks")
    if not marks or "start" not in marks:
        return None
    name = params["counter"]
    return float(marks["end"]["counters"].get(name, 0.0)
                 - marks["start"]["counters"].get(name, 0.0))
