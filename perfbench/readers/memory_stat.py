"""``device.memory_stats()[stat]`` after the window, the highest over the
chips the cell used, times ``scale`` (1e-9: GB)."""


def read(params, facts):
    vals = [m[params["stat"]] for m in facts["memory"].values()
            if params["stat"] in m]
    return max(vals) * params.get("scale", 1.0) if vals else None
