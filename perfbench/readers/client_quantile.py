"""Percentile ``q`` (0-100) of a series the load generator took on its own
clock: ``ttft_ms``, ``itl_ms`` or ``late_ms`` (how late each request of an
open loop was sent; a closed loop has none and reads 0)."""

import numpy as np


def read(params, facts):
    client = facts["window"].get("client")
    if client is None:
        return None
    series = client[params["series"]]
    if not len(series):
        return 0.0 if params["series"] == "late_ms" else None
    return float(np.percentile(series, params["q"]))
