"""Device time of the program executions (``XLA Modules`` events) inside
which an op matching ``contains`` ran — how the decode program is told from
a prefill program when both are jitted lambdas of one name. ``mode``:
``median_ms`` of one execution, or ``ms_per_ktoken``: their total over the
prompt tokens (in thousands) whose first token reached a client inside the
traced window."""

import statistics

import trace_reduce

from ._common import device_of


def read(params, facts):
    dev = device_of(facts)
    if dev is None:
        return None
    progs = trace_reduce.programs_containing(dev, params["contains"])
    if not progs:
        return None
    if params.get("mode", "median_ms") == "median_ms":
        return statistics.median(d for _, _, d in progs) * 1e3
    lo, hi = facts["window"].get("trace_host_window", (None, None))
    if lo is None:
        return None
    tokens = sum(n for t, n in facts["window"]["first_tokens"] if lo <= t < hi)
    return sum(d for _, _, d in progs) * 1e3 / (tokens / 1e3) if tokens else None
