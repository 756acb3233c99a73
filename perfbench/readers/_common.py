"""What several readers share."""

import trace_reduce


def device_of(facts):
    """The busiest device's ops and programs, or None without a trace."""
    trace = facts.get("trace")
    if not trace or not trace["devices"]:
        return None
    return trace["devices"][trace_reduce.busiest_device(trace)]


def trace_window(facts):
    lo, hi = trace_reduce.window_of(facts["trace"])
    return hi - lo
