"""What several readers share."""

import importlib

import trace_reduce


def cost_function(name):
    """``"<module>:<function>"`` -> that function of a module beside
    ``costs.py``; a bare name is a function of ``costs.py`` itself. Imported
    when a reader first asks, so a cell loads only the cost modules its own
    metrics name."""
    module, _, fn = name.rpartition(":")
    return getattr(importlib.import_module(module or "costs"), fn)


def device_of(facts):
    """The busiest device's ops and programs, or None without a trace."""
    trace = facts.get("trace")
    if not trace or not trace["devices"]:
        return None
    return trace["devices"][trace_reduce.busiest_device(trace)]


def window_counters(facts, require=()):
    """The program's counters when the window opened and when it closed, or
    None where the runner took no marks (a train cell) or where a counter
    under ``require`` is in neither: a program that does not count it yet
    has nothing to read, while a counter that exists and never moved reads
    0."""
    marks = facts["window"].get("marks")
    if not marks or "start" not in marks:
        return None
    start, end = marks["start"]["counters"], marks["end"]["counters"]
    if any(k not in start and k not in end for k in require):
        return None
    return start, end


def trace_window(facts):
    lo, hi = trace_reduce.window_of(facts["trace"])
    return hi - lo
