"""Share of the traced window (%) in which the device was idle while the
host was inside the span ``annotation`` (a ``pb.<name>`` TraceAnnotation of
the runner; ``unattributed`` for idle time no span covers)."""

import trace_reduce

from ._common import device_of, trace_window


def read(params, facts):
    dev = device_of(facts)
    if dev is None:
        return None
    window = trace_reduce.window_of(facts["trace"])
    idle = trace_reduce.attribute(
        trace_reduce.gaps(dev["ops"] or dev["programs"], window),
        facts["trace"]["host"])
    span = trace_window(facts)
    return 100.0 * idle.get(params["annotation"], 0.0) / span if span else None
