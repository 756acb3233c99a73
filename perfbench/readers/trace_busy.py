"""Share of the traced window in which no operation ran on the (busiest)
device: 100 x (1 - union of device-op intervals / window)."""

import trace_reduce

from ._common import device_of, trace_window


def read(params, facts):
    dev = device_of(facts)
    if dev is None:
        return None
    window = trace_window(facts)
    busy = trace_reduce.busy_seconds(dev["ops"] or dev["programs"])
    return 100.0 * (1.0 - busy / window) if window else None
