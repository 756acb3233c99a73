"""A kernel family's share of its roofline, in %: the least time the chip
could take for the calls that were made over the device time they took.
``kernels`` maps a regex over op names to a cost function, ``<function>`` of
``costs.py`` or ``<module>:<function>`` of a module beside it; each call is
taken to cover one chip's micro-batch (``facts["shapes"]``). Which roof
bounds each kernel goes on a detail line."""

import costs
import trace_reduce

from ._common import cost_function, device_of


def read(params, facts):
    dev = device_of(facts)
    if dev is None or facts["peak"] is None:
        return None
    least = took = 0.0
    for pattern, fn in params["kernels"].items():
        secs, calls = trace_reduce.matching(dev["ops"], pattern)
        if not calls:
            continue
        t, roof = costs.roofline_seconds(
            cost_function(fn)(facts["shapes"]), facts["peak"])
        print(f"[perfbench] roofline {fn}: {calls} calls, "
              f"{secs / calls * 1e3:.3f} ms a call against {t * 1e3:.3f} ms "
              f"({roof}-bound)", flush=True)
        least += t * calls
        took += secs
    return 100.0 * least / took if took else None
