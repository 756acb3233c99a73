"""Ops matching ``pattern`` (default: collectives) on one device: ``share``
is their busy time over the traced window, ``exposed_share`` the part of it
during which no other op ran on that device, both in %."""

import trace_reduce

from ._common import device_of, trace_window


def read(params, facts):
    dev = device_of(facts)
    if dev is None:
        return None
    pattern = params.get("pattern", trace_reduce.COLLECTIVE)
    leaves = [[n, s, d] for n, s, d, self_s in trace_reduce.self_times(dev["ops"])
              if self_s >= 0.5 * d]           # leaves, not the loops around them
    # a collective's whole span, start to done, is on the async line; the op
    # stream holds only its -start and -done (the wait)
    mine = trace_reduce.spans(leaves, pattern) + \
        trace_reduce.spans(dev.get("async", []), pattern)
    window = trace_window(facts)
    if not window:
        return None
    if params.get("mode", "share") == "share":
        return 100.0 * trace_reduce.total(trace_reduce.union(mine)) / window
    others = trace_reduce.spans(leaves, None, exclude=pattern)
    return 100.0 * trace_reduce.uncovered(mine, others) / window
