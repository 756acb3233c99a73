"""Device self time of the ops whose name matches ``pattern`` (a regex over
the trace's op names) and, where ``scope`` is given, whose scope matches
that (a regex over the op's ``jax.named_scope`` path: ``"pattern": "",
"scope": "/mlp/"`` is the time under the ``mlp`` scope, whatever the
compiler named the fusions). ``mode``: ``share_of_busy`` (% of the
device's busy time), ``share_of_window`` (% of the traced window) or
``ms_per_call``."""

import trace_reduce

from ._common import device_of, trace_window


def read(params, facts):
    dev = device_of(facts)
    if dev is None:
        return None
    secs, calls = trace_reduce.matching(dev["ops"], params["pattern"],
                                        params.get("scope"))
    mode = params.get("mode", "share_of_busy")
    if mode == "ms_per_call":
        return secs / calls * 1e3 if calls else None
    base = trace_reduce.busy_seconds(dev["ops"]) if mode == "share_of_busy" \
        else trace_window(facts)
    return 100.0 * secs / base if base else None
