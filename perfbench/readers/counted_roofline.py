"""Device time of the ops under a scope, inside the executions of the program
that holds a named kernel, against the least time for operands the PROGRAM
COUNTED, in %.

For a kernel family whose work depends on the data (an MoE layer's grouped
matmuls: how many experts a step touches), shapes alone do not give the
operations and bytes, so the cost function gets counters too. ``scope``: regex
over an op's ``jax.named_scope`` path (the ops whose time is taken, where
they ran inside such an execution: a prefill program's ops under the same
scope are not the decode step's); ``program_contains``: regex over op names
that marks the program (its executions inside the trace are the steps); ``per_execution``: the size of
``dims`` that says how many times one execution runs the scope (``n_layer``);
``operands``: cost-function argument -> program counter, each taken as its
growth over the window per unit of ``per`` (a counter: the same steps, so the
operands are a mean over the window's steps, the time is the traced ones');
``cost``: ``<module>:<function>`` of the shapes with those operands added;
``require``: counters that have to exist for there to be a reading."""

import costs
import trace_reduce

from ._common import cost_function, device_of, window_counters


def _inside(ops, progs):
    """The ops that started inside one of ``progs`` (both sorted by start)."""
    out, j = [], 0
    for op in ops:
        while j < len(progs) and progs[j][1] + progs[j][2] <= op[1]:
            j += 1
        if j < len(progs) and progs[j][1] <= op[1]:
            out.append(op)
    return out


def read(params, facts):
    dev = device_of(facts)
    marks = window_counters(facts, params.get("require", ()))
    if dev is None or marks is None or facts["peak"] is None:
        return None
    start, end = marks
    grew = lambda k: end.get(k, 0.0) - start.get(k, 0.0)  # noqa: E731
    per = grew(params["per"])
    progs = trace_reduce.programs_containing(dev, params["program_contains"])
    inside = _inside(dev["ops"], progs)
    took, calls = trace_reduce.matching(inside, "", params["scope"])
    steps = len(progs)
    if not per or not calls or not steps:
        return None
    operands = {arg: grew(counter) / per
                for arg, counter in params["operands"].items()}
    t, roof = costs.roofline_seconds(
        cost_function(params["cost"])({**facts["shapes"], **operands}),
        facts["peak"])
    runs = steps * facts["dims"][params["per_execution"]]
    print(f"[perfbench] counted roofline {params['cost']}: {operands}, "
          f"{took / runs * 1e3:.3f} ms a run of the scope against "
          f"{t * 1e3:.3f} ms ({roof}-bound), {steps} executions", flush=True)
    return 100.0 * t * runs / took
