"""From a profiler trace to numbers: the one place that reads ``.xplane.pb``.

Two stages, so that the arithmetic can be checked on a small recorded trace
(``tests/data/``) without the profiler:

1. :func:`load_xplane` — ``jax.profiler.ProfileData`` -> a plain dict::

       {"devices": {"/device:TPU:0": {"ops": [[name, start_s, dur_s], ...],
                                      "programs": [[name, start_s, dur_s], ...],
                                      "async": [[name, start_s, dur_s], ...]}},
        "host": [[name, start_s, dur_s], ...]}      # TraceAnnotation spans

   Device ops are the events of the plane's ``XLA Ops`` line, programs those
   of ``XLA Modules``, and ``async`` the ``Async XLA Ops`` line: the whole
   span of an operation that runs beside the op stream, from its ``-start``
   to its ``-done`` (collectives, copies). An op's ``name`` is its own HLO name without the
   ``%`` (``flash_fwd.16``, ``fusion.303``, ``all-gather-start.2``,
   ``transpose_jvp_fused_ce_dw__.1``: a Pallas kernel keeps its ``name=``).
   Times are seconds on the trace's clock.
2. pure functions on those lists: union of busy time, self time by name,
   overlap, idle gaps and what the host was doing in them.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Span = Sequence  # [name, start_s, dur_s]

OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"
ASYNC_LINE = "Async XLA Ops"
COLLECTIVE = r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def load_xplane(path: str, host_prefix: str = "pb.") -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": [], "structure": []}
    device_lines = {OPS_LINE: "ops", ASYNC_LINE: "async", PROGRAMS_LINE: "programs"}
    for plane in data.planes:
        lines = list(plane.lines)
        out["structure"].append({"plane": plane.name,
                                 "lines": [ln.name for ln in lines]})
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            dev = {"ops": [], "programs": [], "async": []}
            for ln in lines:
                if ln.name in device_lines:
                    dev[device_lines[ln.name]] = _events(
                        ln, own_names=ln.name != PROGRAMS_LINE)
            if dev["ops"] or dev["programs"]:
                out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for ln in lines:
                for ev in ln.events:
                    if ev.name.startswith(host_prefix):
                        out["host"].append([ev.name[len(host_prefix):],
                                            ev.start_ns / 1e9,
                                            ev.duration_ns / 1e9])
    out["host"].sort(key=lambda e: e[1])
    return out


def _events(line, own_names: bool = False) -> List[list]:
    evs = []
    for ev in line.events:
        name = ev.name
        if own_names:
            # the trace names a device op by its whole HLO line ("%flash_fwd.16
            # = bf16[...] custom-call(%copy.3, ...)"): keep the op's own name,
            # or a regex for a kernel would also find every op that reads it
            name = name.split(" = ", 1)[0].lstrip("%")
        evs.append([name, ev.start_ns / 1e9, ev.duration_ns / 1e9])
    evs.sort(key=lambda e: (e[1], -e[2]))
    return evs


# ----------------------------------------------------------------------- #
# pure reductions


def window_of(trace: dict) -> Tuple[float, float]:
    """First start to last end over every device op and program."""
    lo, hi = float("inf"), float("-inf")
    for dev in trace["devices"].values():
        for evs in (dev["ops"], dev["programs"]):
            for _, s, d in evs:
                lo, hi = min(lo, s), max(hi, s + d)
    return (lo, hi) if lo < hi else (0.0, 0.0)


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def total(intervals: Iterable[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


def spans(events: Iterable[Span], pattern: Optional[str] = None,
          exclude: Optional[str] = None) -> List[Tuple[float, float]]:
    inc = re.compile(pattern) if pattern else None
    exc = re.compile(exclude) if exclude else None
    return [(s, s + d) for name, s, d in events
            if (inc is None or inc.search(name))
            and (exc is None or not exc.search(name))]


def busy_seconds(events: Iterable[Span]) -> float:
    """Seconds in which at least one of ``events`` ran."""
    return total(union(spans(events)))


def self_times(events: Sequence[Span]) -> List[list]:
    """``[name, start, dur, self]`` per event: ``self`` is its duration less
    the part its children (events nested inside it on the same line, such as
    the body of a ``while``) cover. ``events`` sorted by (start, -dur)."""
    out = [[n, s, d, d] for n, s, d in events]
    stack: List[list] = []
    for ev in out:
        while stack and ev[1] >= stack[-1][1] + stack[-1][2] - 1e-12:
            stack.pop()
        if stack:
            stack[-1][3] -= min(ev[2], stack[-1][1] + stack[-1][2] - ev[1])
        stack.append(ev)
    for ev in out:
        ev[3] = max(ev[3], 0.0)
    return out


def sum_by_name(events: Sequence[Span]) -> Dict[str, list]:
    """``{name: [self seconds, calls]}``."""
    acc: Dict[str, list] = {}
    for name, _, _, self_s in self_times(events):
        got = acc.setdefault(name, [0.0, 0])
        got[0] += self_s
        got[1] += 1
    return acc


def matching(events: Sequence[Span], pattern: str) -> Tuple[float, int]:
    """Self seconds and calls of the events whose name matches."""
    rx = re.compile(pattern)
    secs, calls = 0.0, 0
    for name, _, _, self_s in self_times(events):
        if rx.search(name):
            secs += self_s
            calls += 1
    return secs, calls


def uncovered(a: Iterable[Tuple[float, float]],
              b: Iterable[Tuple[float, float]]) -> float:
    """Seconds of the union of ``a`` during which nothing of ``b`` ran."""
    ua, ub = union(a), union(b)
    out, j = 0.0, 0
    for s, e in ua:
        cur = s
        while j < len(ub) and ub[j][1] <= cur:
            j += 1
        k = j
        while k < len(ub) and ub[k][0] < e:
            if ub[k][0] > cur:
                out += ub[k][0] - cur
            cur = max(cur, ub[k][1])
            k += 1
        if cur < e:
            out += e - cur
    return out


def gaps(events: Iterable[Span], window: Tuple[float, float],
         min_gap: float = 0.0) -> List[Tuple[float, float]]:
    """(start, dur) of the stretches of ``window`` in which no event ran."""
    lo, hi = window
    out, cur = [], lo
    for s, e in union(spans(events)):
        if s > cur and s - cur >= min_gap:
            out.append((cur, min(s, hi) - cur))
        cur = max(cur, e)
        if cur >= hi:
            break
    if hi > cur and hi - cur >= min_gap:
        out.append((cur, hi - cur))
    return out


def attribute(gap_list: Iterable[Tuple[float, float]],
              host: Sequence[Span]) -> Dict[str, float]:
    """Idle seconds by what the host was doing: each gap goes, piece by
    piece, to the host spans that overlap it (the innermost where they nest:
    later-starting wins); what no span covers is ``unattributed``."""
    acc: Dict[str, float] = {}
    host = sorted(host, key=lambda e: (e[1], -e[2]))
    for gs, gd in gap_list:
        ge = gs + gd
        covered: List[Tuple[float, float]] = []
        # innermost first: later-starting spans claim their part first
        for name, s, d in reversed(host):
            lo, hi = max(s, gs), min(s + d, ge)
            if hi <= lo:
                continue
            piece = uncovered([(lo, hi)], covered)
            if piece > 0:
                acc[name] = acc.get(name, 0.0) + piece
                covered.append((lo, hi))
        rest = gd - total(union(covered))
        if rest > 1e-12:
            acc["unattributed"] = acc.get("unattributed", 0.0) + rest
    return acc


def busiest_device(trace: dict) -> Optional[str]:
    best, best_s = None, -1.0
    for name, dev in trace["devices"].items():
        s = busy_seconds(dev["ops"] or dev["programs"])
        if s > best_s:
            best, best_s = name, s
    return best


def programs_containing(dev: dict, pattern: str) -> List[Span]:
    """The executions (``XLA Modules`` events) inside which an op matching
    ``pattern`` ran: how a program is told from another when they share a
    name, as jitted lambdas do."""
    rx = re.compile(pattern)
    marks = sorted(s for name, s, _ in dev["ops"] if rx.search(name))
    out, j = [], 0
    for prog in dev["programs"]:
        _, s, d = prog
        while j < len(marks) and marks[j] < s:
            j += 1
        if j < len(marks) and marks[j] < s + d:
            out.append(prog)
    return out


def breakdown(trace: dict, top: int = 10) -> dict:
    """The contract's ``breakdown``: the device operations that took most
    self time on the busiest device, and idle seconds by host activity."""
    name = busiest_device(trace)
    if name is None:
        return {"device_ops": [], "idle_gaps": []}
    dev = trace["devices"][name]
    ops = dev["ops"] or dev["programs"]
    by = sorted(((k, v[0]) for k, v in sum_by_name(ops).items()),
                key=lambda kv: -kv[1])[:top]
    idle = attribute(gaps(ops, window_of(trace)), trace["host"])
    by_gap = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in by],
            "idle_gaps": [[k, v] for k, v in by_gap]}
