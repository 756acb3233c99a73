"""From a profiler trace to numbers: the one place that reads ``.xplane.pb``.

Two stages, so that the arithmetic can be checked on a small recorded trace
(``tests/data/``) without the profiler:

1. :func:`load_xplane` — ``jax.profiler.ProfileData`` -> a plain dict::

       {"devices": {"/device:TPU:0": {"ops": [[name, start_s, dur_s, scope], ...],
                                      "programs": [[name, start_s, dur_s], ...],
                                      "async": [[name, start_s, dur_s, scope], ...]}},
        "host": [[name, start_s, dur_s], ...]}      # TraceAnnotation spans

   Device ops are the events of the plane's ``XLA Ops`` line, programs those
   of ``XLA Modules``, and ``async`` the ``Async XLA Ops`` line: the whole
   span of an operation that runs beside the op stream, from its ``-start``
   to its ``-done`` (collectives, copies). An op's ``name`` is its own HLO name without the
   ``%`` (``flash_fwd.16``, ``fusion.303``, ``all-gather-start.2``,
   ``transpose_jvp_fused_ce_dw__.1``: a Pallas kernel keeps its ``name=``).
   Its ``scope`` is where the program's source put it: the ``tf_op`` stat of
   the op's metadata, which is the HLO ``op_name`` (``jit(paged_decode)/
   while/body/closed_call/mlp/dot_general``: every ``jax.named_scope`` around
   it, then the primitive), or "" where the trace holds none. A compiler's
   ``fusion.142`` reads as ``mlp`` through it. ``ProfileData`` shows an
   event's own stats only, so :func:`op_scopes` takes the metadata's from
   the file's bytes. Times are seconds on the trace's clock.
2. pure functions on those lists: union of busy time, self time by name,
   overlap, idle gaps and what the host was doing in them.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Span = Sequence  # [name, start_s, dur_s] (+ [scope] for a device op)
SCOPE_STAT = "tf_op"

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
    try:
        scopes = op_scopes(path)
    except (ValueError, IndexError, KeyError, UnicodeDecodeError) as e:
        # a scope is a note on an op: a file laid out in a way this reader
        # does not know costs the scopes, not the run
        scopes = {}
        out["structure"].append({"scopes": repr(e)})
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
                        ln, own_names=ln.name != PROGRAMS_LINE,
                        scopes=scopes.get(plane.name, {}))
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


def _events(line, own_names: bool = False,
            scopes: Optional[Dict[str, str]] = None) -> List[list]:
    evs = []
    for ev in line.events:
        span = [ev.name, ev.start_ns / 1e9, ev.duration_ns / 1e9]
        if own_names:
            # the trace names a device op by its whole HLO line ("%flash_fwd.16
            # = bf16[...] custom-call(%copy.3, ...)"): keep the op's own name,
            # or a regex for a kernel would also find every op that reads it;
            # its scope is filed under the whole line
            span[0] = ev.name.split(" = ", 1)[0].lstrip("%")
            span.append((scopes or {}).get(ev.name, ""))
        evs.append(span)
    evs.sort(key=lambda e: (e[1], -e[2]))
    return evs


def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf) -> Iterable[Tuple[int, object]]:
    """``(number, value)`` of each field of one protobuf message: an int for
    a varint, a view of its bytes for a length-delimited field (a string or a
    message). Fields of fixed width are passed over."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield key >> 3, value


def op_scopes(path: str) -> Dict[str, Dict[str, str]]:
    """``{device plane: {event name: scope}}`` from the file itself. The
    field numbers are those of ``xplane.proto``: ``XSpace.planes`` 1;
    ``XPlane.name`` 2, ``.event_metadata`` 4, ``.stat_metadata`` 5 (maps: the
    value is field 2 of an entry); ``XEventMetadata.name`` 2, ``.stats`` 5;
    ``XStatMetadata.id`` 1, ``.name`` 2; ``XStat.metadata_id`` 1,
    ``.str_value`` 5, ``.bytes_value`` 6, ``.ref_value`` 7 (a stat metadata's
    name as the value). Two programs can hold an op of the same text: the
    first scope seen stands."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    text = lambda view: bytes(view).decode()
    out = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, events, stat_names = "", [], {}
        for number, value in _fields(plane):
            if number == 2:
                name = text(value)
            elif number == 4:
                events.append(dict(_fields(value))[2])
            elif number == 5:
                stat = dict(_fields(dict(_fields(value))[2]))
                stat_names[stat.get(1, 0)] = text(stat.get(2, b""))
        if not name.startswith("/device:"):
            continue
        scopes = out.setdefault(name, {})
        for event in events:
            event_name = scope = ""
            for number, value in _fields(event):
                if number == 2:
                    event_name = text(value)
                elif number == 5:
                    stat = dict(_fields(value))
                    if stat_names.get(stat.get(1, 0)) != SCOPE_STAT:
                        continue
                    held = stat.get(5, stat.get(6))
                    scope = text(held) if held is not None \
                        else stat_names.get(stat.get(7), "")
            if scope:
                # xprof writes "<op_name>:<op_type>"; jax gives no type
                scopes.setdefault(event_name, scope.rstrip(":"))
    return out


# ----------------------------------------------------------------------- #
# pure reductions


def window_of(trace: dict) -> Tuple[float, float]:
    """First start to last end over every device op and program."""
    lo, hi = float("inf"), float("-inf")
    for dev in trace["devices"].values():
        for evs in (dev["ops"], dev["programs"]):
            for ev in evs:
                lo, hi = min(lo, ev[1]), max(hi, ev[1] + ev[2])
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
    return [(ev[1], ev[1] + ev[2]) for ev in events
            if (inc is None or inc.search(ev[0]))
            and (exc is None or not exc.search(ev[0]))]


def busy_seconds(events: Iterable[Span]) -> float:
    """Seconds in which at least one of ``events`` ran."""
    return total(union(spans(events)))


def self_times(events: Sequence[Span]) -> List[list]:
    """``[name, start, dur, self]`` per event: ``self`` is its duration less
    the part its children (events nested inside it on the same line, such as
    the body of a ``while``) cover. ``events`` sorted by (start, -dur)."""
    out = [[ev[0], ev[1], ev[2], ev[2]] for ev in events]
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


def scope_of(ev: Span) -> str:
    return ev[3] if len(ev) > 3 else ""


def matching(events: Sequence[Span], pattern: str,
             scope: Optional[str] = None) -> Tuple[float, int]:
    """Self seconds and calls of the events whose name matches ``pattern``
    and, where ``scope`` is given, whose scope matches that."""
    rx = re.compile(pattern)
    sx = re.compile(scope) if scope else None
    secs, calls = 0.0, 0
    for ev, (name, _, _, self_s) in zip(events, self_times(events)):
        if rx.search(name) and (sx is None or sx.search(scope_of(ev))):
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
    marks = sorted(ev[1] for ev in dev["ops"] if rx.search(ev[0]))
    out, j = [], 0
    for prog in dev["programs"]:
        _, s, d = prog
        while j < len(marks) and marks[j] < s:
            j += 1
        if j < len(marks) and marks[j] < s + d:
            out.append(prog)
    return out


def largest_ops(ops: Sequence[Span], top: int = 10) -> List[list]:
    """``[name, self seconds, calls, scope]`` of the ``top`` ops by self
    time; the scope is that of the name's first event."""
    scopes: Dict[str, str] = {}
    for ev in ops:
        scopes.setdefault(ev[0], scope_of(ev))
    by = sorted(sum_by_name(ops).items(), key=lambda kv: -kv[1][0])[:top]
    return [[name, secs, calls, scopes[name]] for name, (secs, calls) in by]


def breakdown(trace: dict, top: int = 10) -> dict:
    """The contract's ``breakdown``: the device operations that took most
    self time on the busiest device, and idle seconds by host activity."""
    name = busiest_device(trace)
    if name is None:
        return {"device_ops": [], "idle_gaps": []}
    dev = trace["devices"][name]
    ops = dev["ops"] or dev["programs"]
    idle = attribute(gaps(ops, window_of(trace)), trace["host"])
    by_gap = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[op, secs] for op, secs, _, _
                           in largest_ops(ops, top)],
            "idle_gaps": [[k, v] for k, v in by_gap]}
