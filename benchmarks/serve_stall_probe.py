"""One serving cell of the benchmark, run as the benchmark runs it, with what
its result line leaves out about stalls of the serving loop:

    python benchmarks/serve_stall_probe.py --workload solaropen2_serve_decode \
        --seed N --seconds 51 [--stacks FILE]
        [--trace 1 --keep-trace [--trace-seconds S]]

takes ``perfbench/run.py``'s own arguments (``--stacks`` and
``--trace-seconds``, a trace of another length than the cell's, are its own)
and prints, beside its lines,

* ``stall counters over the window``: the growth of ``host/gc_*``, of
  ``serving/decode_steps{,_ahead,_late}``, of ``serving/prefill_{steps,
  tokens,padded_tokens}`` and of the loop's own time (PR 54:
  ``serving/late_ms`` and ``late_slack_ms``, the TIME the late steps' device
  had nothing queued, as a bracket; ``serving/loop_*`` and
  ``serving/commit_*``, of which the manifest enters only some for the
  mixed cell; PR 55: ``serving/block_*`` of a model that generates by
  blocks, the rows' passes, lone commits, riders and decided tokens, from
  which the positions a token the model really computed are 4 x
  (``block_row_passes`` + ``block_commit_rides``) /
  ``block_decided_tokens``) between the window's marks, and the
  mean full (generation 2) pause, ``host/gc_full_pause_ms`` over
  ``host/gc_full_collections``: the per-layer metrics hold the count and the
  share, no line of the harness holds the pause;
* ``stalls by the load thread's clock``: every hold-up of the load thread
  over 20 ms (it only sleeps 5 ms at a time, so what holds it up held the
  whole process up) with the CPU time the whole process and the serving
  loop's thread spent during it (none: the process did not run at all;
  the loop's: the loop held the interpreter lock), and when
  ``serving/decode_steps_late`` and the collector's totals moved, all in
  seconds of the window: which stalls of the loop are the whole process's;
* with ``--stacks FILE``, ``threads during hold-ups``: the stacks of EVERY
  Python thread taken DURING each hold-up of over 30 ms by ``faulthandler``'s
  watchdog (a C thread that needs no interpreter lock, armed by the load
  thread at every tick and disarmed when the tick returns in time), raw in
  ``FILE`` and counted by their innermost frames: the one thread that is not
  waiting is the one that holds the interpreter lock;
* ``the machine over the window``: growth of the control group's
  ``cpu.stat`` (``nr_throttled``, ``throttled_usec``: the CPU quota stopping
  every thread until its next period), of ``/proc/stat``'s steal ticks and
  of ``/proc/pressure/cpu``;
* with ``--trace 1 --keep-trace``, ``longest device gaps``: the longest idle
  stretches of the busiest device in the kept trace and, for each, what
  every host thread of the profile did across it (the program's ``pb.*``
  spans, ``gc`` among them, and the runtime's own events), so that a stall
  no span names can be followed to a thread; and how many of the trace's
  spans are ``gc`` spans, with their lengths.

``--machine SECONDS`` runs no cell and imports no jax: one thread that sleeps
5 ms at a time for that long and prints every hold-up of over 30 ms with the
CPU time the process spent during it, by the clock since its start. What it
sees is the machine's: nothing else runs in the process.

The run is the benchmark's own but for the reading: ``ServeRunner``'s
``_facts``, ``_snapshot`` and ``_tick`` are wrapped (two clock reads and a
counter read every tick of the load thread).
"""

import collections
import faulthandler
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "perfbench")]

COUNTERS = ("host/gc_pause_ms", "host/gc_full_pause_ms",
            "host/gc_full_collections", "serving/decode_steps",
            "serving/decode_steps_ahead", "serving/decode_steps_late",
            # what a window holds of prompts: a seed's draw of them moves a
            # closed loop's tokens/s (PERF.md section 6, PR 43)
            "serving/prefill_steps", "serving/prefill_tokens",
            "serving/prefill_padded_tokens")
#: the loop's own time (``monitor.trace.LoopTime``), by family, and the
#: block passes of a model that generates by blocks (rows' passes, lone
#: commits, riders, decided tokens: what the manifest's ratios are made of)
LOOP_TIME = ("serving/late_", "serving/loop_", "serving/commit_",
             "serving/block_")


def stall_counters(marks):
    """Growth of :data:`COUNTERS` and of the :data:`LOOP_TIME` families
    between a window's marks (a counter the program does not have is left
    out) and the mean full pause in ms."""
    start, end = marks["start"]["counters"], marks["end"]["counters"]
    grown = {k: end[k] - start.get(k, 0.0) for k in COUNTERS if k in end}
    grown.update({k: end[k] - start.get(k, 0.0) for k in sorted(end)
                  if k.startswith(LOOP_TIME)})
    full = grown.get("host/gc_full_collections")
    if full:
        grown["mean_full_pause_ms"] = grown["host/gc_full_pause_ms"] / full
    return grown


def longest_gaps(reduced, path, top=3, prefix="pb."):
    """The ``top`` longest idle stretches of the busiest device of a kept
    trace (``reduced``: the harness's ``trace_reduced.json``, its gaps the
    harness's own) and, for each, the events of every host line of the
    profile at ``path`` that overlap it: ``[line, event, overlap ms,
    count]``, the program's spans first."""
    import trace_reduce
    from jax.profiler import ProfileData
    name = trace_reduce.busiest_device(reduced)
    if name is None:
        return []
    dev = reduced["devices"][name]
    found = sorted(trace_reduce.gaps(dev["ops"] or dev["programs"],
                                     trace_reduce.window_of(reduced)),
                   key=lambda g: -g[1])[:top]
    host = [(line.name, [(e.name.split(" ")[0][:60], e.start_ns / 1e9,
                          e.duration_ns / 1e9) for e in line.events])
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:") for line in plane.lines]
    out = []
    for lo, dur in found:
        across = {}
        for line, evs in host:
            for event, start, length in evs:
                over = min(start + length, lo + dur) - max(start, lo)
                if over > 0:
                    got = across.setdefault((line, event), [0.0, 0])
                    got[0] += over
                    got[1] += 1
        rows = sorted(([ln, ev, s * 1e3, n] for (ln, ev), (s, n)
                       in across.items()),
                      key=lambda r: (not r[1].startswith(prefix), -r[2]))
        out.append({"gap_ms": dur * 1e3, "from_s": lo, "host": rows[:24]})
    return out


def machine():
    """What the operating system counts of time this process wanted and did
    not get: the control group's CPU quota, the hypervisor, the run queue."""
    out = {}
    for path in ("/sys/fs/cgroup/cpu.stat", "/sys/fs/cgroup/cpu/cpu.stat"):
        try:
            with open(path) as f:
                out.update((k, int(v)) for k, v in map(str.split, f))
            break
        except (OSError, ValueError):
            continue
    try:
        with open("/proc/stat") as f:
            out["steal_ticks"] = int(f.readline().split()[8])
        with open("/proc/pressure/cpu") as f:
            out["pressure_some_us"] = int(f.readline().rsplit("=", 1)[1])
    except (OSError, ValueError, IndexError):
        pass
    return out


def stacks_by_frame(text, depth=3, top=8):
    """``faulthandler``'s dumps in ``text``, each as its threads counted by
    their ``depth`` innermost frames: ``[[count, "file:line func < ..."]]``."""
    dumps = []
    for dump in text.split("Timeout (")[1:]:
        threads = collections.Counter()
        for block in dump.split("Thread 0x")[1:]:
            frames = [ln.split('File "', 1)[1] for ln in block.splitlines()
                      if ln.startswith('  File "')]
            threads[" < ".join(
                f.replace('", line ', ":").replace(" in ", " ")
                .rsplit("/", 1)[-1] for f in frames[:depth])] += 1
        dumps.append([[n, where] for where, n in threads.most_common(top)])
    return dumps


#: what the load thread reads at the end of every tick
_Tick = collections.namedtuple(
    "_Tick", "at cpu loop_cpu late full_pause_ns full")


def watch(serve, say, stacks=None):
    """Wrap ``ServeRunner`` so that its load thread also keeps the stalls'
    timeline (module docstring); everything is said when ``_facts`` runs.
    ``stacks``: an open file for the threads' stacks during hold-ups."""
    from deepspeed_tpu.monitor.trace import gc_totals
    facts, snapshot, tick = (serve.ServeRunner._facts,
                             serve.ServeRunner._snapshot,
                             serve.ServeRunner._tick)
    seen = {"machine": [], "holdups": [], "late": [], "gc": [], "last": None,
            "loop_clock": None}

    def snapshot_and_machine(self):
        seen["machine"].append(machine())
        return snapshot(self)

    def tick_and_watch(self, t0, dt, on_clock):
        if seen["loop_clock"] is None:
            seen["loop_clock"] = time.pthread_getcpuclockid(
                self.serving._thread.ident)
        before = seen["last"]
        started = time.perf_counter()
        if stacks is not None:
            faulthandler.dump_traceback_later(dt + 0.030, file=stacks)
        tick(self, t0, dt, on_clock)
        if stacks is not None:
            faulthandler.cancel_dump_traceback_later()
        now = seen["last"] = _Tick(
            self._ticked, time.process_time(),
            time.clock_gettime(seen["loop_clock"]),
            self.registry.counter("serving/decode_steps_late").value,
            *gc_totals()[1:])
        if before is None:
            return
        over = now.at - started - dt
        if over > 0.020:
            seen["holdups"].append({
                "at_s": round(started - t0, 3), "ms": round(over * 1e3, 1),
                "process_cpu_ms": round((now.cpu - before.cpu) * 1e3, 1),
                "loop_cpu_ms": round((now.loop_cpu - before.loop_cpu) * 1e3,
                                     1)})
        if now.late != before.late:
            seen["late"].append([round(now.at - t0, 3),
                                 now.late - before.late])
        if now.full != before.full:
            seen["gc"].append([round(now.at - t0, 3), round(
                (now.full_pause_ns - before.full_pause_ns) / 1e6, 1)])

    def facts_and_stalls(self, t0, t_close, seconds, late, marks):
        say("stall counters over the window: "
            + json.dumps(stall_counters(marks)))
        say("stalls by the load thread's clock: " + json.dumps({
            "holdups_over_20ms": seen["holdups"],
            "late_steps_at_s": seen["late"],
            "full_collections_at_s_ms": seen["gc"]}))
        if stacks is not None:
            stacks.flush()
            with open(stacks.name) as f:
                say("threads during hold-ups: "
                    + json.dumps(stacks_by_frame(f.read())))
        first, last = seen["machine"][0], seen["machine"][-1]
        say("the machine over the window: " + json.dumps(
            {k: last[k] - first[k] for k in last if k in first}))
        return facts(self, t0, t_close, seconds, late, marks)

    serve.ServeRunner._facts = facts_and_stalls
    serve.ServeRunner._snapshot = snapshot_and_machine
    serve.ServeRunner._tick = tick_and_watch


def machine_holdups(seconds, dt=0.005):
    """Hold-ups of an otherwise idle process: ``[at s, ms, process cpu ms]``
    for each sleep of ``dt`` that came back over 30 ms late."""
    out, t0 = [], time.perf_counter()
    now = t0
    while now - t0 < seconds:
        cpu = time.process_time()
        time.sleep(dt)
        late = time.perf_counter() - now - dt
        if late > 0.030:
            out.append([round(now - t0, 3), round(late * 1e3, 1),
                        round((time.process_time() - cpu) * 1e3, 1)])
        now = time.perf_counter()
    return out


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv[:1] == ["--machine"]:
        print("hold-ups of an idle process [at s, ms, process cpu ms]: "
              + json.dumps(machine_holdups(float(argv[1]))), flush=True)
        return 0
    import run
    from runners import serve

    stacks = None
    if "--stacks" in argv:
        at = argv.index("--stacks")
        os.makedirs(os.path.dirname(os.path.abspath(argv[at + 1])),
                    exist_ok=True)
        stacks = open(argv[at + 1], "w")
        del argv[at:at + 2]
    watch(serve, run.say, stacks)
    if "--trace-seconds" in argv:
        # a stall that comes once in many seconds wants a longer trace than
        # the cell's own (whose length the reduction's time is sized for)
        import traffic
        at = argv.index("--trace-seconds")
        seconds = float(argv[at + 1])
        del argv[at:at + 2]
        load = traffic.load
        traffic.load = lambda name: {**load(name), "trace_seconds": seconds}
    rc = run.main(argv)
    if "--keep-trace" in argv:
        import trace_reduce
        kept = os.path.join(ROOT, ".perfbench_out",
                            argv[argv.index("--workload") + 1])
        with open(os.path.join(kept, "trace_reduced.json")) as f:
            reduced = json.load(f)
        run.say("longest device gaps: " + json.dumps(longest_gaps(
            reduced, trace_reduce.find_xplane(os.path.join(kept, "trace")))))
        pauses = [round(d * 1e3, 2) for name, _, d in reduced["host"]
                  if name == "gc"]
        run.say(f"the trace holds {len(reduced['host'])} spans of the "
                f"program, {len(pauses)} of them gc, ms each: "
                + json.dumps(pauses))
    return rc


if __name__ == "__main__":
    sys.exit(main())
