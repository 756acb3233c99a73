"""What the serving loop's own time (``monitor.trace.LoopTime``) costs a
loop step with telemetry on and no trace:

    python benchmarks/loop_time_bench.py [--rows 40 256] [--steps 20000]

One fused step's host skeleton, no model: ``serve.step`` around the seven
phases of a launch and a landing, a commit loop of ``--rows`` rows whose
``record`` and ``on_tokens`` do nothing, a real device array asked
``is_ready()``. Once as the loop was (bare ``span``s, one ``is_ready()`` at
the launch) and once as it is (``LoopTime``'s phases, its polls, the sampled
commit step, the publication every ``_LOOP_PUBLISH_STEPS`` steps), the
latter twice: with the step in flight seen finished at the first poll (a
host-bound loop: one query a step) and never (a device-bound loop: a query
at every phase's exit and every 32 rows of the commit loop). Prints the
difference a step in microseconds, the median of ``--repeats`` passes, and
one publication's own length. Runs wherever jax does (the numbers that
count are the machine's that serves: run it through ``chiprun``)."""

import argparse
import os
import statistics
import sys
import time
import timeit
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax.numpy as jnp  # noqa: E402

from deepspeed_tpu.inference.engine import _ServeSession  # noqa: E402
from deepspeed_tpu.inference.scheduler import ServingTelemetry  # noqa: E402
from deepspeed_tpu.inference.serve import _LOOP_PUBLISH_STEPS  # noqa: E402
from deepspeed_tpu.monitor.metrics import MetricsRegistry  # noqa: E402
from deepspeed_tpu.monitor.trace import LoopTime, span  # noqa: E402

LAUNCH = ("schedule", "inputs", "dispatch", "sample")


def nothing(*args):
    return None


class NotYet:
    """A step's tokens that are asked for real and never there."""

    def __init__(self, tok):
        self.tok = tok

    def is_ready(self):
        self.tok.is_ready()
        return False


def bare(steps, rows, tok):
    """The loop's spans as they were before the counters."""
    t0 = time.perf_counter_ns()
    for _ in range(steps):
        with span("serve.step"):
            for name in LAUNCH:
                with span("serve." + name):
                    if name == "inputs":
                        tok.is_ready()
            with span("serve.fetch"):
                pass
            with span("serve.commit"):
                for r, tokens in rows:
                    out = nothing(None, r, None, tokens)
                    if out is not None:
                        tokens = out
                    if tokens:
                        nothing(r, tokens)
            with span("serve.release"):
                pass
    return time.perf_counter_ns() - t0


def counted(steps, rows, tok, tel):
    """The same through ``LoopTime``, published as ``_step_once`` does."""
    sess = object.__new__(_ServeSession)
    sess.loop = loop = LoopTime()
    sess.sched, sess.on_tokens = None, nothing
    step = SimpleNamespace(kind=SimpleNamespace(record=nothing), part=None)
    t0 = time.perf_counter_ns()
    for _ in range(steps):
        with loop.step():
            for name in LAUNCH:
                with loop.phase(name):
                    pass
                if name == "inputs":
                    loop.finished
                    before = loop.t
                elif name == "dispatch":
                    loop.launched(True, before)
            loop.watch(tok)
            with loop.phase("fetch"):
                pass
            with loop.phase("commit"):
                sess._commit(step, rows)
            with loop.phase("release"):
                pass
            loop.steps += 1
        if loop.due(_LOOP_PUBLISH_STEPS, idle=False):
            tel.count_loop(loop.take())
    return time.perf_counter_ns() - t0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, nargs="+", default=[40, 256])
    ap.add_argument("--steps", type=int, default=20000)
    ap.add_argument("--repeats", type=int, default=7)
    args = ap.parse_args()
    tok = jnp.zeros((256,), jnp.int32).block_until_ready()
    tel = ServingTelemetry(MetricsRegistry())
    for n in args.rows:
        rows = [(i, [1]) for i in range(n)]
        was, now, busy = [], [], []
        for _ in range(args.repeats):
            was.append(bare(args.steps, rows, tok) / args.steps / 1e3)
            now.append(counted(args.steps, rows, tok, tel) / args.steps / 1e3)
            busy.append(counted(args.steps, rows, NotYet(tok), tel)
                        / args.steps / 1e3)
        base = statistics.median(was)
        print(f"rows {n}: bare spans {base:.2f} us a step; with LoopTime "
              f"{statistics.median(now):.2f} host-bound (added "
              f"{statistics.median(now) - base:.2f}), "
              f"{statistics.median(busy):.2f} device-bound (added "
              f"{statistics.median(busy) - base:.2f}); added by pass: "
              f"{[round(b - a, 2) for a, b in zip(was, now)]} | "
              f"{[round(b - a, 2) for a, b in zip(was, busy)]}")
    loop = LoopTime()
    took = []
    for i in range(2000):
        loop.steps += 16
        for k in loop.ns:
            loop.ns[k] += 1000
        loop.busy_ns += 9000
        loop.cpu_ns += 8000
        t0 = time.perf_counter_ns()
        tel.count_loop(loop.take())
        took.append((time.perf_counter_ns() - t0) / 1e3)
    print(f"one publication (take + count_loop): "
          f"{statistics.median(took):.2f} us, every {_LOOP_PUBLISH_STEPS} "
          f"steps: {statistics.median(took) / _LOOP_PUBLISH_STEPS:.2f} us a "
          "step")
    # the pieces, a call each (us): where a machine's cost comes from
    loop = LoopTime()

    def bare_span():
        with span("serve.commit"):
            pass

    def phase():
        with loop.phase("commit"):
            pass

    def fetch():
        with loop.phase("fetch"):
            pass

    def step():
        with loop.step():
            pass

    pieces = {"is_ready()": tok.is_ready,
              "perf_counter_ns()": time.perf_counter_ns,
              "thread_time_ns()": time.thread_time_ns,
              "a bare span": bare_span, "a phase": phase,
              "the fetch phase": fetch,
              "serve.step (a CPU clock pair one turn in 8)": step}
    for name, fn in pieces.items():
        print(f"{name}: {timeit.timeit(fn, number=100000) * 10:.3f} us")
    loop.watch(NotYet(tok))
    print(f"a phase that asks: {timeit.timeit(phase, number=100000) * 10:.3f}"
          " us")


if __name__ == "__main__":
    main()
