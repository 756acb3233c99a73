"""The KDA decode state update alone on a TPU, by live rows.

    python benchmarks/kda_decode_bench.py [--live 1 16 64 128] [--phase-rows 1 2 8]

One call is a decode step's state update of ONE linear-attention layer at
the ``solaropen2_serve_decode`` cell's widths: 128 rows, 64 heads, a float32
128 x 128 state a head, 129 slots. ``kernel`` is
``ops/pallas/kda_decode_update.py`` (each live row's state read once and
written once, addressed row -> slot), ``twin`` the plain-XLA form the program
takes off a TPU (``models/state_mixers.py`` ``_kda_slot_update``: all 129
slots in slot order, read twice and written once). Each is timed at 1, 16,
64 and 128 live rows of 128, the others idle on the dummy slot: the only
place the low-occupancy saving is measured, since no cell serves a stateful
model under an open loop (PERF.md section 7). ``--phase-rows`` times the
kernel at those rows a phase (``state_phases.phase_rows`` is what the
program takes).

The time is the device's: a program of ``LAYERS`` updates of one pool, its
``XLA Modules`` event in a profiler trace over ``LAYERS`` (so the vectors'
preparation counts), and beside it the kernel's own events. ``GB/s`` is the
LIVE rows' state read once and written once over that time. Each line also
checks the live rows' ``o`` and the pool past the dummy against the twin's. The numbers behind
``state_phases._PHASE_BYTES`` (PERF.md section 6, PR 32). TPU only: the script
refuses to print a time from another backend.
"""

import argparse
import importlib
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "perfbench")]

import jax
import jax.numpy as jnp
import numpy as np

import trace_reduce

ROWS, HEADS, DK, DV, SLOTS = 128, 64, 128, 128, 129
LAYERS = 4          # updates a program, one pool


def draw(seed, live):
    """The vectors of ``LAYERS`` steps and the rows' slots: ``live`` rows on
    distinct slots in no order, the rest on the dummy."""
    r = np.random.default_rng(seed)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    shape = (LAYERS, ROWS, HEADS)
    vecs = dict(
        qh=unit(r.standard_normal((*shape, DK))) * DK ** -0.5,
        kh=unit(r.standard_normal((*shape, DK))),
        v=r.standard_normal((*shape, DV)),
        g=-1.6 * r.random((*shape, DK)),
        beta=2 * r.random(shape))
    slots = np.zeros(ROWS, np.int32)
    slots[r.choice(ROWS, live, replace=False)] = \
        r.permutation(np.arange(1, SLOTS))[:live]
    return {k: jnp.asarray(a, jnp.float32) for k, a in vecs.items()}, \
        jnp.asarray(slots)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--live", type=int, nargs="+", default=[1, 16, 64, 128])
    ap.add_argument("--phase-rows", type=int, nargs="*", default=[])
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=3200000001)
    args = ap.parse_args()
    platform = jax.devices()[0].platform
    if platform != "tpu":
        sys.exit(f"kda_decode_bench: the default device is {platform!r}, "
                 "not a TPU: no time is taken")
    here = importlib.import_module("deepspeed_tpu.ops.pallas.kda_decode_update")
    from deepspeed_tpu.models.state_mixers import _kda_slot_update
    from deepspeed_tpu.ops.pallas import state_phases
    phase_rows = state_phases.phase_rows
    variants = [("twin", None), ("kernel", None)]
    variants += [(f"kernel_r{n}", n) for n in args.phase_rows]

    def program(label):
        def update(state, x, slots):
            step = (x["qh"], x["kh"], x["v"], x["g"], x["beta"], slots, 0)
            if label == "twin":
                return _kda_slot_update(state, *step, SLOTS)
            return here.kda_decode_update(state, *step)

        def stack(state, vecs, slots):
            def one(state, x):
                o, state = update(state, x, slots)
                return state, o
            state, o = jax.lax.scan(one, state, vecs)
            return o, state
        return stack

    pool0 = jax.random.normal(jax.random.key(args.seed % (1 << 31)),
                              (SLOTS, HEADS, DK, DV), jnp.float32)
    runs = {}
    for live in args.live:
        vecs, slots = draw(args.seed + live, live)
        want = None
        for label, n in variants:
            state_phases.phase_rows = (lambda *a, n=n: n) if n else phase_rows
            stack = program(label)
            stack.__name__ = f"kda_{live}_{label}"
            run = jax.jit(stack, donate_argnums=(0,))
            o, pool = jax.block_until_ready(run(pool0 + 0.0, vecs, slots))
            on = np.asarray(slots) != 0
            # the twin also steps the dummy, on some idle row's vectors
            got = (np.asarray(o)[:, on], np.asarray(pool)[1:])
            want = want or got
            err = max(float(np.abs(a - b).max() / np.abs(b).max())
                      for a, b in zip(got, want))
            runs[live, label] = (run, vecs, slots, err)
    state_phases.phase_rows = phase_rows

    trace_dir = tempfile.mkdtemp(prefix="kda_decode_bench_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    pool = pool0
    for run, vecs, slots, _ in runs.values():
        for _ in range(args.reps):
            _, pool = run(pool, vecs, slots)
        jax.block_until_ready(pool)
    jax.profiler.stop_trace()
    trace = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
    dev = trace["devices"][trace_reduce.busiest_device(trace)]

    execs = sorted((p for p in dev["programs"] if "jit_kda_" in p[0]),
                   key=lambda p: p[1])
    if len(execs) != len(runs) * args.reps:
        sys.exit(f"{len(execs)} executions in the trace, {len(runs)} x "
                 f"{args.reps} were run: {sorted({p[0] for p in execs})}")
    for i, ((live, label), (*_, err)) in enumerate(runs.items()):
        mine = execs[i * args.reps:(i + 1) * args.reps]
        whole = sorted(dur for _, _, dur in mine)[len(mine) // 2]
        took = calls = 0
        for _, start, dur in mine:
            inside = [op for op in dev["ops"] if start <= op[1] < start + dur]
            t, n = trace_reduce.matching(inside, "kda_decode_update")
            took, calls = took + t, calls + n
        ms = whole / LAYERS * 1e3
        state_bytes = 2 * live * HEADS * DK * DV * 4
        print(json.dumps({
            "live_rows": live, "form": label,
            "device_ms_per_layer": round(ms, 4),
            "kernel_ms_per_call": round(took / calls * 1e3, 4) if calls else None,
            "live_state_gb_per_s": round(state_bytes / ms / 1e6, 1),
            "rows_a_phase": None if label == "twin" else
            int(label.split("_r")[1]) if "_r" in label else
            phase_rows(ROWS, HEADS * DK * DV * 4),
            "max_rel_err_from_twin": float(f"{err:.3g}")}), flush=True)


if __name__ == "__main__":
    main()
