"""The Mamba-2 decode state update alone on a TPU, by live rows.

    python benchmarks/mamba2_decode_bench.py [--live 1 16 64] [--phase-rows 4 16]
        [--other LABEL=FILE[:hpn] ...] [--top 8]

One call is a decode step's state update of ONE Mamba-2 layer at the
``granite4hmicro_serve_chat`` cell's widths: 64 rows, 64 heads of a 64 x 128
float32 state (2 MB a row), 65 slots. ``kernel`` is
``ops/pallas/mamba2_decode_update.py`` (each live row's state read once and
written once where it lies, in phases of one direction at a time), ``twin``
the plain-XLA form the program takes off a TPU (``models/state_mixers.py``
``_ssd_decode_update``: the rows' states gathered, updated and scattered
back). Each is timed at 1, 16 and 64 live rows of 64, the others idle on the
dummy slot: the only place the low-occupancy cost is measured, since no cell
serves this model under an open loop (PERF.md section 7). ``--phase-rows``
times the kernel at those rows a phase (``state_phases.phase_rows`` is what
the program takes). ``--other`` times another version of the kernel's module
beside them, by file (the parent's, or a scratch copy with its arithmetic or
its copies taken out: how PR 44 found which of the two bound the kernel);
``:hpn`` says that version keeps the pool ``[rows, H, P, N]``, as the tree did
until PR 44.

The time is the device's: a program of ``LAYERS`` = 36 updates chained on one
pool (a decode step's; an op of this size cannot be timed a call at a time: a
jitted call costs ~0.4 ms of host dispatch, PERF.md section 7 (g)), its ``XLA
Modules`` event in a profiler trace over ``LAYERS`` (so the vectors'
preparation counts), and beside it the kernel's own events. ``GB/s`` is the
LIVE rows' state read once and written once over that time. Each line also
checks the live rows' ``y`` and the pool against the twin's. The numbers
behind ``state_phases._PHASE_BYTES`` at this state size (PERF.md section 6, PR
44). TPU only: the script refuses to print a time from another backend.
"""

import argparse
import importlib.util
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

ROWS, HEADS, P, N, SLOTS = 64, 64, 64, 128, 65
LAYERS = 36         # updates a program, one pool: a decode step's


def draw(seed, live):
    """The vectors of ``LAYERS`` steps as ``_mamba2_project`` gives them (dt
    a softplus in the family's range, A in -(1, 16)) and the rows' slots:
    ``live`` rows on distinct slots in no order, the rest on the dummy."""
    r = np.random.default_rng(seed)
    shape = (LAYERS, ROWS)
    vecs = dict(
        x=r.standard_normal((*shape, HEADS, P)),
        dt=np.exp(r.uniform(np.log(1e-3), np.log(0.3), (*shape, HEADS))),
        Bm=r.standard_normal((*shape, N)), Cm=r.standard_normal((*shape, N)))
    slots = np.zeros(ROWS, np.int32)
    slots[r.choice(ROWS, live, replace=False)] = \
        r.permutation(np.arange(1, SLOTS))[:live]
    A = -r.uniform(1.0, 16.0, HEADS)
    return {k: jnp.asarray(a, jnp.float32) for k, a in vecs.items()}, \
        jnp.asarray(A, jnp.float32), jnp.asarray(slots)


def load_other(spec):
    """``LABEL=FILE[:hpn]`` -> (label, the file's ``mamba2_decode_update``,
    whether it keeps the pool [rows, H, P, N])."""
    label, path = spec.split("=", 1)
    hpn = path.endswith(":hpn")
    path = path[:-4] if hpn else path
    mod_spec = importlib.util.spec_from_file_location(f"m2_{label}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return label, mod.mamba2_decode_update, hpn


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--live", type=int, nargs="+", default=[1, 16, 64])
    ap.add_argument("--phase-rows", type=int, nargs="*", default=[])
    ap.add_argument("--other", nargs="*", default=[], metavar="LABEL=FILE[:hpn]")
    ap.add_argument("--top", type=int, default=0,
                    help="also print each line's TOP largest device ops "
                         "[name, self s, calls, scope] of its executions")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=4400000001)
    args = ap.parse_args()
    platform = jax.devices()[0].platform
    if platform != "tpu":
        sys.exit(f"mamba2_decode_bench: the default device is {platform!r}, "
                 "not a TPU: no time is taken")
    from deepspeed_tpu.models import state_mixers as SM
    from deepspeed_tpu.ops.pallas import state_phases
    from deepspeed_tpu.ops.pallas.mamba2_decode_update import \
        mamba2_decode_update
    phase_rows = state_phases.phase_rows
    zero = jnp.zeros((HEADS,), jnp.float32)      # the kernel's y is S C alone
    # label -> (the update, rows a phase or None, the pool kept [rows, H, P, N])
    variants = {"twin": (None, None, False),
                "kernel": (mamba2_decode_update, None, False)}
    for n in args.phase_rows:
        variants[f"kernel_r{n}"] = (mamba2_decode_update, n, False)
    for spec in args.other:
        label, fn, hpn = load_other(spec)
        variants[label] = (fn, None, hpn)

    def program(label):
        fn = variants[label][0]

        def stack(state, vecs, A, slots):
            def one(state, v):
                step = (v["x"], v["dt"], A, v["Bm"], v["Cm"])
                if fn is None:
                    y, state = SM._ssd_decode_update(state, *step, zero, slots, 0)
                else:
                    y, state = fn(state, *step, slots, 0)
                return state, y
            state, y = jax.lax.scan(one, state, vecs)
            return y, state
        return stack

    pool0 = jax.random.normal(jax.random.key(args.seed % (1 << 31)),
                              (SLOTS, N, HEADS * P), jnp.float32)
    pools = {False: pool0}
    if any(hpn for *_, hpn in variants.values()):
        pools[True] = SM._ssd_from_pool(pool0, HEADS)
    runs = {}
    for live in args.live:
        vecs, A, slots = draw(args.seed + live, live)
        want = None
        for label, (_, n, hpn) in variants.items():
            state_phases.phase_rows = (lambda *a, n=n: n) if n else phase_rows
            stack = program(label)
            stack.__name__ = f"m2_{live}_{label}"
            run = jax.jit(stack, donate_argnums=(0,))
            y, pool = jax.block_until_ready(run(pools[hpn] + 0.0, vecs, A, slots))
            on = np.asarray(slots) != 0
            if hpn:
                pool = SM._ssd_to_pool(pool)
            got = (np.asarray(y)[:, on], np.asarray(pool))
            want = want or got
            err = max(float(np.abs(a - b).max() / np.abs(b).max())
                      for a, b in zip(got, want))
            runs[live, label] = (run, hpn, vecs, A, slots, err)
    state_phases.phase_rows = phase_rows

    trace_dir = tempfile.mkdtemp(prefix="mamba2_decode_bench_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    held = {hpn: pool + 0.0 for hpn, pool in pools.items()}
    for run, hpn, vecs, A, slots, _ in runs.values():
        for _ in range(args.reps):
            _, held[hpn] = run(held[hpn], vecs, A, slots)
        jax.block_until_ready(held[hpn])
    jax.profiler.stop_trace()
    trace = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
    dev = trace["devices"][trace_reduce.busiest_device(trace)]

    execs = sorted((p for p in dev["programs"] if "jit_m2_" in p[0]),
                   key=lambda p: p[1])
    if len(execs) != len(runs) * args.reps:
        sys.exit(f"{len(execs)} executions in the trace, {len(runs)} x "
                 f"{args.reps} were run: {sorted({p[0] for p in execs})}")
    for i, ((live, label), (*_, err)) in enumerate(runs.items()):
        mine = execs[i * args.reps:(i + 1) * args.reps]
        whole = sorted(dur for _, _, dur in mine)[len(mine) // 2]
        took = calls = 0
        ops = []
        for _, start, dur in mine:
            inside = [op for op in dev["ops"] if start <= op[1] < start + dur]
            t, n = trace_reduce.matching(inside, "mamba2_decode_update")
            took, calls = took + t, calls + n
            ops += inside
        ms = whole / LAYERS * 1e3
        state_bytes = 2 * live * HEADS * P * N * 4
        n = variants[label][1]
        print(json.dumps({
            "live_rows": live, "form": label,
            "device_ms_per_layer": round(ms, 4),
            "kernel_ms_per_call": round(took / calls * 1e3, 4) if calls else None,
            "live_state_gb_per_s": round(state_bytes / ms / 1e6, 1),
            "rows_a_phase": n or phase_rows(ROWS, HEADS * P * N * 4)
            if label.startswith("kernel") else None,
            "max_rel_err_from_twin": float(f"{err:.3g}")}), flush=True)
        if args.top:
            print(json.dumps(trace_reduce.largest_ops(ops, args.top)), flush=True)


if __name__ == "__main__":
    main()
