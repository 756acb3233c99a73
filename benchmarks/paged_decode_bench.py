"""The paged decode attention kernel alone on a TPU, in the states the
serving cells put it in.

    python benchmarks/paged_decode_bench.py [--groups 1 2 4] [--forms]
                                            [--steps 1 21] [--parent FILE]

One call of ``paged_decode_attention`` is a decode step's attention of one
layer over every row. States shaped as PERF.md section 5 reads them off the
cells, and one no cell has: ``opt_decode`` (40 rows of 32 heads x 64, 16
table entries, every row ~330 tokens deep), ``opt_mixed`` (the same table
with 3 rows decoding and 37 idle rows on the dummy block), ``olmoe_decode``
(64 rows of 16 heads x 128, 32 table entries, ~510 tokens a row);
``narrow_decode`` (``opt_decode`` with 12 heads x 64: a block is 0.19 MB a
pool, not 0.5, so an iteration's fixed cost weighs more); ``sdar_block`` (a
pass of generation by blocks as ``_paged_block_attention`` hands it over:
64 rows of 4 POSITIONS x 32 heads over 4 kv heads x 128 = 512 lanes, 8
table entries, 130-640 tokens a row: 32 query rows a kv head, so the
products are taken a kv head; against the block-diagonal query the same
scores cost 4 times the arithmetic over 128 query rows and the arithmetic,
not the copy, set the time: PERF.md section 6, PR 36); ``solar_gqa`` (the
one softmax layer of a Solar-Open2 period: 128 rows of 64 heads over 8 kv
heads x 128 = 1,024 lanes, 16 table entries, ~1,000 tokens a row: 8 query
rows a kv head, the fewest the per-kv-head form takes, so the state the
line between the two forms was set on). The time is the device's: the
kernel's own events in a profiler trace, a call. ``--groups`` times the
kernel at those blocks a loop iteration (``_group_blocks`` is what the
program takes); ``--forms`` times each state under both forms of the
products where its shape allows both (``_per_kv_head`` is what the program
takes); ``--steps 1 21`` at those blocks a step of the running softmax,
largest first, a digit each (``_step_blocks``: 421 at G 6); ``--parent``
names another version of the kernel's module (a file) to time beside it:
one that takes no position axis is handed a block's positions as query
rows, a kv head's together, as the program did before the axis. Each line
also checks the output against a float32 gather + softmax (a row without a
request, its table zeroed, yields zeros since PR 49 and is left out of the
check) and reads the time beside what the version COPIES (``copied_blocks``:
the live rows' blocks; a version from before PR 49, one that does not know
the dummy block, also copies it once an idle row) and the time those bytes
take at the chip's 819 GB/s (``copies_ms_at_819_gb_s``). The numbers
behind ``_STREAM_VMEM_BYTES`` (PERF.md section 6, PR 27),
``_PER_KV_HEAD_MIN_ROWS`` and ``_step_blocks`` (PR 36). TPU only: the
script refuses to print a time from another backend.
"""

import argparse
import importlib
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

BS = 128
LAYERS = 8          # calls a program, each with its own query
# rows, heads, head size, table width, pool blocks, kv heads, positions a row
STATES = {
    "opt_decode": (40, 32, 64, 16, 224, 32, 1),
    "opt_mixed": (40, 32, 64, 16, 224, 32, 1),
    "olmoe_decode": (64, 16, 128, 32, 528, 16, 1),
    "narrow_decode": (40, 12, 64, 16, 224, 12, 1),  # gpt2:125m's 768-lane row
    "sdar_block": (64, 32, 128, 8, 328, 4, 4),      # a block of 4 positions
    "solar_gqa": (128, 64, 128, 16, 2064, 8, 1),
}
DEPTHS = {"olmoe_decode": (128, 900), "sdar_block": (128, 640),
          "solar_gqa": (256, 1800)}             # else (128, 540)


def draw_state(name, seed):
    """Block tables and positions of ``name``: live blocks drawn without
    replacement from the pool, the dead tail zero (the dummy block)."""
    B, H, Hd, width, blocks, _, _ = STATES[name]
    r = np.random.default_rng(seed)
    if name == "opt_mixed":
        pos = np.zeros(B, np.int32)
        pos[r.choice(B, 3, replace=False)] = r.integers(300, 700, 3)
    else:
        pos = r.integers(*DEPTHS.get(name, (128, 540)), B)
    live = pos // BS + 1
    ids = iter(r.permutation(np.arange(1, blocks)))
    bt = np.zeros((B, width), np.int32)
    for b in range(B):
        if pos[b]:
            bt[b, :live[b]] = [next(ids) for _ in range(live[b])]
    return bt, pos.astype(np.int32)


def copied(mod, pos, live_rows):
    """Blocks one call of ``mod`` copies a pool: every live row's, and
    before PR 49 the dummy block for each idle row."""
    rows = live_rows if hasattr(mod, "DUMMY_BLOCK") else slice(None)
    return int((pos[rows] // BS + 1).sum())


def reference(q, kp, vp, bt, pos):
    """q [B, Q, H, Hd]: query head h reads kv head h // (H / KV), KV off the
    pool's row; a row's Q positions see the same keys."""
    B, Q, H, Hd = q.shape
    KV = kp.shape[2] // Hd
    k = kp[bt].reshape(B, -1, KV, Hd).astype(jnp.float32)
    v = vp[bt].reshape(B, -1, KV, Hd).astype(jnp.float32)
    q6 = (q.astype(jnp.float32) * Hd**-0.5).reshape(B, Q, KV, H // KV, Hd)
    s = jnp.einsum("bqcgd,bscd->bqcgs", q6, k, precision="highest")
    kpos = jnp.arange(k.shape[1])
    s = jnp.where(kpos <= pos[:, None, None, None, None], s, -1e30)
    return jnp.einsum("bqcgs,bscd->bqcgd", jax.nn.softmax(s, axis=-1), v,
                      precision="highest").reshape(B, Q, H, Hd)


def call(mod, q, kp, vp, bt, pos):
    """One layer's call, q [B, Q, H, Hd]. A module without the position axis
    gets the positions as query rows, a kv head's together (what
    ``_paged_block_attention`` did before the axis)."""
    B, Q, H, Hd = q.shape
    if Q == 1:
        return mod.paged_decode_attention(q[:, 0], kp, vp, bt, pos)[:, None]
    if hasattr(mod, "_per_kv_head"):
        return mod.paged_decode_attention(q, kp, vp, bt, pos)
    KV = kp.shape[2] // Hd
    qr = q.reshape(B, Q, KV, H // KV, Hd).transpose(0, 2, 1, 3, 4)
    o = mod.paged_decode_attention(qr.reshape(B, Q * H, Hd), kp, vp, bt, pos)
    return o.reshape(B, KV, Q, H // KV, Hd).transpose(0, 2, 1, 3, 4) \
        .reshape(B, Q, H, Hd)


def load_module(path):
    spec = importlib.util.spec_from_file_location("paged_parent", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--groups", type=int, nargs="*", default=[])
    ap.add_argument("--forms", action="store_true")
    ap.add_argument("--steps", nargs="*", default=[])
    ap.add_argument("--parent", default=None)
    ap.add_argument("--states", nargs="+", default=list(STATES))
    ap.add_argument("--reps", type=int, default=6)
    ap.add_argument("--seed", type=int, default=2700000001)
    args = ap.parse_args()
    platform = jax.devices()[0].platform
    if platform != "tpu":
        sys.exit(f"paged_decode_bench: the default device is {platform!r}, "
                 "not a TPU: no time is taken")
    here = importlib.import_module(
        "deepspeed_tpu.ops.pallas.paged_decode_attention")
    # a variant: a label, a module, and what it overrides of THIS module's
    # choices (each is a function of static shapes the wrapper calls)
    chosen = {name: getattr(here, name)
              for name in ("_group_blocks", "_per_kv_head", "_step_blocks")}
    variants = [("program", here, {})]
    variants += [(f"G{g}", here, {"_group_blocks": lambda *a, g=g: g})
                 for g in args.groups]
    if args.forms:
        variants += [(form, here, {"_per_kv_head": lambda *a, on=on: on})
                     for form, on in (("per_kv_head", True),
                                      ("block_diagonal", False))]
    variants += [(f"S{s}", here, {"_step_blocks": lambda G, s=s: tuple(
        int(c) for c in s if int(c) <= G)}) for s in args.steps]
    if args.parent:
        variants.append(("parent", load_module(args.parent), {}))

    runs = {}
    for name in args.states:
        B, H, Hd, width, blocks, KV, Q = STATES[name]
        bt_np, pos_np = draw_state(name, args.seed)
        live_rows = bt_np[:, 0] != 0
        key = jax.random.key(args.seed % (1 << 31))
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (LAYERS, B, Q, H, Hd), jnp.bfloat16)
        kp = jax.random.normal(kk, (blocks, BS, KV * Hd), jnp.bfloat16)
        vp = jax.random.normal(kv, (blocks, BS, KV * Hd), jnp.bfloat16)
        bt, pos = jnp.asarray(bt_np), jnp.asarray(pos_np)
        want = jax.jit(reference)(q[0], kp, vp, bt, pos)
        for label, mod, override in variants:
            if label == "per_kv_head" and ((Q * H // KV) % 8 or Hd % 128):
                continue        # rows or lanes a kv head that fill no tile

            def stack(q, kp, vp, bt, pos, mod=mod):
                return jax.lax.map(lambda ql: call(mod, ql, kp, vp, bt, pos),
                                   q)
            stack.__name__ = f"paged_{name}_{label}"
            for attr, fn in {**chosen, **override}.items():
                setattr(here, attr, fn)
            run = jax.jit(stack)
            out = jax.block_until_ready(run(q, kp, vp, bt, pos))
            err = float(jnp.abs(out[0].astype(jnp.float32)
                                - want)[live_rows].max())
            runs[name, label] = (run, (q, kp, vp, bt, pos),
                                 copied(mod, pos_np, live_rows), err)
    for attr, fn in chosen.items():
        setattr(here, attr, fn)

    trace_dir = tempfile.mkdtemp(prefix="paged_decode_bench_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    for run, operands, _, _ in runs.values():
        for _ in range(args.reps):
            out = run(*operands)
        jax.block_until_ready(out)
    jax.profiler.stop_trace()
    trace = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
    dev = trace["devices"][trace_reduce.busiest_device(trace)]

    # two variants of one state can be ONE program to XLA (the same HLO under
    # the first one's name), so executions are told apart by their order
    execs = sorted((p for p in dev["programs"] if "jit_paged_" in p[0]),
                   key=lambda p: p[1])
    if len(execs) != len(runs) * args.reps:
        sys.exit(f"{len(execs)} executions in the trace, {len(runs)} x "
                 f"{args.reps} were run: {sorted({p[0] for p in execs})}")
    for i, ((name, label), (_, _, blocks, err)) in enumerate(runs.items()):
        B, H, Hd, width, _, KV, _ = STATES[name]
        took, calls = 0.0, 0
        for _, start, dur in execs[i * args.reps:(i + 1) * args.reps]:
            inside = [op for op in dev["ops"] if start <= op[1] < start + dur]
            t, n = trace_reduce.matching(inside, "paged_decode_attention")
            took, calls = took + t, calls + n
        events, calls = calls, args.reps * LAYERS
        ms = took / calls * 1e3
        block_bytes = 2 * BS * KV * Hd * 2
        print(json.dumps({
            "state": name, "kernel": label, "device_ms_per_call": round(ms, 4),
            "trace_events_per_call": events / calls,
            "rows": B, "table_entries": B * width, "copied_blocks": blocks,
            "us_per_copied_block": round(ms * 1e3 / blocks, 3),
            "copied_gb_per_s": round(blocks * block_bytes / ms / 1e6, 1),
            "copies_ms_at_819_gb_s": round(blocks * block_bytes / 819e6, 4),
            "max_abs_err_from_float32": round(err, 5)}), flush=True)


if __name__ == "__main__":
    main()
