"""The flash kernels of a training step alone on a TPU, by diagonal chunk.

    python benchmarks/flash_train_bench.py [--chunks 128 256 512 0]
        [--other LABEL=FILE ...] [--pairs general packed] [--layers 12]
        [--reps 3]

One call is ONE layer's attention at a train cell's shapes, forward and
backward: ``general`` is ``bloom560m_train_1chip``'s (B 4, 16 heads of 64,
2,048 positions, ALiBi: the non-plain path of ``flash_fwd`` / ``flash_dq`` /
``flash_dkv``), ``packed`` is ``opt1b3_train_zero3_4chip``'s a chip (B 2, 32
heads of 64, 2,048 positions: ``flash_packed_fwd`` / ``_dq`` / ``_dkv``).
Both run 1,024 x 1,024 blocks, so two of a backward kernel's three blocks are
diagonal. ``--chunks`` are the rows / keys a chunk of the backward kernels'
walk over a diagonal block (``flash_attention._DIAG_CHUNK``; 0 = every block
whole, what a call that is not causal runs). ``--other`` times another version
of the kernels' module beside them, by file (the parent's: ``git show
HEAD~1:deepspeed_tpu/ops/pallas/flash_attention.py > /tmp/parent.py``).

The time is the device's: the gradient of ``--layers`` chained calls (an op
of a millisecond cannot be timed a call at a time: a jitted call costs ~0.4
ms of host dispatch, PERF.md section 7 (g)), each kernel's own events in a
profiler trace over their count. ``ps_per_score`` is that time over the
2,048^2 / 2 scores a head the causal mask keeps (what ``perfbench/costs.py``
counts); the matmul floor at head size 64 is 1.3 ps a product, 3 products in
``dq`` and 4 in ``dkv``. Each line also holds one layer's output and its
three gradients to ``ops.attention.mha_attention`` in float32 at the highest
matmul precision (largest difference over the reference's largest value).
The numbers behind ``_DIAG_CHUNK`` (PERF.md section 6, PR 46). TPU only: the
script refuses to print a time from another backend.
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

S, HD = 2048, 64
#: pair -> (batch, heads, ALiBi): the two train cells' attention a chip
SHAPES = {"general": (4, 16, True), "packed": (2, 32, False)}
# fwd / dq / dkv -> the name pattern ``flash_roofline`` reads that kernel by
with open(os.path.join(ROOT, "perfbench", "layer_metrics", "flash_roofline.json")) as f:
    KERNELS = {cost.removeprefix("flash_"): pattern
               for pattern, cost in json.load(f)["params"]["kernels"].items()}


def load_other(spec):
    """``LABEL=FILE`` -> (label, the file's ``flash_attention``)."""
    label, path = spec.split("=", 1)
    mod_spec = importlib.util.spec_from_file_location(f"flash_{label}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return label, mod.flash_attention


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--chunks", type=int, nargs="*", default=[128, 256, 512, 0])
    ap.add_argument("--other", nargs="*", default=[], metavar="LABEL=FILE")
    ap.add_argument("--pairs", nargs="+", default=list(SHAPES), choices=list(SHAPES))
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=4600000001)
    args = ap.parse_args()
    platform = jax.devices()[0].platform
    if platform != "tpu":
        sys.exit(f"flash_train_bench: the default device is {platform!r}, "
                 "not a TPU: no time is taken")
    from deepspeed_tpu.models.transformer import _alibi_slopes
    from deepspeed_tpu.ops.attention import mha_attention
    from deepspeed_tpu.ops.pallas import flash_attention
    fa = sys.modules["deepspeed_tpu.ops.pallas.flash_attention"]
    # label -> (flash_attention, the chunk to give this tree's module)
    variants = {chunk or "whole": (flash_attention, chunk or 2 * S)
                for chunk in args.chunks}       # 2 S: no block is a multiple
    variants.update((label, (fn, None)) for label, fn in map(load_other, args.other))

    def grads(attn):
        """(o, dq, dk, dv) of one layer under a fixed cotangent."""
        def run(q, k, v, g):
            o, vjp = jax.vjp(attn, q, k, v)
            return (o, *vjp(g.astype(o.dtype)))
        return run

    runs = {}
    for pair in args.pairs:
        B, H, alibi = SHAPES[pair]
        slopes = jnp.asarray(_alibi_slopes(H), jnp.float32) if alibi else None
        keys = jax.random.split(jax.random.key(args.seed % (1 << 31)), 4)
        q, k, v, g = (jax.random.normal(kk, (B, S, H, HD), jnp.float32)
                      .astype(jnp.bfloat16) for kk in keys)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(grads(lambda q, k, v: mha_attention(
                q, k, v, causal=True, alibi_slopes=slopes)))(
                    *(x.astype(jnp.float32) for x in (q, k, v, g)))
        want = [np.asarray(x) for x in want]

        for label, (flash, chunk) in variants.items():
            if chunk:
                fa._DIAG_CHUNK = chunk

            def attn(q, k, v, slopes=slopes, flash=flash):
                return flash(q, k, v, causal=True, alibi_slopes=slopes)

            got = jax.jit(grads(attn))(q, k, v, g)
            err = max(float(np.abs(np.asarray(a, np.float32) - b).max()
                            / np.abs(b).max()) for a, b in zip(got, want))

            def stack(q, k, v):
                def loss(q, k, v):
                    x, _ = jax.lax.scan(lambda x, _: (attn(x, k, v), None), q,
                                        None, length=args.layers)
                    return x.astype(jnp.float32).sum()
                return jax.grad(loss, (0, 1, 2))(q, k, v)
            stack.__name__ = f"fb_{pair}_{label}"
            run = jax.jit(stack)
            jax.block_until_ready(run(q, k, v))
            runs[pair, label] = (run, (q, k, v), err)

    trace_dir = tempfile.mkdtemp(prefix="flash_train_bench_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    for run, operands, _ in runs.values():
        for _ in range(args.reps):
            jax.block_until_ready(run(*operands))
    jax.profiler.stop_trace()
    trace = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
    dev = trace["devices"][trace_reduce.busiest_device(trace)]

    execs = sorted((p for p in dev["programs"] if "jit_fb_" in p[0]),
                   key=lambda p: p[1])
    if len(execs) != len(runs) * args.reps:
        sys.exit(f"{len(execs)} executions in the trace, {len(runs)} x "
                 f"{args.reps} were run: {sorted({p[0] for p in execs})}")
    for n, ((pair, label), (*_, err)) in enumerate(runs.items()):
        B, H, _ = SHAPES[pair]
        kept = B * H * S * S / 2          # scores the causal mask keeps
        line = {"pair": pair, "chunk": label}
        for _, start, dur in execs[n * args.reps:(n + 1) * args.reps]:
            inside = [op for op in dev["ops"] if start <= op[1] < start + dur]
            for name, pattern in KERNELS.items():
                took, calls = trace_reduce.matching(inside, pattern)
                if calls != args.layers:
                    sys.exit(f"{pair} {label}: {calls} {name} calls in an "
                             f"execution of {args.layers} layers")
                line.setdefault(name, []).append(took / calls)
        for name in KERNELS:
            ms = sorted(line[name])[args.reps // 2] * 1e3
            line[name] = {"ms_per_call": round(ms, 4),
                          "ps_per_score": round(ms * 1e9 / kept, 3)}
        line["max_rel_err_from_float32"] = float(f"{err:.3g}")
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
