"""The latent paged decode kernel alone on a v5e, at the LongCat cell's shapes:
64 rows x 64 query heads over cached rows of 512 + 64 bf16 values in 640
lanes, tables of 36 blocks, every row ``--tokens`` deep (default 3,072).

Prints, from a device trace of ``--layers`` chained calls (a call's output
feeds the next call's query, so nothing overlaps): ms a call of
``latent_paged_decode_attention``; the time its copies need at the chip's
819 GB/s (live blocks x 128 x 640 x 2 B: what it reads, the lane padding
included) and the time the 576 values alone would (the roofline the
benchmark's ``decode.latent_decode_roofline`` counts); and the k-pool /
v-pool kernel (``paged_decode_attention``, one kv head of 640 lanes, the
same pool handed twice) beside it: each row read twice.

    python benchmarks/latent_decode_bench.py [--rows 64] [--tokens 3072 1024]

Exits 1 off a TPU: a CPU run never gives a time."""

import argparse
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "perfbench")]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=64)
    ap.add_argument("--tokens", type=int, nargs="+", default=[3072, 1024])
    ap.add_argument("--layers", type=int, default=8)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.accelerator import require_tpu
    from deepspeed_tpu.ops.pallas.latent_decode_attention import \
        latent_decode_attention
    from deepspeed_tpu.ops.pallas.paged_decode_attention import \
        paged_decode_attention
    try:
        require_tpu()
    except Exception as e:  # noqa: BLE001
        sys.exit(f"latent_decode_bench: {e}")
    import trace_reduce

    H, R, row, bs, width = 64, 512, 640, 128, 36
    B = args.rows
    rng = np.random.default_rng(0)
    for tokens in args.tokens:
        per = -(-tokens // bs)
        n_blocks = B * per + 1
        cp = jnp.asarray(rng.standard_normal((n_blocks, bs, row)) * 0.1,
                         jnp.bfloat16)
        bt = np.zeros((B, width), np.int32)
        bt[:, :per] = 1 + rng.permutation(B * per).reshape(B, per)
        bt, pos = jnp.asarray(bt), jnp.full((B,), tokens - 1, jnp.int32)
        q0 = jnp.asarray(rng.standard_normal((B, H, row)), jnp.bfloat16)

        def latent(q, cp):
            for _ in range(args.layers):
                o = latent_decode_attention(q, cp, bt, pos, latent=R,
                                            scale=0.07)
                q = jnp.pad(o, ((0, 0), (0, 0), (0, row - R)))
            return q

        def twice(q, cp):
            for _ in range(args.layers):
                q = paged_decode_attention(q, cp, cp, bt, pos, scale=0.07)
            return q

        for name, fn, kernel in (("latent", latent, "latent_paged_decode"),
                                 ("k-pool/v-pool", twice,
                                  "^paged_decode_attention")):
            run = jax.jit(fn)
            try:
                jax.block_until_ready(run(q0, cp))
            except Exception as e:  # noqa: BLE001 — a form that does not compile is a line
                print(f"{tokens} tokens, {name}: not built "
                      f"({type(e).__name__}: {str(e)[:200]})", flush=True)
                continue
            trace_dir = tempfile.mkdtemp(prefix="latent_decode_bench_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            jax.block_until_ready(run(q0, cp))
            jax.profiler.stop_trace()
            trace = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
            dev = trace["devices"][trace_reduce.busiest_device(trace)]
            took, calls = trace_reduce.matching(dev["ops"], kernel, "")
            live = B * per
            print(f"{tokens} tokens x {B} rows, {name}: "
                  f"{took / max(calls, 1) * 1e3:.4f} ms a call ({calls} calls); "
                  f"copies {live * bs * row * 2 / 819e9 * 1e3:.4f} ms at 819 GB/s, "
                  f"the 576 values alone {B * tokens * 1152 / 819e9 * 1e3:.4f} ms",
                  flush=True)


if __name__ == "__main__":
    main()
