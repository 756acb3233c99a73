"""Profile the bench training step: where the non-MFU time goes.

Runs the same engine/config as ``bench.py`` and prints a cost breakdown
two ways:

1. XLA's AOT cost analysis of the compiled train step (flops / bytes
   accessed / estimated optimal seconds) — available everywhere;
2. a ``jax.profiler`` device trace (written to ``--trace-dir``, viewable
   in TensorBoard / Perfetto) — meaningful on real hardware.

Usage::

    python benchmarks/profile_bench.py [--steps 5] [--trace-dir /tmp/ds_trace]
                                       [--config gpt2|llama]

Knobs are bench.py's env vars (BENCH_BATCH/SEQ/REMAT/LOSS_CHUNK/OPT...).
Like bench.py it runs on the chip only (device guard first).
"""

from __future__ import annotations

import argparse
import json
import os
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=5,
                    help="timed steps (>= 1)")
    ap.add_argument("--trace-dir", default=None,
                    help="write a jax.profiler trace here (TPU: perfetto/TB)")
    ap.add_argument("--config", choices=("gpt2", "llama", "bert"), default="gpt2",
                    help="which bench metric's engine to profile")
    args = ap.parse_args()
    if args.steps < 1:
        ap.error("--steps must be >= 1")

    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from bench import (build_bench_engine, build_bert_bench_engine,
                       build_llama_bench_engine)

    from deepspeed_tpu.accelerator import require_tpu
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    try:
        require_tpu()
    except RuntimeError as e:
        sys.exit(f"profile_bench: {e}")
    enable_compile_cache()

    import jax
    import jax.numpy as jnp

    build = {"llama": build_llama_bench_engine,
             "bert": build_bert_bench_engine,
             "gpt2": build_bench_engine}[args.config]
    engine, model, batch, knobs = build()
    BATCH, SEQ = knobs["BATCH"], knobs["SEQ"]

    # ---- 1. AOT cost analysis of the compiled step ----
    jax.block_until_ready(engine.train_batch(batch()))  # compile
    cost = None
    try:
        fn = next(iter(engine._train_batch_jit.values()))
        fn = getattr(fn, "inner", fn)   # the jit under the compile watchdog
        # the compiled step takes the batch stacked [gas, B, ...] (gas=1)
        b = jax.tree.map(lambda x: jnp.asarray(x)[None], batch())
        cost = fn.lower(engine.state, b,
                        jax.random.key(0)).compile().cost_analysis()
    except Exception as e:  # layout varies across jax versions
        print(f"cost_analysis unavailable: {type(e).__name__}: {e}")
    if cost:
        ca = cost[0] if isinstance(cost, (list, tuple)) else cost
        wanted = {k: ca[k] for k in ("flops", "bytes accessed",
                                     "optimal_seconds") if k in ca}
        print(json.dumps(wanted, indent=2, default=float))

    # ---- 2. wall-clock + optional device trace ----
    t0 = time.perf_counter()
    if args.trace_dir:
        with jax.profiler.trace(args.trace_dir):
            for _ in range(args.steps):
                loss = engine.train_batch(batch())
            jax.block_until_ready(loss)
        print(f"trace written to {args.trace_dir}")
    else:
        for _ in range(args.steps):
            loss = engine.train_batch(batch())
        jax.block_until_ready(loss)
    dt = (time.perf_counter() - t0) / args.steps
    toks = BATCH * SEQ / dt
    print(json.dumps({"seconds_per_step": round(dt, 4),
                      "tokens_per_sec": round(toks, 1)}))


if __name__ == "__main__":
    main()
