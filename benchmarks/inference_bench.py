"""Inference latency benchmark: prefill/decode p50/p90/p99.

Reference parity: ``benchmarks/inference/{bert,gpt}-bench.py`` (per-call
latency percentiles over an HF model wrapped by ``init_inference``).

Usage:
    python benchmarks/inference_bench.py --model gpt2-125m --batch 1 \
        --prompt-len 128 --gen 32 --trials 20 [--dtype bf16|int8]

Prints one JSON line with prefill latency, per-token decode latency, and
tokens/s percentiles.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np


def pct(xs, q):
    return float(np.percentile(np.asarray(xs), q))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="gpt2-125m",
                    help="zoo preset (gpt2-125m/350m/774m, llama-tiny/7b) or HF checkpoint dir")
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--prompt-len", type=int, default=128)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--trials", type=int, default=20)
    ap.add_argument("--dtype", default="bf16")
    ap.add_argument("--stream", action="store_true",
                    help="ZeRO-Inference weight streaming (host-resident layers)")
    ap.add_argument("--forward-only", action="store_true",
                    help="measure engine.forward latency instead of "
                         "generate — the reference's bert-bench.py shape. "
                         "Encoder families (bert/distilbert/clip text) are "
                         "served by passing their HF checkpoint DIRECTORY "
                         "as --model; the name presets are decoder-only")
    args = ap.parse_args()

    import jax

    import deepspeed_tpu
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    if "/" in args.model or args.model.startswith("."):
        model = args.model  # HF checkpoint path
        kw = {}
    else:
        from deepspeed_tpu.models import gpt2, llama
        fam, _, size = args.model.partition("-")
        presets = {"gpt2": gpt2, "llama": llama}
        if fam not in presets:
            ap.error(f"unknown preset family {fam!r} (presets: "
                     f"{sorted(presets)}; other architectures: pass an HF "
                     "checkpoint directory path)")
        model = presets[fam](size or "125m")
        kw = {"params": model.init_params(jax.random.key(0))}
    if args.stream:
        kw["zero"] = {"stage": 3, "offload_param": {"device": "cpu"}}
    engine = deepspeed_tpu.init_inference(model, dtype=args.dtype, **kw)

    rng = np.random.default_rng(0)
    vocab = getattr(engine.module.config, "vocab_size", 50257)
    prompt = rng.integers(0, vocab, size=(args.batch, args.prompt_len)).astype(np.int32)

    if args.forward_only:
        np.asarray(engine.forward(prompt))  # warmup/compile
        fwd = []
        for _ in range(args.trials):
            t0 = time.perf_counter()
            np.asarray(engine.forward(prompt))  # host fetch = device sync
            fwd.append(time.perf_counter() - t0)
        print(json.dumps({
            "model": args.model, "batch": args.batch,
            "seq_len": args.prompt_len, "dtype": args.dtype,
            "forward_ms": {q: round(pct(fwd, p) * 1e3, 2)
                           for q, p in (("p50", 50), ("p90", 90), ("p99", 99))},
            "samples_per_s": round(args.batch / pct(fwd, 50), 1),
        }))
        return

    # warmup (compile prefill + decode)
    engine.generate(prompt, max_new_tokens=2)

    total, prefill = [], []
    for _ in range(args.trials):
        t0 = time.perf_counter()
        out = engine.generate(prompt, max_new_tokens=1)
        prefill.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        out = engine.generate(prompt, max_new_tokens=args.gen)
        total.append(time.perf_counter() - t0)
    n_gen = int(np.asarray(out).shape[1]) - args.prompt_len
    # with gen < 2 "decode" would be the jitter between two identical calls
    decode = ([(t - p) / (n_gen - 1) for t, p in zip(total, prefill)]
              if n_gen >= 2 else None)

    print(json.dumps({
        "model": args.model, "batch": args.batch,
        "prompt_len": args.prompt_len, "gen": n_gen, "dtype": args.dtype,
        "stream": bool(args.stream),
        "prefill_ms": {q: round(pct(prefill, p) * 1e3, 2)
                       for q, p in (("p50", 50), ("p90", 90), ("p99", 99))},
        "decode_ms_per_token": ({q: round(pct(decode, p) * 1e3, 2)
                                 for q, p in (("p50", 50), ("p90", 90), ("p99", 99))}
                                if decode else None),
        "tokens_per_s": round(args.batch * n_gen / pct(total, 50), 1),
    }))


if __name__ == "__main__":
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    main()
