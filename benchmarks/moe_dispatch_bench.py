"""One OLMoE-sized MoE layer stack on a TPU, by dispatch form and rows.

    python benchmarks/moe_dispatch_bench.py [--rows 1 16 64 512 1024 1536 2048]
    python benchmarks/moe_dispatch_bench.py --preset sdar 30b-a3b-ep8 --rows 64 256 1024

Times ``MoECausalLM._nodrop_mlp`` scanned over the 8 layers of the ``olmoe``
``1b-7b-8l`` preset (64 experts of 2,048 x 1,024, top-8; 805 MB of expert
weights a layer, so nothing stays in a cache between layers), as every path
of the model runs it (the layer's weights are the scan's slices), in its two
forms: ``sorted`` (rows sorted into ragged groups, ``jax.lax.ragged_dot``)
and ``dense`` (every expert over every row). The time is the device's: the
median duration of the program's executions in a profiler trace, over the
layers. The numbers behind ``moe_lm._SORTED_DISPATCH_MIN_ROWS`` (PERF.md
section 6, PR 26). TPU only: a time from another backend says nothing about
the threshold, so the script refuses to print one. ``--preset FAMILY SIZE``
times another preset's layer in the same way at 8 layers of depth (PR 33:
``sdar 30b-a3b-ep8``, 16 held experts of 2,048 x 768 of a router's 128,
top-8, so one choice in eight is held: the dense form does 16 times the
products the routing needs, the sorted form a sixteenth of the rows).
"""

import argparse
import json
import os
import statistics
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "perfbench")]

import jax
import jax.numpy as jnp

import trace_reduce
from deepspeed_tpu.models import moe_lm
from deepspeed_tpu.models.presets import get_model

FORMS = {"sorted": 0, "dense": 1 << 30}     # _SORTED_DISPATCH_MIN_ROWS


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, nargs="+",
                    default=[1, 16, 64, 512, 1024, 1536, 2048])
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--preset", nargs=2, default=["olmoe", "1b-7b-8l"],
                    metavar=("FAMILY", "SIZE"))
    args = ap.parse_args()
    platform = jax.devices()[0].platform
    if platform != "tpu":
        sys.exit(f"moe_dispatch_bench: the default device is {platform!r}, "
                 "not a TPU: no time is taken")
    model = get_model(*args.preset, n_layer=8, param_dtype=jnp.bfloat16)
    cfg, moe = model.config, model.moe
    mlp = jax.jit(lambda k: model.init_params(k)["layers"]["mlp"])(jax.random.key(0))
    jax.block_until_ready(mlp)
    layer_bytes = 3 * moe.num_experts * cfg.d_model * model.expert_ff * 2
    print(f"device {jax.devices()[0].device_kind}; a layer's experts "
          f"{layer_bytes / 1e6:.0f} MB", flush=True)

    runs, outs = {}, {}
    for rows in args.rows:
        x = jax.random.normal(jax.random.key(rows), (1, rows, cfg.d_model),
                              jnp.bfloat16)
        for form, max_rows in FORMS.items():
            def stack(mlp, x):
                def body(h, lp):
                    out, _, counts, _ = model._nodrop_mlp(lp, h)
                    return h + out, counts
                return jax.lax.scan(body, x, mlp)
            # the name is what the trace files the program's executions under
            stack.__name__ = f"moe_{form}_r{rows}"
            moe_lm._SORTED_DISPATCH_MIN_ROWS = max_rows    # read while tracing
            run = jax.jit(stack)
            outs[rows, form] = jax.block_until_ready(run(mlp, x))
            runs[rows, form] = (run, x)

    trace_dir = tempfile.mkdtemp(prefix="moe_dispatch_bench_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    for run, x in runs.values():
        for _ in range(args.reps):
            out = run(mlp, x)
        jax.block_until_ready(out)
    jax.profiler.stop_trace()
    trace = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
    programs = trace["devices"][trace_reduce.busiest_device(trace)]["programs"]

    for rows in args.rows:
        for form in FORMS:
            took = [d for name, _, d in programs
                    if f"moe_{form}_r{rows}(" in name + "("]
            if len(took) != args.reps:
                sys.exit(f"moe_{form}_r{rows}: {len(took)} executions in the "
                         f"trace, {args.reps} were run; the trace's programs: "
                         f"{sorted({name for name, _, _ in programs})}")
            ms = statistics.median(took) / cfg.n_layer * 1e3
            counts = outs[rows, form][1]
            print(json.dumps({
                "rows": rows, "form": form, "device_ms_per_layer": round(ms, 4),
                "expert_weight_gb_per_s": round(layer_bytes / ms / 1e6, 1),
                "experts_touched_per_layer":
                    float((counts > 0).sum() / cfg.n_layer)}), flush=True)
        a, b = (outs[rows, f][0].astype(jnp.float32) for f in FORMS)
        print(json.dumps({"rows": rows, "max_abs_diff_dense_from_sorted":
                          float(jnp.abs(a - b).max())}), flush=True)


if __name__ == "__main__":
    main()
