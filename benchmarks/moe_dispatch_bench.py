"""One MoE layer stack on a TPU, by dispatch form, rows and experts touched.

    python benchmarks/moe_dispatch_bench.py [--rows 1 16 64 512 1024 1536 2048]
    python benchmarks/moe_dispatch_bench.py --preset sdar 30b-a3b-ep8 --rows 64 256 1024
    python benchmarks/moe_dispatch_bench.py --preset smallthinker 21b-a3b-12l \\
        --rows 16 --forms dense kernel --touched 16 32 50 64
    python benchmarks/moe_dispatch_bench.py --preset lfm2_moe 24b-a2b-9l \\
        --rows 256 384 512 --forms dense kernel gmm --row-tile 32 64 128

Times ``MoECausalLM._nodrop_mlp`` scanned over 8 layers of a preset (default
``olmoe`` ``1b-7b-8l``: 64 experts of 2,048 x 1,024, top-8; 805 MB of expert
weights a layer, so nothing stays in a cache between layers), as the model's
programs run it, in its forms:

* ``sorted`` (rows sorted into ragged groups, ``jax.lax.ragged_dot``) and
  ``dense`` (every expert over every row), the layer's weights the scan's
  slices;
* ``kernel`` (``ops/pallas/grouped_expert_mlp.py``: the touched experts, the
  stacks closed over whole and read in place; calls of at most its
  ``MAX_ROWS`` rows), ``--f-tile`` lanes of F a grid step, and for a call
  past its ``RIDE_ROWS`` (each expert over its own rows) ``--row-tile`` rows
  of an expert's own at a time;
* ``gmm`` (the zero-line baseline of PR 40: three calls of jax's own
  ``megablox.gmm`` over the whole stacks, the layer's group sizes written
  into a zero ``[layers x E]`` vector, behind ``sorted_dispatch``).

``--touched N`` folds the router's choices onto the layer's first N experts
(a row's two choices may then meet in one expert: the weights add), so a
form's time can be read against the bytes it has to move. The time is the
device's: the median duration of the program's executions in a profiler
trace, over the layers. The numbers behind
``moe_lm._SORTED_DISPATCH_MIN_ROWS`` (PERF.md section 6, PR 26),
``moe_lm._GROUPED_KERNEL_MAX_ROWS`` (PR 40, PR 53) and the kernel's
``ROW_TILE`` (PR 53). TPU only: a time from another
backend says nothing about either, so the script refuses to print one.
``--preset FAMILY SIZE`` is any MoE preset, at 8 layers of depth (PR 33:
``sdar 30b-a3b-ep8``, 16 held experts of 2,048 x 768 of a router's 128,
top-8; PR 40: ``smallthinker 21b-a3b-12l``, 64 of 2,560 x 768, top-6, and
``solar_open2 250b-4l-ep8``, 40 held of 320 of 4,096 x 1,280, top-8; PR 53:
``lfm2_moe 24b-a2b-9l``, 64 of 2,048 x 1,536, top-4).
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
from deepspeed_tpu.moe.sharded_moe import sorted_dispatch
# the module, which the package's function of the same name hides
kernel_module = sys.modules[moe_lm.grouped_expert_mlp.__module__]

FORMS = ("sorted", "dense", "kernel", "gmm")
N_LAYER = 8
_f_tile = kernel_module._f_tile         # the kernel's own rule (--f-tile 0)
ROW_TILE = kernel_module.ROW_TILE       # and its own row tile (--row-tile 0)


def _gmm_mlp(model, lp, whole, layer, h):
    """``_nodrop_mlp``'s routed part with ``megablox.gmm`` over the whole
    stacks: (out, counts)."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    E = model.moe.num_experts
    tokens = h.reshape(-1, h.shape[-1])
    weights, experts, *_ = model._route(lp, tokens)
    rows = tokens.shape[0] * model.moe.k
    tm = next(t for t in (128, 64, 32, 16, 8) if rows % t == 0)

    def grouped(xs, sizes):
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((N_LAYER * E,), jnp.int32), sizes, (layer * E,))

        def dot(a, w):
            w = w.reshape(-1, *w.shape[2:])
            return gmm(a, w, sizes, preferred_element_type=jnp.float32,
                       tiling=(tm, w.shape[1], 256))
        act = model._act(dot(xs, whole["w_up"]), dot(xs, whole["w_gate"]))
        return dot(act.astype(xs.dtype), whole["w_down"])

    out, counts = sorted_dispatch(tokens, weights, experts, E, grouped)
    return out.reshape(h.shape), counts


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, nargs="+",
                    default=[1, 16, 64, 512, 1024, 1536, 2048])
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--preset", nargs=2, default=["olmoe", "1b-7b-8l"],
                    metavar=("FAMILY", "SIZE"))
    ap.add_argument("--forms", nargs="+", default=["sorted", "dense"],
                    choices=FORMS)
    ap.add_argument("--touched", type=int, nargs="+", default=[0],
                    help="fold the routing onto the first N experts "
                         "(0: as the router has it)")
    ap.add_argument("--f-tile", type=int, nargs="+", default=[0],
                    help="the kernel form's F tile (0: its own)")
    ap.add_argument("--row-tile", type=int, nargs="+", default=[0],
                    help="rows of an expert's own a visit of the kernel form "
                         "computes at a time in a call past its RIDE_ROWS "
                         "(0: its own ROW_TILE)")
    args = ap.parse_args()
    platform = jax.devices()[0].platform
    if platform != "tpu":
        sys.exit(f"moe_dispatch_bench: the default device is {platform!r}, "
                 "not a TPU: no time is taken")
    model = get_model(*args.preset, n_layer=N_LAYER, param_dtype=jnp.bfloat16)
    cfg, moe = model.config, model.moe
    E = moe.num_experts
    mlp = jax.jit(lambda k: model._mlp_params(k, N_LAYER))(jax.random.key(0))
    jax.block_until_ready(mlp)
    layer_bytes = 3 * E * cfg.d_model * model.expert_ff * 2
    print(f"device {jax.devices()[0].device_kind}; a layer's experts "
          f"{layer_bytes / 1e6:.0f} MB", flush=True)
    keys = model._expert_keys()
    route = model._route
    touched = [0]

    def folded(lp, tokens):
        """The router's choices folded onto the first ``touched[0]`` experts."""
        weights, experts, *rest = route(lp, tokens)
        if touched[0]:
            experts = jnp.where(experts < E, experts * touched[0] // E, E)
        return (weights, experts, *rest)
    model._route = folded

    variants = []
    for rows in args.rows:
        for n in args.touched:
            for form in args.forms:
                if form == "kernel" and rows > kernel_module.MAX_ROWS:
                    continue
                tiled = form == "kernel" and rows > kernel_module.RIDE_ROWS
                for tf in (args.f_tile if form == "kernel" else [0]):
                    for tm in (args.row_tile if tiled else [0]):
                        variants.append((rows, n, form, tf, tm))

    runs, outs = {}, {}
    for rows, n, form, tf, tm in variants:
        x = jax.random.normal(jax.random.key(rows), (1, rows, cfg.d_model),
                              jnp.bfloat16)

        def stack(mlp, x, form=form):
            whole = {k: mlp[k] for k in keys}
            sliced = mlp if form in ("sorted", "dense") else \
                {k: w for k, w in mlp.items() if k not in keys}

            def body(h, xs):
                lp, layer = xs
                if form == "kernel":
                    out, _, counts, _ = model._nodrop_mlp(
                        lp, h, stack=(whole, layer))
                elif form == "gmm":
                    out, counts = _gmm_mlp(model, lp, whole, layer, h)
                else:
                    out, _, counts, _ = model._nodrop_mlp(lp, h)
                # the stream kept at RMS 1: no norm sits between these MLPs,
                # and some presets' init would overflow it in 8 layers
                h = (h + out).astype(jnp.float32)
                h = h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True))
                return h.astype(x.dtype), counts
            return jax.lax.scan(body, x, (sliced, jnp.arange(N_LAYER)))
        name = f"moe_{form}_r{rows}_t{n}_f{tf}_m{tm}"
        # the name is what the trace files the program's executions under
        stack.__name__ = name
        # read while tracing
        moe_lm._SORTED_DISPATCH_MIN_ROWS = 0 if form == "sorted" else 1 << 30
        kernel_module._f_tile = (lambda *_, tf=tf: tf) if tf else _f_tile
        kernel_module.ROW_TILE = tm or ROW_TILE
        touched[0] = n
        run = jax.jit(stack)
        try:
            outs[name] = jax.block_until_ready(run(mlp, x))
        except Exception as e:      # a form the compiler refuses: say so, go on
            print(json.dumps({"rows": rows, "touched": n, "form": form,
                              "f_tile": tf, "row_tile": tm,
                              "error": str(e)[:400]}), flush=True)
            continue
        runs[name] = (run, x, rows, n, form, tf, tm)

    trace_dir = tempfile.mkdtemp(prefix="moe_dispatch_bench_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    for run, x, *_ in runs.values():
        for _ in range(args.reps):
            out = run(mlp, x)
        jax.block_until_ready(out)
    jax.profiler.stop_trace()
    trace = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
    programs = trace["devices"][trace_reduce.busiest_device(trace)]["programs"]

    first = {}
    for name, (_, _, rows, n, form, tf, tm) in runs.items():
        took = [d for prog, _, d in programs if name + "(" in prog + "("]
        if len(took) != args.reps:
            sys.exit(f"{name}: {len(took)} executions in the trace, "
                     f"{args.reps} were run; the trace's programs: "
                     f"{sorted({prog for prog, _, _ in programs})}")
        ms = statistics.median(took) / N_LAYER * 1e3
        out, counts = outs[name]
        n_touched = float((counts > 0).sum() / N_LAYER)
        line = {"rows": rows, "touched": n, "form": form,
                "device_ms_per_layer": round(ms, 4),
                "experts_touched_per_layer": n_touched,
                "touched_weight_gb_per_s":
                    round(layer_bytes * n_touched / E / ms / 1e6, 1)}
        if form == "kernel":
            line["f_tile"] = tf or _f_tile(cfg.d_model, model.expert_ff, 2)
            if rows > kernel_module.RIDE_ROWS:
                line["row_tile"] = tm or ROW_TILE
        ref = first.setdefault((rows, n), out.astype(jnp.float32))
        line["max_abs_diff_from_first_form"] = \
            float(jnp.abs(out.astype(jnp.float32) - ref).max())
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
