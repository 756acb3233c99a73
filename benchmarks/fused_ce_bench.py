"""The fused cross-entropy kernels alone on a TPU, by shape and tile.

    python benchmarks/fused_ce_bench.py [--shapes bloom gpt2 d4096]
        [--fwd-tiles 0 256 ...] [--block-t N] [--block-v N]
        [--other LABEL=FILE ...] [--calls 4] [--reps 3]

One call is a whole vocab head's ``value_and_grad``: ``fused_ce_fwd``, then
``fused_ce_dh`` and ``fused_ce_dw``. ``bloom`` is ``bloom560m_train_1chip``'s
(D 1,024, V 250,880, 4 x 2,048 tokens: the one cell of the benchmark that
runs these kernels), ``gpt2`` is ``chip_smoke.py``'s (D 768, V 50,257 padded
to 50,688, 32 x 1,024 tokens), ``d4096`` a 7B-class head's (D 4,096,
V 32,000, 4,096 tokens); all bf16 with a tenth of the labels masked.
``--fwd-tiles`` are the token tiles offered to the forward
(``fused_cross_entropy._FWD_TILES``; 0 = the module's own list, N = that one
tile where it divides, else the backward's); ``--block-t`` / ``--block-v``
go to every version as ``block_t`` / ``block_v`` (256 / 512 with
``--fwd-tiles 256`` are the parent's tiles at ``bloom`` and ``gpt2``).
``--other`` times another version of the kernels' module beside them, by
file (the parent's: ``git show HEAD~1:deepspeed_tpu/ops/pallas/
fused_cross_entropy.py > .chip_checkout/parent.py``).

The time is the device's: ``--calls`` chained calls in one program (each
call's ``h`` and ``W`` are the last call's plus its gradients, so that no
call can be hoisted out of the loop), each kernel's own events in a profiler
trace over their count, the median of ``--reps`` executions. ``floor_share``
is ``perfbench/costs.py``'s least time for that kernel (operations over 197
TFLOP/s or bytes over 819 GB/s, ``perfbench/peaks.json``) over the time
taken: what ``fused_ce_roofline`` reads for the three together.
``program_ms`` is a whole call, the chain's two additions included. Each
line also holds the loss and both gradients of ONE call to
``models.transformer.chunked_vocab_ce`` (largest difference over the
reference's largest value). The numbers behind ``_FWD_TILES`` and
``_FWD_VMEM_BYTES`` (PERF.md section 6, PR 50). TPU only: the script refuses
to print a time from another backend.
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

import costs
import trace_reduce

#: shape -> (tokens, D, V)
SHAPES = {"bloom": (4 * 2048, 1024, 250880),
          "gpt2": (32 * 1024, 768, 50257),
          "d4096": (4096, 4096, 32000)}
# fwd / dh / dw -> the name pattern ``fused_ce_roofline`` reads that kernel by
with open(os.path.join(ROOT, "perfbench", "layer_metrics",
                       "fused_ce_roofline.json")) as f:
    KERNELS = {cost.removeprefix("fused_ce_"): pattern
               for pattern, cost in json.load(f)["params"]["kernels"].items()}


def load_other(spec):
    """``LABEL=FILE`` -> (label, the file's module)."""
    label, path = spec.split("=", 1)
    mod_spec = importlib.util.spec_from_file_location(f"fused_ce_{label}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return label, mod


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="+", default=list(SHAPES), choices=list(SHAPES))
    ap.add_argument("--fwd-tiles", type=int, nargs="*", default=[0])
    ap.add_argument("--block-t", type=int, default=None)
    ap.add_argument("--block-v", type=int, default=None)
    ap.add_argument("--other", nargs="*", default=[], metavar="LABEL=FILE")
    ap.add_argument("--calls", type=int, default=4)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=5000000001)
    args = ap.parse_args()
    dev0 = jax.devices()[0]
    if dev0.platform != "tpu":
        sys.exit(f"fused_ce_bench: the default device is {dev0.platform!r}, "
                 "not a TPU: no time is taken")
    with open(os.path.join(ROOT, "perfbench", "peaks.json")) as f:
        peak = json.load(f)["device_kinds"][dev0.device_kind]
    from deepspeed_tpu.models.transformer import chunked_vocab_ce
    tree = importlib.import_module("deepspeed_tpu.ops.pallas.fused_cross_entropy")
    # label -> (module, the tiles to offer this tree's forward)
    variants = {f"fwd{tile}" if tile else "tree": (tree, (tile,) if tile else tree._FWD_TILES)
                for tile in args.fwd_tiles}
    variants.update((label, (mod, None)) for label, mod in map(load_other, args.other))
    blocks = dict(block_t=args.block_t, block_v=args.block_v)

    runs = {}
    for shape in args.shapes:
        N, D, V = SHAPES[shape]
        rng = np.random.default_rng(args.seed % (1 << 31))
        h = jnp.asarray(rng.standard_normal((N, D), np.float32), jnp.bfloat16)
        w = jnp.asarray(rng.standard_normal((D, V), np.float32) * 0.02, jnp.bfloat16)
        labels = jnp.asarray(rng.integers(0, V, size=(N,)), jnp.int32)
        valid = jnp.asarray(rng.random((N,)) > 0.1)
        want = jax.jit(jax.value_and_grad(
            lambda h, w: chunked_vocab_ce(h[None], w, 0, labels[None], valid[None], 2048),
            argnums=(0, 1)))(h, w)
        want = [np.asarray(x, np.float32) for x in jax.tree.leaves(want)]

        for label, (mod, tiles) in variants.items():
            if tiles:
                tree._FWD_TILES = tiles

            def vag(h, w, mod=mod):
                return jax.value_and_grad(
                    lambda h, w: mod.fused_cross_entropy(h, w, labels, valid=valid, **blocks),
                    argnums=(0, 1))(h, w)

            got = jax.tree.leaves(jax.jit(vag)(h, w))
            err = max(float(np.abs(np.asarray(a, np.float32) - b).max() / np.abs(b).max())
                      for a, b in zip(got, want))

            def chain(h, w, vag=vag):
                def call(hw, _):
                    loss, (dh, dw) = vag(*hw)
                    return (hw[0] + dh, hw[1] + dw), loss
                return jax.lax.scan(call, (h, w), None, length=args.calls)[1]
            chain.__name__ = f"ce_{shape}_{label}"
            run = jax.jit(chain)
            jax.block_until_ready(run(h, w))
            geometry = (dict(zip(("bt", "bt_fwd", "bv", "bv_dw"),
                                 mod._tiles(N, D, V, 2, **blocks)))
                        if hasattr(mod, "_tiles") else {})
            runs[shape, label] = (run, (h, w), err, geometry)
        del want

    trace_dir = tempfile.mkdtemp(prefix="fused_ce_bench_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    for run, operands, *_ in runs.values():
        for _ in range(args.reps):
            jax.block_until_ready(run(*operands))
    jax.profiler.stop_trace()
    trace = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
    dev = trace["devices"][trace_reduce.busiest_device(trace)]

    execs = sorted((p for p in dev["programs"] if "jit_ce_" in p[0]),
                   key=lambda p: p[1])
    if len(execs) != len(runs) * args.reps:
        sys.exit(f"{len(execs)} executions in the trace, {len(runs)} x "
                 f"{args.reps} were run: {sorted({p[0] for p in execs})}")
    for n, ((shape, label), (*_, err, geometry)) in enumerate(runs.items()):
        N, D, V = SHAPES[shape]
        sizes = {"tokens_per_chip": N, "d_model": D, "vocab": V}
        line = {"shape": shape, "version": label, **geometry}
        took = {name: [] for name in (*KERNELS, "program")}
        for _, start, dur in execs[n * args.reps:(n + 1) * args.reps]:
            inside = [op for op in dev["ops"] if start <= op[1] < start + dur]
            for name, pattern in KERNELS.items():
                secs, calls = trace_reduce.matching(inside, pattern)
                if calls != args.calls:
                    sys.exit(f"{shape} {label}: {calls} {name} calls in an "
                             f"execution of {args.calls}")
                took[name].append(secs / calls)
            took["program"].append(dur / args.calls)
        ms = {name: sorted(ts)[args.reps // 2] * 1e3 for name, ts in took.items()}
        floors = {name: costs.roofline_seconds(
            getattr(costs, f"fused_ce_{name}")(sizes), peak) for name in KERNELS}
        for name, (floor, roof) in floors.items():
            line[name] = {"ms_per_call": round(ms[name], 3), "floor_ms": round(floor * 1e3, 3),
                          "floor_share": round(floor * 1e3 / ms[name], 4), "roof": roof}
        line["three_floor_share"] = round(
            sum(f for f, _ in floors.values()) * 1e3 / sum(ms[k] for k in KERNELS), 4)
        line["program_ms"] = round(ms["program"], 3)
        line["max_rel_err_from_loss_chunk"] = float(f"{err:.3g}")
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
