"""Deviceless sizing: compile a cell's programs for a DESCRIBED v5e and read
``memory_analysis()``. Nothing runs, so nothing here is a time.

    JAX_PLATFORMS=cpu python perfbench/tools/aot_size.py train bloom 560m --batch 4 6
    JAX_PLATFORMS=cpu python perfbench/tools/aot_size.py train opt 1.3b --chips 4 --batch 2 4
    JAX_PLATFORMS=cpu python perfbench/tools/aot_size.py serve opt 1.3b --blocks 208 224 --rows 40 --prefill 1792

``train`` compiles the loss-and-gradient program (``jax.value_and_grad`` of
the model's loss) the train engine wraps; the engine's state (16 B a
parameter: bf16 copy, fp32 master, two Adam moments, bf16 gradients) is added
by arithmetic. ``serve`` compiles ``forward_paged_decode`` and
``forward_paged_prefill`` with the pools donated, as ``inference/engine.py``
jits them. The sizing rules that read these numbers are in ``PERF.md`` §4.
"""

import argparse
import contextlib
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

GB = 1e9


def describe(chips):
    from jax.experimental import topologies
    return topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2" if chips == 4 else "v5e:1x1",
        **({} if chips == 4 else
           dict(chips_per_host_bounds=(1, 1, 1), num_slices=1))).devices


def report(tag, compiled):
    m = compiled.memory_analysis()
    print(f"{tag}: arguments {m.argument_size_in_bytes / GB:.2f} GB, outputs "
          f"{m.output_size_in_bytes / GB:.2f} GB, aliased "
          f"{m.alias_size_in_bytes / GB:.2f} GB, temporaries "
          f"{m.temp_size_in_bytes / GB:.2f} GB", flush=True)
    return m


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("kind", choices=["train", "serve"])
    ap.add_argument("family")
    ap.add_argument("size")
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--batch", type=int, nargs="+", default=[4])
    ap.add_argument("--remat", default="dots")
    ap.add_argument("--blocks", type=int, nargs="+", default=[224])
    ap.add_argument("--rows", type=int, default=40)
    ap.add_argument("--block-size", type=int, default=128)
    ap.add_argument("--prefill", type=int, nargs="+", default=[1792])
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.models.presets import get_model
    from deepspeed_tpu.ops import dispatch
    dispatch.on_tpu = lambda: True

    devs = describe(args.chips)
    print(f"described: {len(devs)} x {devs[0].device_kind}", flush=True)

    if args.kind == "train":
        model = get_model(args.family, args.size, remat=args.remat)
        shapes = jax.eval_shape(model.init_params, jax.random.key(0))
        n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
        print(f"{n / 1e6:.0f}M parameters; engine state at 16 B a parameter "
              f"{16 * n / GB / args.chips:.2f} GB a chip", flush=True)
        if args.chips == 1:
            bsh = SingleDeviceSharding(devs[0])
            params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, jnp.bfloat16, sharding=bsh), shapes)
            mesh = None
        else:
            from deepspeed_tpu.runtime.zero.partition import ZeroShardingRules
            mesh = Mesh(np.array(devs).reshape(args.chips), ("fsdp",))
            dist.set_mesh(mesh)
            from deepspeed_tpu.runtime.zero.config import ZeroConfig
            rules = ZeroShardingRules(mesh, ZeroConfig(stage=3))
            psh = rules.param_shardings(shapes, None)
            params = jax.tree.map(
                lambda a, s: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16,
                                                  sharding=s), shapes, psh)
            bsh = NamedSharding(mesh, P("fsdp"))
        for b in args.batch:
            batch = {"input_ids": jax.ShapeDtypeStruct(
                (b * args.chips, args.seq), jnp.int32, sharding=bsh)}

            def lg(p, bt):
                out = model.loss(p, bt, None)
                return out[0] if isinstance(out, tuple) else out
            fn = jax.jit(jax.value_and_grad(lg))
            with mesh if mesh is not None else contextlib.nullcontext():
                compiled = fn.lower(params, batch).compile()
            m = report(f"loss+grad, {b} x {args.seq} a chip", compiled)
            text = compiled.as_text()
            print("  collectives in the program text:",
                  {k: len(re.findall(k + r"[-.( ]", text))
                   for k in ("all-gather", "reduce-scatter", "all-reduce")},
                  flush=True)
            total = 14 * n / args.chips + m.output_size_in_bytes \
                + m.temp_size_in_bytes
            print(f"  state 14 B + gradients + temporaries = "
                  f"{total / GB:.2f} GB a chip of 15.75", flush=True)
        print("forms:", sorted(dispatch.selected()), flush=True)
        return

    model = get_model(args.family, args.size)
    cfg = model.config
    sh = SingleDeviceSharding(devs[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sh)
    shapes = jax.eval_shape(model.init_params, jax.random.key(0))
    params = jax.tree.map(lambda a: sds(a.shape, jnp.bfloat16), shapes)
    n_max = -(-cfg.max_seq // args.block_size)
    W = args.rows
    for nb in args.blocks:
        pools = jax.tree.map(
            lambda a: sds(a.shape, a.dtype),
            jax.eval_shape(lambda: model.init_paged_cache(
                nb, args.block_size, dtype=jnp.bfloat16)))
        pool_b = sum(int(np.prod(a.shape)) * 2 for a in jax.tree.leaves(pools))
        print(f"{nb} blocks: pools {pool_b / GB:.2f} GB "
              f"({nb * args.block_size} tokens)", flush=True)
        dec = jax.jit(lambda p, t, pools, bt, pos:
                      model.forward_paged_decode(p, t, pools, bt, pos),
                      donate_argnums=(2,))
        try:
            c = dec.lower(params, sds((W, 1), jnp.int32), pools,
                          sds((W, n_max), jnp.int32),
                          sds((W,), jnp.int32)).compile()
            report(f"  decode, {W} rows", c)
        except Exception as e:  # noqa: BLE001 — the compiler's refusal is the result
            print(f"  decode, {W} rows: REFUSED: {str(e)[:300]}", flush=True)
        for T in args.prefill:
            pre = jax.jit(lambda p, t, pools, slots, li:
                          model.forward_paged_prefill(p, t, pools, slots, li),
                          donate_argnums=(2,))
            try:
                c = pre.lower(params, sds((1, T), jnp.int32), pools,
                              sds((T,), jnp.int32),
                              sds((), jnp.int32)).compile()
                report(f"  prefill, {T} tokens", c)
            except Exception as e:  # noqa: BLE001
                print(f"  prefill, {T}: REFUSED: {str(e)[:300]}", flush=True)
    print("forms:", sorted(dispatch.selected()), flush=True)


if __name__ == "__main__":
    main()
