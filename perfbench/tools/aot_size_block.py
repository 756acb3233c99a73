"""``aot_size.py serve`` for a model that generates by diffusion over blocks
(a ``generation`` record on its config): the fused block step, the decision
included (what the serving engine compiles as ``paged_block``: feed,
``forward_paged_block``, ``unmask``), and the block-causal prefills, compiled
for a DESCRIBED v5e. Nothing runs, so nothing here is a time.

    JAX_PLATFORMS=cpu python perfbench/tools/aot_size_block.py sdar 30b-a3b-ep8 \\
        --blocks 328 --rows 64 --prefill 128 256 [--hlo-dir /root/scratch]

``aot_size.py`` compiles ``forward_paged_decode``, which such a model does
not serve with, and is a file this PR could not edit (PERF.md section 7(b)
asks a ``benchmark`` PR to fold these tools into one); ``describe`` and
``report`` are its own.
"""

import argparse
import os

from aot_size import GB, describe, report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("family")
    ap.add_argument("size")
    ap.add_argument("--blocks", type=int, default=328)
    ap.add_argument("--rows", type=int, default=64)
    ap.add_argument("--block-size", type=int, default=128)
    ap.add_argument("--prefill", type=int, nargs="+", default=[128, 256])
    ap.add_argument("--hlo-dir", help="write each compiled program's text here")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from deepspeed_tpu.inference import blockgen
    from deepspeed_tpu.models.presets import get_model
    from deepspeed_tpu.ops import dispatch
    dispatch.on_tpu = lambda: True

    sh = SingleDeviceSharding(describe(1)[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sh)  # noqa: E731
    nbytes = lambda tree: sum(  # noqa: E731
        int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
        for a in jax.tree.leaves(tree))
    model = get_model(args.family, args.size)
    gen = model.config.generation
    shapes = jax.eval_shape(model.init_params, jax.random.key(0))
    params = jax.tree.map(lambda a: sds(a.shape, jnp.bfloat16), shapes)
    W, nb, bs, Bg = args.rows, args.blocks, args.block_size, gen.block
    n_max = -(-model.config.max_seq // bs)
    pools = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: model.init_paged_cache(nb, bs, dtype=jnp.bfloat16)))
    print(f"weights {nbytes(params) / GB:.2f} GB; pools {nbytes(pools) / GB:.2f} GB",
          flush=True)

    def keep(tag, compiled):
        report(tag, compiled)
        mem = compiled.memory_analysis()
        print(f"  aliased {mem.alias_size_in_bytes / GB:.2f} GB of the pools' "
              f"{nbytes(pools) / GB:.2f}; temporaries "
              f"{mem.temp_size_in_bytes / 1e6:.1f} MB", flush=True)
        if args.hlo_dir:
            name = tag.replace(" ", "_").replace(",", "") + ".hlo.txt"
            with open(os.path.join(args.hlo_dir, name), "w") as f:
                f.write(compiled.as_text())

    def step(p, prev, idx, host, pools, bt, pos, n_decide, commit):
        state = blockgen.feed(prev, idx, host)
        logits, pools, aux = model.forward_paged_block(
            p, blockgen.tokens_of(gen, state), pools, bt, pos)
        return blockgen.unmask(gen, logits, state, n_decide, commit), pools, aux

    blk = sds((W, Bg), jnp.int32)
    keep(f"block step, {W} rows of {Bg}", jax.jit(step, donate_argnums=(4,)).lower(
        params, blk, sds((W,), jnp.int32), blk, pools,
        sds((W, n_max), jnp.int32), sds((W,), jnp.int32),
        sds((W,), jnp.int32), sds((W,), jnp.bool_)).compile())
    pre = jax.jit(lambda p, t, pools, slots, li: model.forward_paged_prefill(
        p, t, pools, slots, li), donate_argnums=(2,))
    for T in args.prefill:
        keep(f"prefill, {T} tokens", pre.lower(
            params, sds((1, T), jnp.int32), pools, sds((T,), jnp.int32),
            sds((), jnp.int32)).compile())
    print("forms:", sorted(dispatch.selected()), flush=True)


if __name__ == "__main__":
    main()
