"""``aot_size.py serve`` for a model whose cache spec has a recurrent state:
the decode step and the prefills compiled for a DESCRIBED v5e with
``rows + 1`` state slots beside the KV pools and the slots operand the paged
programs of such a model take. Nothing runs, so nothing here is a time.

    JAX_PLATFORMS=cpu python perfbench/tools/aot_size_state.py solar_open2 250b-4l-ep8 \\
        --blocks 2064 --rows 128 --prefill 256 1024 [--hlo-dir /root/scratch]

``aot_size.py`` hands a model neither (``init_paged_cache(nb, bs, dtype)``,
``forward_paged_decode(p, t, pools, bt, pos)``) and is a file this PR could
not edit (PERF.md section 7); ``describe`` and ``report`` are its own.
"""

import argparse
import os

from aot_size import GB, describe, report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("family")
    ap.add_argument("size")
    ap.add_argument("--blocks", type=int, default=2064)
    ap.add_argument("--rows", type=int, default=128)
    ap.add_argument("--block-size", type=int, default=128)
    ap.add_argument("--prefill", type=int, nargs="+", default=[256, 1024])
    ap.add_argument("--hlo-dir", help="write each compiled program's text here")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from deepspeed_tpu.models.presets import get_model
    from deepspeed_tpu.ops import dispatch
    dispatch.on_tpu = lambda: True

    sh = SingleDeviceSharding(describe(1)[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sh)  # noqa: E731
    nbytes = lambda tree: sum(  # noqa: E731
        int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
        for a in jax.tree.leaves(tree))
    model = get_model(args.family, args.size)
    shapes = jax.eval_shape(model.init_params, jax.random.key(0))
    params = jax.tree.map(lambda a: sds(a.shape, jnp.bfloat16), shapes)
    W, nb, bs = args.rows, args.blocks, args.block_size
    n_max = -(-model.config.max_seq // bs)
    pools = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: model.init_paged_cache(
            nb, bs, dtype=jnp.bfloat16, state_slots=W + 1)))
    print(f"weights {nbytes(params) / GB:.2f} GB; pools {nbytes(pools) / GB:.2f} GB:",
          {k: f"{nbytes(v) / GB:.2f}" for k, v in pools.items()}, flush=True)

    def keep(tag, compiled):
        report(tag, compiled)
        if args.hlo_dir:
            name = tag.replace(" ", "_").replace(",", "") + ".hlo.txt"
            with open(os.path.join(args.hlo_dir, name), "w") as f:
                f.write(compiled.as_text())

    dec = jax.jit(lambda p, t, pools, bt, pos, ss: model.forward_paged_decode(
        p, t, pools, bt, pos, state_slots=ss), donate_argnums=(2,))
    keep(f"decode, {W} rows", dec.lower(
        params, sds((W, 1), jnp.int32), pools, sds((W, n_max), jnp.int32),
        sds((W,), jnp.int32), sds((W,), jnp.int32)).compile())
    pre = jax.jit(lambda p, t, pools, slots, li, ss: model.forward_paged_prefill(
        p, t, pools, slots, li, state_slot=ss), donate_argnums=(2,))
    for T in args.prefill:
        keep(f"prefill, {T} tokens", pre.lower(
            params, sds((1, T), jnp.int32), pools, sds((T,), jnp.int32),
            sds((), jnp.int32), sds((), jnp.int32)).compile())
    print("forms:", sorted(dispatch.selected()), flush=True)


if __name__ == "__main__":
    main()
