"""Digests of the jaxprs an AUTOREGRESSIVE model's serving programs and the
flash kernel trace to: what ``test_sdar.py`` pins against the values
recorded on the commit before generation by blocks came in (PR 33), since
the kinds table, ``_grouped_cache_einsum``, ``_qk_norm`` and
``flash_attention`` are shared with a model that generates by blocks.

Run as a script it prints the digests of the tree it is run in
(``JAX_PLATFORMS=cpu python tests/unit/paged_program_digests.py`` from the
root of a checkout): how the recorded values were taken, and how they are
taken again when a later PR changes one of these programs on purpose.
"""

import hashlib
import re

import jax
import jax.numpy as jnp

BS = 16


def _digest(fn, *args) -> str:
    text = str(jax.make_jaxpr(fn)(*args))
    # a kernel's name carries its source line; addresses are the run's
    text = re.sub(r"\S+\.py:\d+", "", text)
    text = re.sub(r" at 0x[0-9a-f]+", "", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _models():
    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.models.presets import get_model
    from deepspeed_tpu.models.transformer import TransformerConfig
    opt = CausalLM(TransformerConfig(
        vocab_size=512, max_seq=256, n_layer=2, n_head=4, d_model=64,
        d_ff=128, activation="relu", attn_bias=True))
    return {"opt": opt, "olmoe": get_model("olmoe", "tiny", max_seq=256),
            # the two presets with recurrent layers (KDA over an MoE; Mamba-2):
            # recorded on the commit before PR 47 moved their mixers
            "solar_open2": get_model("solar_open2", "tiny", max_seq=256),
            "granite_hybrid": get_model("granite_hybrid", "tiny", max_seq=256),
            # and their programs as the chip takes them: the state-update
            # kernels (interpreted here) in the plain-XLA twins' place (the
            # KDA kernel wants a value width of whole lane tiles)
            "solar_open2.kernels": get_model(
                "solar_open2", "tiny", max_seq=256, lin_head_dim=128,
                attention_backend="flash"),
            "granite_hybrid.kernels": get_model(
                "granite_hybrid", "tiny", max_seq=256,
                attention_backend="flash")}


def program_digests() -> dict:
    i32 = jnp.int32
    out = {}
    for name, model in _models().items():
        params = jax.eval_shape(model.init_params, jax.random.key(0))
        stateful = bool(model.config.cache_spec["state"])
        pools = jax.eval_shape(lambda: model.init_paged_cache(
            8, BS, dtype=jnp.float32, state_slots=4 if stateful else 0))
        sds = jax.ShapeDtypeStruct
        # a stack with recurrent state takes its rows' slots / its request's
        # slot as the programs' last operand, and prompts in whole KDA chunks
        rows = (None, sds((3,), i32)) if stateful else ()
        one = (sds((), i32),) if stateful else ()
        T = 64 if stateful else 32               # the prefill bucket
        out[f"{name}.decode"] = _digest(
            model.forward_paged_decode, params, sds((3, 1), i32), pools,
            sds((3, 16), i32), sds((3,), i32), *rows)
        out[f"{name}.prefill"] = _digest(
            model.forward_paged_prefill, params, sds((1, T), i32), pools,
            sds((T,), i32), sds((), i32), *one)
        out[f"{name}.prefill_chunk"] = _digest(
            model.forward_paged_prefill_chunk, params, sds((1, T), i32),
            pools, sds((1, 16), i32), sds((T,), i32), sds((), i32),
            sds((), i32), *one)
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=True)

    def flash_loss(q, k, v):
        return jax.grad(lambda *a: flash(*a).sum(), argnums=(0, 1, 2))(q, k, v)

    f32 = jnp.float32
    shapes = {"gqa": ((1, 256, 4, 128), (1, 256, 2, 128)),      # plain
              "packed": ((1, 256, 4, 64), (1, 256, 4, 64)),     # packed heads
              "padded": ((1, 200, 4, 128), (1, 200, 2, 128))}   # computed bias
    for tag, (qs, ks) in shapes.items():
        q, k = jax.ShapeDtypeStruct(qs, f32), jax.ShapeDtypeStruct(ks, f32)
        out[f"flash.{tag}.fwd"] = _digest(flash, q, k, k)
        out[f"flash.{tag}.grad"] = _digest(flash_loss, q, k, k)
    return out


if __name__ == "__main__":
    import json
    print(json.dumps(program_digests(), indent=1))
