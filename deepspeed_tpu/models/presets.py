"""Architecture presets for the model zoo.

Coverage target: the model families the reference injects kernels for
(``deepspeed/module_inject/containers/*.py`` — gpt2, gptj, gptneo, gptneox,
opt, bloom, megatron) plus Llama-class models (the BASELINE.json north-star
config). Sizes follow the published architecture tables.
"""

from __future__ import annotations

import math
from typing import Callable, Dict

import jax.numpy as jnp

from deepspeed_tpu.models.causal_lm import CausalLM
from deepspeed_tpu.models.transformer import TransformerConfig


def gpt2(size: str = "125m", **over) -> CausalLM:
    dims = {
        "125m": dict(n_layer=12, n_head=12, d_model=768),
        "350m": dict(n_layer=24, n_head=16, d_model=1024),
        "774m": dict(n_layer=36, n_head=20, d_model=1280),
        "1.5b": dict(n_layer=48, n_head=25, d_model=1600),
    }[size]
    cfg = TransformerConfig(vocab_size=50257, max_seq=1024, pos_embedding="learned", norm="layernorm",
                            activation="gelu", tie_embeddings=True, attn_bias=True, **dims, **over)
    return CausalLM(cfg)


def gpt2_medium(**over) -> CausalLM:
    return gpt2("350m", **over)


def gpt2_large(**over) -> CausalLM:
    return gpt2("774m", **over)


def gpt2_xl(**over) -> CausalLM:
    return gpt2("1.5b", **over)


def llama_7b(**over) -> CausalLM:
    cfg = TransformerConfig(vocab_size=32000, n_layer=32, n_head=32, d_model=4096, d_ff=11008, max_seq=2048,
                            pos_embedding="rope", norm="rmsnorm", activation="swiglu", tie_embeddings=False,
                            norm_eps=1e-6, **over)
    return CausalLM(cfg)


def llama(size: str = "7b", **over) -> CausalLM:
    dims = {
        "tiny": dict(n_layer=4, n_head=8, d_model=512, d_ff=1408, vocab_size=32000, max_seq=512),
        "7b": dict(n_layer=32, n_head=32, d_model=4096, d_ff=11008, vocab_size=32000, max_seq=2048),
        "13b": dict(n_layer=40, n_head=40, d_model=5120, d_ff=13824, vocab_size=32000, max_seq=2048),
        "70b": dict(n_layer=80, n_head=64, d_model=8192, d_ff=28672, n_kv_head=8, vocab_size=32000, max_seq=4096),
    }[size]
    cfg = TransformerConfig(pos_embedding="rope", norm="rmsnorm", activation="swiglu", tie_embeddings=False,
                            norm_eps=1e-6, **{**dims, **over})
    return CausalLM(cfg)


def bloom(size: str = "560m", **over) -> CausalLM:
    dims = {
        "560m": dict(n_layer=24, n_head=16, d_model=1024),
        "1b7": dict(n_layer=24, n_head=16, d_model=2048),
        "7b1": dict(n_layer=30, n_head=32, d_model=4096),
        "176b": dict(n_layer=70, n_head=112, d_model=14336),
    }[size]
    cfg = TransformerConfig(vocab_size=250880, max_seq=2048, pos_embedding="alibi", norm="layernorm",
                            activation="gelu", tie_embeddings=True, embed_layernorm=True,
                            attn_bias=True, **dims, **over)
    return CausalLM(cfg)


def opt(size: str = "125m", **over) -> CausalLM:
    dims = {
        "125m": dict(n_layer=12, n_head=12, d_model=768),
        "1.3b": dict(n_layer=24, n_head=32, d_model=2048),
        "6.7b": dict(n_layer=32, n_head=32, d_model=4096),
        "13b": dict(n_layer=40, n_head=40, d_model=5120),
        "30b": dict(n_layer=48, n_head=56, d_model=7168),
        "66b": dict(n_layer=64, n_head=72, d_model=9216),
    }[size]
    cfg = TransformerConfig(vocab_size=50272, max_seq=2048, pos_embedding="learned", norm="layernorm",
                            activation="relu", tie_embeddings=True, attn_bias=True, **dims, **over)
    return CausalLM(cfg)


def gpt_neox(size: str = "20b", **over) -> CausalLM:
    dims = {
        "tiny": dict(n_layer=4, n_head=8, d_model=512),
        "20b": dict(n_layer=44, n_head=64, d_model=6144),
    }[size]
    cfg = TransformerConfig(vocab_size=50432, max_seq=2048, pos_embedding="rope", norm="layernorm",
                            activation="gelu", parallel_residual=True, tie_embeddings=False,
                            attn_bias=True, **dims, **over)
    return CausalLM(cfg)


def olmoe(size: str = "1b-7b", **over):
    """OLMoE-1B-7B (Muennighoff et al. 2024; ``allenai/OLMoE-1B-7B-0125-
    Instruct`` config.json): pre-RMSNorm, no bias, query/key RMSNorm over the
    whole projection, rope in the half-split pairing, an untied head, and in
    every layer 64 gated-SiLU experts of width 1,024 of which a token takes
    the 8 of largest softmax probability, weights not renormalised, nothing
    dropped. ``1b-7b-8l`` is the same model at half depth (what one 16 GB
    chip holds beside a KV pool: perfbench's ``olmoe1b7b_serve_decode``);
    ``tiny`` keeps every kind of part at toy widths (8 experts, top-2)."""
    from deepspeed_tpu.models.moe_lm import MoECausalLM, MoEConfig
    published = dict(n_head=16, d_model=2048, vocab_size=50304, max_seq=4096)
    dims, moe = {
        "tiny": (dict(n_layer=2, n_head=4, d_model=128, vocab_size=50304, max_seq=2048),
                 dict(num_experts=8, k=2, expert_d_ff=64)),
        "1b-7b": (dict(n_layer=16, **published),
                  dict(num_experts=64, k=8, expert_d_ff=1024)),
        "1b-7b-8l": (dict(n_layer=8, **published),
                     dict(num_experts=64, k=8, expert_d_ff=1024)),
    }[size]
    param_dtype = over.pop("param_dtype", jnp.float32)
    cfg = TransformerConfig(pos_embedding="rope", norm="rmsnorm", norm_eps=1e-5,
                            rope_theta=10000.0, qk_norm=True, tie_embeddings=False,
                            attn_bias=False, **{**dims, **over})
    return MoECausalLM(cfg, MoEConfig(
        dispatch="nodrop", expert_activation="swiglu", norm_topk_prob=False,
        aux_loss_coef=0.01, **moe),
        param_dtype=param_dtype)


def solar_open2(size: str = "250b-4l-ep8", share: int = 0, **over):
    """Solar-Open2-250B (``upstage/Solar-Open2-250B`` config.json): 48
    pre-RMSNorm layers of d 4,096 without positions, in periods of four: a
    softmax GQA layer (64 query and 8 key/value heads of 128, a sigmoid
    output gate) and three KDA layers (the channel-wise gated delta rule:
    64 heads of 128 x 128 float32 state, a causal conv of 4 taps on q, k
    and v); in every layer 320 gated-SiLU experts of width 1,280, 8 a token
    by sigmoid score plus a selection bias, weights normalised, and one
    shared expert; an untied head over 196,608 rows. ``250b-4l-ep8`` is ONE
    CHIP OF THE EIGHT that share each layer, at one period of depth: the
    router keeps its 320 outputs, the 40 experts of ``share`` (0-7) are
    held here, attention, KDA and the shared expert are whole, the
    vocabulary is this chip's eighth (perfbench's
    ``solaropen2_serve_decode``). ``max_seq`` is what the deployment serves
    (it sizes the block tables; the model has no positions to run out of).
    Its token embedding is drawn at std 2.0, the RMS of the residual stream
    at the middle of the 48-layer stack under this init (a layer adds a
    mixer's 0.31 and an MoE's 0.26: 0.41 sqrt(24)), so that the four layers
    see what a stage of the deployment sees: over an untrained embedding's
    0.02 each layer's output is fifteen times its input, a router's flipped
    boundary choice (weights 1/8 each under sigmoid scores) moves the hidden
    state by 4%, bf16 rounding flips 7-30% of the choices a layer and the
    flips feed the next layer's. What a served-token check can and cannot
    see at this scale is in PERF.md section 6, PR 31.
    ``tiny`` keeps every kind of part at toy widths, a head size that is
    not d_model / heads, and 2 of 16 experts held, IN THE SAME REGIME: its
    matrices at 0.16 = 0.02 sqrt(4096 / 64), which puts its branches where
    the cut's are (mixers 0.17-0.35, MoE 0.31 against 0.15-0.32, 0.26), and
    its embedding at the same 2.0."""
    from deepspeed_tpu.models.moe_lm import MoECausalLM, MoEConfig
    dims, moe = {
        "tiny": (dict(n_layer=4, n_head=4, n_kv_head=2, head_size=32,
                      d_model=64, d_ff=64, vocab_size=512, max_seq=1024,
                      lin_heads=4, lin_head_dim=32,
                      init_std=0.16, embed_init_std=2.0),
                 dict(num_experts=2, router_experts=16, k=4, expert_d_ff=32,
                      shared_expert_d_ff=32)),
        "250b-4l-ep8": (dict(n_layer=4, n_head=64, n_kv_head=8, head_size=128,
                             d_model=4096, d_ff=1280, vocab_size=24576,
                             max_seq=2048, lin_heads=64, lin_head_dim=128,
                             embed_init_std=2.0),
                        dict(num_experts=40, router_experts=320, k=8,
                             expert_d_ff=1280, shared_expert_d_ff=1280)),
    }[size]
    param_dtype = over.pop("param_dtype", jnp.float32)
    moe = {**moe, **over.pop("moe", {})}
    cfg = TransformerConfig(
        pos_embedding="none", norm="rmsnorm", norm_eps=1e-5,
        activation="swiglu", tie_embeddings=False, attn_bias=False,
        attn_out_gate=True,
        layer_kinds=("attention",) + ("linear_attention",) * 3,
        **{**dims, **over})
    return MoECausalLM(cfg, MoEConfig(
        dispatch="nodrop", expert_activation="swiglu", scoring="sigmoid",
        norm_topk_prob=True, aux_loss_coef=0.0,
        expert_offset=share * moe["num_experts"], **moe),
        param_dtype=param_dtype)


def sdar(size: str = "30b-a3b-ep8", share: int = 0, rule: str = "sequential",
         steps: int = 4, threshold: float = 0.9, **over):
    """SDAR-30B-A3B-Chat (``JetLM/SDAR-30B-A3B-Chat`` config.json,
    ``model_type`` ``sdar_moe``): 48 pre-RMSNorm layers (eps 1e-6) of d
    2,048, each GQA (32 query and 4 key/value heads of 128, q and k
    RMS-normed head by head, rope theta 1,000,000 in the half-split pairing,
    no bias) and an MoE of 128 gated-SiLU experts of width 768, 8 a token by
    softmax probability, the 8 weights normalised, no shared expert; an
    untied head over 151,936 rows. It GENERATES BY DIFFUSION OVER BLOCKS:
    its config carries a ``BlockGeneration`` record (blocks of 4 positions,
    bidirectional inside a block and causal between blocks; ``steps``
    denoise passes a block and its commit), which the scheduler and the
    serving session read. ``30b-a3b-ep8`` is ONE CHIP OF THE EIGHT of a
    v5e-8 that share each layer, AT FULL DEPTH: the router keeps its 128
    outputs, the 16 experts of ``share`` (0-7) are held here, attention,
    router and norms are whole, the vocabulary is this chip's eighth, and
    ``[MASK]`` is the last id of the slice (the published 151,669 lies
    outside it). ``max_seq`` is what the deployment serves (it sizes the
    block tables only; rope has no table). ``rule`` defaults to
    ``sequential`` at 4 steps: the family's ``generate`` defaults to the
    confidence-threshold rule (0.9), whose order the served check cannot
    replay from the tokens alone; the device work of a pass is the same
    but for the order (perfbench's ``sdar30b_serve_blockgen``; PERF.md
    section 7). ``tiny`` keeps every kind of part at toy widths: 4 of 8
    experts held, top-2."""
    from deepspeed_tpu.models.moe_lm import MoECausalLM, MoEConfig
    from deepspeed_tpu.models.transformer import BlockGeneration
    dims, moe = {
        "tiny": (dict(n_layer=2, n_head=4, n_kv_head=2, head_size=32,
                      d_model=64, d_ff=32, vocab_size=512, max_seq=1024),
                 dict(num_experts=4, router_experts=8, k=2, expert_d_ff=32)),
        "30b-a3b-ep8": (dict(n_layer=48, n_head=32, n_kv_head=4,
                             head_size=128, d_model=2048, d_ff=768,
                             vocab_size=18992, max_seq=1024),
                        dict(num_experts=16, router_experts=128, k=8,
                             expert_d_ff=768)),
    }[size]
    param_dtype = over.pop("param_dtype", jnp.float32)
    moe = {**moe, **over.pop("moe", {})}
    dims = {**dims, **over}
    cfg = TransformerConfig(
        pos_embedding="rope", rope_theta=1e6, norm="rmsnorm", norm_eps=1e-6,
        activation="swiglu", tie_embeddings=False, attn_bias=False,
        qk_norm="head", generation=BlockGeneration(
            block=4, steps=steps, rule=rule, threshold=threshold,
            mask_id=dims["vocab_size"] - 1), **dims)
    return MoECausalLM(cfg, MoEConfig(
        dispatch="nodrop", expert_activation="swiglu", scoring="softmax",
        norm_topk_prob=True, aux_loss_coef=0.0,
        expert_offset=share * moe["num_experts"], **moe),
        param_dtype=param_dtype)


def smallthinker(size: str = "21b-a3b-12l", **over):
    """SmallThinker-21BA3B-Instruct (``PowerInfer/SmallThinker-21BA3B-
    Instruct`` config.json): 52 pre-RMSNorm layers (eps 1e-6) of d 2,560 in
    periods of four (``sliding_window_layout`` and ``rope_layout`` both
    0,1,1,1): a FULL attention layer WITHOUT positions, then three layers
    with a WINDOW of 4,096 and rope (theta 1,500,000, the half-split
    pairing); GQA of 28 query and 4 key/value heads of 128, no bias, no q/k
    norm. In every layer 64 ReLU-gated experts of width 768, 6 a token by
    a softmax over the 6 chosen logits, no shared expert, and the ROUTER
    READS THE ATTENTION'S INPUT (the first norm's output), the experts the
    post-attention norm's; an untied head over 151,936 rows. The config
    says what the serving path keeps: ``cache_spec`` counts the window
    layers apart, whose KV lives in a ring a running request.
    ``21b-a3b-12l`` is ONE PIPELINE STAGE of four over a v5e-4, cut from
    its 13 layers to three whole periods, with every expert, every head and
    the whole vocabulary (perfbench's ``smallthinker21b_serve_longctx``).
    Its seeded init makes the attention PEAKED, as a trained model's is:
    every matrix at std 0.045 (output projections depth-scaled), so that a
    head's scores have a standard deviation of ~5 and, of the 4,096-6,146
    keys a query sees, about 4 hold the mass (the largest weight ~0.4). At
    the default 0.02 the scores' deviation is 1.0, a head averages over
    ~1,400-2,100 keys, its output is 0.007 beside the MoE's 0.037, and a
    served token says nothing of the window, the ring or the positions.
    The token embedding is drawn at 8.0, a little over the RMS of the
    residual stream at the end of the 52-layer stack under this init (a
    layer adds a mixer's 0.73 and an MoE's 0.55: 0.92 sqrt(52) = 6.6): a
    peaked softmax multiplies what bf16 rounding its input carries, and
    over a small embedding the stage's layers feed that to one another
    (sound served tokens then fail the benchmark's check); 8.0 is where
    they read lowest. Chosen by measurement: PERF.md section 6, PR 37.
    ``tiny`` keeps every kind of part at toy widths with a window of 256
    (two blocks of 128) under a ``max_seq`` of 1,024, IN THE SAME REGIME a
    little lower: its matrices at 0.25 (q and k components of deviation
    2.0, its scores' 4) and its embedding at 4.0, because with 2 of 8
    experts a token one boundary choice of its router weighs five times
    the cut's and at the cut's numbers its own rehearsal fails sound."""
    from deepspeed_tpu.models.moe_lm import MoECausalLM, MoEConfig
    dims, moe = {
        "tiny": (dict(n_layer=4, n_head=4, n_kv_head=2, head_size=32,
                      d_model=64, d_ff=32, vocab_size=512, max_seq=1024,
                      attn_window=256, init_std=0.25, embed_init_std=4.0),
                 dict(num_experts=8, k=2, expert_d_ff=32)),
        "21b-a3b-12l": (dict(n_layer=12, n_head=28, n_kv_head=4,
                             head_size=128, d_model=2560, d_ff=768,
                             vocab_size=151936, max_seq=16384,
                             attn_window=4096, init_std=0.045,
                             embed_init_std=8.0),
                        dict(num_experts=64, k=6, expert_d_ff=768)),
    }[size]
    param_dtype = over.pop("param_dtype", jnp.float32)
    moe = {**moe, **over.pop("moe", {})}
    cfg = TransformerConfig(
        pos_embedding="rope", rope_theta=1.5e6, norm="rmsnorm", norm_eps=1e-6,
        activation="swiglu", tie_embeddings=False, attn_bias=False,
        layer_kinds=("attention",) + ("window_attention",) * 3,
        rope_kinds=("window_attention",), **{**dims, **over})
    return MoECausalLM(cfg, MoEConfig(**{**dict(
        dispatch="nodrop", expert_activation="reglu", scoring="softmax",
        norm_topk_prob=True, aux_loss_coef=0.0,
        router_input="mixer_input"), **moe}), param_dtype=param_dtype)


def granite_hybrid(size: str = "4.0-h-micro", **over) -> CausalLM:
    """granite-4.0-h-micro (``ibm-granite/granite-4.0-h-micro`` config.json,
    ``model_type`` ``granitemoehybrid``): 40 pre-RMSNorm layers (eps 1e-5)
    of d 2,048 WITHOUT positions, in periods of ten: five Mamba-2 layers, a
    softmax GQA layer (32 query and 8 key/value heads of 64, no bias, its
    scores times ``attention_multiplier`` 1/64, not 1/sqrt(64)), four more
    Mamba-2 layers (64 heads of 64 channels, a float32 state of 128 a
    channel, one group of B and C, a causal conv of 4 taps with a bias over
    4,352 channels, SSD chunks of 256); in every layer a dense gated-SiLU
    MLP of width 8,192 (``num_local_experts`` 0: the ``shared_mlp`` alone);
    a tied head over 100,352 rows; and Granite's scalars: the embedding
    times 12, every branch times 0.22 before it joins the residual stream,
    the logits over 8. 3,191,396,096 parameters, 6.38 GB in bf16: ONE
    v5e CHIP SERVES THE WHOLE MODEL, nothing cut (``reduced`` is empty in
    perfbench's ``granite4hmicro_serve_chat``). ``max_seq`` is what the
    deployment serves (it sizes the block tables; the model has no
    positions to run out of).
    Its seeded init: ``A_log`` = log U(1, 16), ``dt_bias`` the inverse
    softplus of a log-uniform (1e-3, 1e-1), ``D`` 1, the conv U(-1/2, 1/2)
    (the family's own draws), output projections depth-scaled, and EVERY
    MATRIX AT 0.0235 OVER A TOKEN EMBEDDING AT 0.004 (``init_std`` /
    ``embed_init_std``: the two knobs moved, on the chip, with
    ``benchmarks/granite_check_controls.py``; PERF.md section 6, PR 43). At
    the published scheme's 0.02 / 0.02 the residual stream is 12 x 0.02 =
    0.24 of embedding against 0.22 of everything the 40 layers add, the
    tied head serves the INPUT token back whatever the layers do, and a
    lost state, a dropped conv state and a residual multiplier of 1.0 all
    pass the served check (100% of served tokens the token they were
    computed from). A fault inside a Mamba-2 mixer turns the layers' sum
    without lengthening it (the gated norm fixes the mixer's output), so an
    echoed token sees none of them: the check sees them where the SOUND
    model leaves the echo and the faulted one does not, and on those tokens
    a sound program's bf16 noise is read against the check's 4 bf16 steps
    at the magnitude of the largest logit. The embedding at 0.004 puts the
    largest logit of a token on the edge of the echo just over 0.0625,
    where 4 steps are 3.1% of it (at 0.02 / 0.003 it lies just under, 4
    steps are 1.6%, and sound prompts read 3.1 and 3.4 of 4 with the
    state's faults seen on 1 prompt in 16); the matrices at 0.0235 let 1-2%
    of sound tokens leave the echo: sound prompts 0 of 320 refused, worst
    2.63 of 4; a lost state refused on 10 of 64 prompts, a dropped conv
    state on 9 of 64 (readings of 6-198 steps), a residual multiplier of
    1.0 on 16 of 16; a state rounded to bf16 and the attention's scale on
    none. At 0.022 nothing of the state is seen (0 of 32), at 0.025 it is
    seen on 4 prompts in 10 and sound prompts read 3.5, at 0.027 on 8 in
    10 and 2 of 64 SOUND prompts are refused: no seeded init refuses the
    state's faults on most prompts and keeps the room. What the tokens
    cannot hold, the logits do (``tests/unit/test_granite_hybrid.py``; the
    same tool's ``--logits`` on the chip and ``--logits --float32``).
    ``tiny`` is one whole published period of ten (attention sixth) at toy
    widths, every multiplier at its published value, IN THE PUBLISHED
    WIDTHS' REGIME: its matrices at 0.113 = 0.02 sqrt(2048 / 64), so that
    its projections' outputs are as large as the model's (at 0.02 over d 64
    its state is a thousandth of ``D x`` and losing it moves the logits by
    3e-6 of 0.17), and its embedding at 0.006, where its ten layers and not
    the x12 embedding decide the logits (the served check of its rehearsal
    refuses a wrong multiplier and a dropped conv state; a state rounded to
    bf16 moves its logits by 6.7e-6, a lost one by 2e-3:
    ``tests/unit/test_granite_hybrid.py``)."""
    dims = {
        "tiny": dict(n_layer=10, n_head=4, n_kv_head=2, head_size=16,
                     d_model=64, d_ff=128, vocab_size=512, max_seq=1024,
                     ssm_heads=4, ssm_head_dim=32, ssm_state=16, ssm_chunk=8,
                     init_std=0.113, embed_init_std=0.006),
        "4.0-h-micro": dict(n_layer=40, n_head=32, n_kv_head=8, head_size=64,
                            d_model=2048, d_ff=8192, vocab_size=100352,
                            max_seq=2048, ssm_heads=64, ssm_head_dim=64,
                            ssm_state=128, ssm_chunk=256,
                            init_std=0.0235, embed_init_std=0.004),
    }[size]
    param_dtype = over.pop("param_dtype", jnp.float32)
    published = dict(
        pos_embedding="none", norm="rmsnorm", norm_eps=1e-5,
        activation="swiglu", tie_embeddings=True, attn_bias=False,
        attn_scale=0.015625, embedding_multiplier=12.0,
        residual_multiplier=0.22, logits_scaling=8.0, ssm_conv_kernel=4,
        layer_kinds=("mamba2",) * 5 + ("attention",) + ("mamba2",) * 4)
    return CausalLM(TransformerConfig(**{**published, **dims, **over}),
                    param_dtype=param_dtype)


def longcat_flash(size: str = "omni-4l-ep32", **over):
    """LongCat-Flash-Omni's language model (``meituan-longcat/LongCat-Flash-
    Omni`` config.json; the audio/vision encoders and the codec decoder are
    no part of this family): 28 published layers of d 6,144, each TWO
    sub-blocks of pre-RMSNorm (eps 1e-5) multi-head LATENT attention (64
    heads; queries through a rank-1,536 bottleneck, keys and values from one
    latent of 512 a token beside a roped key part of 64 shared by the heads;
    keys 128 + 64, values 128; both bottlenecks RMSNormed (eps 1e-6) and
    scaled by sqrt(6144 / rank); rope theta 1e7 on interleaved pairs) and a
    dense gated-SiLU MLP of 12,288, around a SHORTCUT MoE that reads the
    first sub-block's post-attention norm and joins the stream at the end of
    the layer: a float32 softmax router over 768 outputs with a selection
    bias, top-12 weights NOT normalised and times 6, outputs 0-511 gated-SiLU
    experts of width 2,048 and outputs 512-767 ZERO-COMPUTE experts (weight
    times the input); an untied head over 131,072 rows. Here a published
    layer is one PERIOD of two ``latent_attention`` sub-blocks
    (``n_layer`` counts sub-blocks: two cache layers a published layer).
    ``omni-4l-ep32`` is ONE CHIP OF THE 32 that share each layer (expert
    parallel: experts 0-15 of 512 held, every zero expert, the attention,
    both dense MLPs and the router whole), of a pipeline stage of 4 of the
    28 layers, with an eighth of the vocabulary (16,384): 5,172,749,312
    parameters (perfbench's ``longcatflashomni_serve_ctx3k``). ``share``
    picks another of the 32. Its seeded init (``init_std``,
    ``embed_init_std``, the router's scale, the selection bias's deviation
    of half a mean score) is measured by
    ``benchmarks/longcat_check_controls.py`` (PERF.md section 6, PR 48).
    ``tiny``: 2 published layers at toy widths, 8 real experts of which 4
    held and 4 zero experts, top-3, keys 32 + 64 and values 16 (Dqk != Dv
    kept), a latent of 128."""
    from deepspeed_tpu.models.moe_lm import MoECausalLM, MoEConfig
    dims, moe = {
        "tiny": (dict(n_layer=4, n_head=8, d_model=64, d_ff=128,
                      vocab_size=512, max_seq=1024, q_lora_rank=48,
                      kv_lora_rank=128, qk_nope_head_dim=32,
                      qk_rope_head_dim=64, v_head_dim=16, init_std=0.1,
                      embed_init_std=1.0),
                 dict(num_experts=4, router_experts=8, zero_experts=4, k=3,
                      expert_d_ff=32, router_init_scale=2.0,
                      select_bias_init_std=0.5 / 12)),
        "omni-4l-ep32": (dict(n_layer=8, n_head=64, d_model=6144, d_ff=12288,
                              vocab_size=16384, max_seq=4608,
                              q_lora_rank=1536, kv_lora_rank=512,
                              qk_nope_head_dim=128, qk_rope_head_dim=64,
                              v_head_dim=128, init_std=0.02,
                              embed_init_std=1.0),
                         dict(num_experts=16, router_experts=512,
                              zero_experts=256, k=12, expert_d_ff=2048,
                              router_init_scale=3.0,
                              select_bias_init_std=0.5 / 768)),
    }[size]
    param_dtype = over.pop("param_dtype", jnp.float32)
    share = over.pop("share", 0)
    moe = {**moe, **over.pop("moe", {})}
    cfg = TransformerConfig(**{**dict(
        pos_embedding="rope", rope_theta=1e7, rope_interleaved=True,
        norm="rmsnorm", norm_eps=1e-5, activation="swiglu",
        tie_embeddings=False, attn_bias=False, mla_lora_scale=True,
        layer_kinds=("latent_attention",) * 2), **dims, **over})
    return MoECausalLM(cfg, MoEConfig(**{**dict(
        dispatch="nodrop", expert_activation="swiglu", scoring="softmax",
        select_bias=True, norm_topk_prob=False, routed_scaling_factor=6.0,
        shortcut=True, aux_loss_coef=0.0,
        expert_offset=share * moe["num_experts"]), **moe}),
        param_dtype=param_dtype)


def lfm2_moe(size: str = "24b-a2b-9l", **over):
    """LFM2-24B-A2B (``LiquidAI/LFM2-24B-A2B`` config.json, ``model_type``
    ``lfm2_moe``): 40 pre-RMSNorm layers (eps 1e-5, no bias anywhere) of d
    2,048 whose mixer is by ``layer_types`` a GATED SHORT CONVOLUTION (30
    layers: ``[B, C, x~] = x W_in``, a causal depthwise conv of 3 taps over
    ``B * x~``, ``(C * conv) W_out``; it keeps the conv's last 2 inputs a
    request and nothing else) or softmax GQA (10 layers: 32 query and 8
    key/value heads of 64, RMSNorm of each head's q and k before RoPE of
    theta 1e6 over the whole head); the first ``num_dense_layers`` 2 layers
    with a dense gated-SiLU MLP of 11,776, the other 38 with 64 gated-SiLU
    experts of 1,536, 4 a token: a float32 sigmoid router with a selection
    bias (``expert_bias``, for the choice alone), the four weights divided
    by their sum + 1e-6, times ``routed_scaling_factor`` 1; a final RMSNorm
    and a head tied to the embedding over 65,536 rows. 23.84 B parameters.
    ``24b-a2b-9l`` is ONE PIPELINE STAGE on one chip (perfbench's
    ``lfm2_24b_serve_rollout``): published layers 1-9, ONE leading dense
    conv layer (``lead_kinds``; leading dense layers count once) and two
    whole periods of (attention, conv, conv, conv) with every expert and the
    whole vocabulary, each layer whole on its chip: 5,177,950,976
    parameters, 10.36 GB in bf16. ``max_seq`` is what the deployment serves
    (the block tables' width). Its seeded init, chosen on the chip with
    ``benchmarks/lfm2_check_controls.py`` (PERF.md section 6, PR 52): THE
    MATRICES AT 0.4, THE EMBEDDING AT 5,400, the router at the library's 1
    (logits of deviation 1: sigmoid scores of 0.1-0.9, all 64 experts
    chosen in a step). Why numbers so far from a trained model's: NO seeded
    init lets the served check (tokens, 4 bf16 steps) both pass the sound
    program and see this model's layers. The head is TIED, so an embedding
    that leads the residual stream serves the input token back whatever
    nine layers do (1.0 or 0.08 over matrices at 0.02: no planted fault
    refused), and one that does not leaves the logits to a sum the bf16
    program cannot repeat: the 4th and 5th of 64 router logits lie 0.12
    deviations apart and bf16 rounds the stream by ~0.5% of one, at ANY
    router scale and ``expert_bias`` deviation (tried: 0.5, 1, 3; 0.02,
    0.1), so ~5% of (token, layer) pairs take another fourth expert, a
    QUARTER of an MoE layer's output under normalised sigmoid weights: at
    matrices 0.02 sound prompts read 7-34 of the check's 4 steps. A gated
    conv's output goes with the matrices' deviation to the 4th power and an
    expert's to the 3rd: at 0.4 the conv layers carry the stream (14,570 a
    value against an expert's 866) and a changed choice is 2% of a layer;
    what is left is bf16 rounding through seven cubic mixers, and where the
    layers decide every position (embedding 0.02) 3 sound prompts of 64
    still read over 4 (5.2 the worst). So the embedding sits at THE EDGE OF
    THE ECHO, as Granite's does (PR 43): at 5,400 the layers decide 3.9% of
    served positions (5.5% at 5,000, 0.8% at 5,800; all measured), a lost
    conv state shows on 3 and a skipped lead on 6 of 16 prompts, sound
    prompts on none of 32 (2.8 the worst): a check's 28 prompts refuse
    either fault with probability over 0.99 and a sound program with
    ~0.05. A router or conv taps in bf16 and a missing ``expert_bias``
    are of the size of the stream's own rounding and are seen by no init:
    the float32 logits tests hold those.
    ``tiny``: one leading dense conv layer and one period at toy widths
    (embedding 1.0 over matrices at 0.1, router logits of deviation 3:
    float32 tests, no flips)."""
    from deepspeed_tpu.models.moe_lm import MoECausalLM, MoEConfig
    dims, moe = {
        "tiny": (dict(n_layer=5, n_head=4, n_kv_head=2, head_size=16,
                      d_model=64, d_ff=32, lead_d_ff=96, vocab_size=512,
                      max_seq=1024, init_std=0.1, embed_init_std=1.0),
                 dict(num_experts=8, k=2, expert_d_ff=32,
                      router_init_scale=3.0)),
        "24b-a2b-9l": (dict(n_layer=9, n_head=32, n_kv_head=8, head_size=64,
                            d_model=2048, d_ff=1536, lead_d_ff=11776,
                            vocab_size=65536, max_seq=2560, init_std=0.4,
                            embed_init_std=5400.0),
                       dict(num_experts=64, k=4, expert_d_ff=1536)),
    }[size]
    param_dtype = over.pop("param_dtype", jnp.float32)
    moe = {**moe, **over.pop("moe", {})}
    cfg = TransformerConfig(**{**dict(
        pos_embedding="rope", rope_theta=1e6, norm="rmsnorm", norm_eps=1e-5,
        activation="swiglu", tie_embeddings=True, attn_bias=False,
        qk_norm="head", conv_kernel=3, lead_kinds=("short_conv",),
        layer_kinds=("attention",) + ("short_conv",) * 3), **dims, **over})
    return MoECausalLM(cfg, MoEConfig(**{**dict(
        dispatch="nodrop", expert_activation="swiglu", scoring="sigmoid",
        norm_topk_prob=True, norm_topk_eps=1e-6, routed_scaling_factor=1.0,
        aux_loss_coef=0.0), **moe}),
        param_dtype=param_dtype)


def trinity(size: str = "large-preview-5l-ep8", share: int = 0, **over):
    """Trinity-Large-Preview (``arcee-ai/Trinity-Large-Preview``
    config.json, ``model_type`` ``afmoe``, 400B-A13B): 60 SANDWICH-NORMED
    layers (RMSNorm, eps 1e-5, on each branch's way in and on its way out:
    ``h + norm_post(branch(norm_pre(h)))``, no bias anywhere) of d 3,072.
    Attention is GQA of 48 query and 8 key/value heads of 128 with a
    sigmoid output gate and an RMSNorm of each head's q and k; by
    ``layer_types`` three layers of four have a WINDOW of 4,096 and rope
    (theta 10,000, the half-split pairing, the whole head) and the fourth
    is FULL attention with NO positions. Layers 0-5 have a dense gated-SiLU
    MLP of 12,288; the other 54 have 256 gated-SiLU experts of 3,072, 4 a
    token by a float32 sigmoid score plus a selection bias (``expert_bias``,
    for the choice alone), the four scores divided by their sum + 1e-20,
    times ``route_scale`` 2.448, beside one shared expert of 3,072. The
    embedding is multiplied by sqrt(3,072) (``mup_enabled``); a final
    RMSNorm and an untied head over 200,192 rows. 398.6 B parameters.
    ``large-preview-5l-ep8`` is ONE CHIP OF THE EIGHT that share each layer
    (perfbench's ``trinitylarge_serve_shortlong``): one leading dense WINDOW
    layer (``lead_kinds``; leading dense layers count once) and ONE whole
    period of the pattern (window, window, window, full: published layers
    8-11), the router's 256 outputs with the 32 experts of ``share`` (0-7)
    held here, attention and the shared expert whole, the vocabulary this
    chip's eighth: 4,321,903,872 parameters, 8.64 GB in bf16. ``max_seq``
    is what the deployment serves (6,144 + 1,024; the block tables'
    width). Its seeded init: matrices at the library's 0.02 (output
    projections depth-scaled), every norm's scale 1, and the EMBEDDING at
    ``embed_init_std`` 0.16, 8.9 a value after the sqrt(d) multiplier. A
    sandwich norm hands every branch to the stream at a deviation of 1
    whatever its weights are, so the embedding's scale alone sets how much
    of the stream one branch is (a ninth here), and with it both what a
    served token can show of a fault and what a flipped boundary choice of
    the router costs (one held expert beside the shared one is half an
    MoE branch, and a flip feeds the next layers' routers). Chosen on the
    chip with ``benchmarks/trinity_check_controls.py`` (PERF.md section 6,
    PR 56): at 0.08 a sound prompt of 16 read 17 of the served check's 4
    bf16 steps; at 0.16, 0.3 and 0.5 none of 96 sound prompts each
    was refused (worst 1.68, 0.85, 1.17: a bf16 logit's own step), while
    the check's reach falls with the scale (a missing post-norm is refused
    on 16, 13 of 16 prompts at 0.16, 0.3; a missing shared expert on 16,
    8; a missing gate on 5, 2; float8 on 2, 0; a window left out on 1, 0):
    0.16 is the least scale whose sound readings leave room under the
    limit, and the check reaches twice as far under it as under 0.3; what
    it still does not see is held by the float32 logits tests and the
    tool's ``--logits``. What the check sees under it is in the
    configuration file (``assumed.seeded_init``).
    ``tiny``: the lead and TWO periods at toy widths, a window of 256 (two
    blocks of 128), 4 of 16 experts held, top-4."""
    from deepspeed_tpu.models.moe_lm import MoECausalLM, MoEConfig
    dims, moe = {
        "tiny": (dict(n_layer=9, n_head=4, n_kv_head=2, head_size=32,
                      d_model=64, d_ff=32, lead_d_ff=96, vocab_size=512,
                      max_seq=1024, attn_window=256, embed_init_std=0.16),
                 dict(num_experts=4, router_experts=16, k=4, expert_d_ff=32,
                      shared_expert_d_ff=32)),
        "large-preview-5l-ep8": (
            dict(n_layer=5, n_head=48, n_kv_head=8, head_size=128,
                 d_model=3072, d_ff=3072, lead_d_ff=12288, vocab_size=25024,
                 max_seq=7168, attn_window=4096, embed_init_std=0.16),
            dict(num_experts=32, router_experts=256, k=4, expert_d_ff=3072,
                 shared_expert_d_ff=3072)),
    }[size]
    param_dtype = over.pop("param_dtype", jnp.float32)
    moe = {**moe, **over.pop("moe", {})}
    dims = {**dims, **over}
    cfg = TransformerConfig(**{**dict(
        pos_embedding="rope", rope_theta=10000.0, norm="rmsnorm",
        norm_eps=1e-5, norm_position="sandwich", activation="swiglu",
        tie_embeddings=False, attn_bias=False, qk_norm="head",
        attn_out_gate=True, lead_kinds=("window_attention",),
        layer_kinds=("window_attention",) * 3 + ("attention",),
        rope_kinds=("window_attention",),
        embedding_multiplier=math.sqrt(dims["d_model"])), **dims})
    return MoECausalLM(cfg, MoEConfig(**{**dict(
        dispatch="nodrop", expert_activation="swiglu", scoring="sigmoid",
        norm_topk_prob=True, norm_topk_eps=1e-20,
        routed_scaling_factor=2.448, aux_loss_coef=0.0,
        expert_offset=share * moe["num_experts"]), **moe}),
        param_dtype=param_dtype)


def deepseek_v3(size: str = "kanana-2-30b-8l", **over):
    """The ``deepseek_v3`` stack as Kanana-2-30B-A3B publishes it
    (``kakaocorp/kanana-2-30b-a3b-instruct-2601`` config.json): 48
    pre-RMSNorm layers (eps 1e-6, no bias anywhere) of d 2,048, every mixer
    multi-head LATENT attention with a DIRECT query (``q_lora_rank`` null:
    one matrix to 32 heads of 128 + 64, no bottleneck and no norm), keys
    and values from one RMSNormed latent of 512 a token beside a roped key
    part of 64 shared by the heads (values 128; rope theta 1e6 on
    interleaved pairs, scale 1/sqrt(192)); layer 0 with a dense gated-SiLU
    MLP of 6,144 (``first_k_dense_replace`` 1), the other 47 with 128
    gated-SiLU experts of 768, 6 a token by a float32 sigmoid score plus a
    selection bias (for the choice alone; ``n_group`` = ``topk_group`` = 1:
    no group limit), the six scores divided by their sum + 1e-20 and times
    2.448, beside two shared experts (one gated MLP of 1,536); a final
    RMSNorm and an untied head over 128,256 rows. 30.67 B parameters.
    ``kanana-2-30b-8l`` is STAGE 1 OF A SIX-STAGE PIPELINE (perfbench's
    ``kanana2_30b_serve_longdoc``): the leading dense layer (a LATENT lead:
    ``lead_kinds``, pool ``c``'s first entry) and 7 MoE layers with ALL 128
    experts and the whole vocabulary. ``max_seq`` is what the deployment
    serves (20,480 + 768; the block tables' width, 166 blocks of 128). Its
    seeded init: matrices at ``init_std`` 0.045 (output projections
    depth-scaled), the embedding at ``embed_init_std`` 24.0, the router's
    scale 1.0; the selection bias ``b_select`` is zero here and drawn N(0,
    0.02) by the harness, as Solar's and Trinity's. The init was chosen on
    the chip (``benchmarks/kanana_check_controls.py``; the sweep's readings
    are in PERF.md section 6, PR 61): matrices sharp enough that attention
    over 12 k keys is not flat, the stream held by the embedding firmly
    enough that ONE expert taken for another by a router under bf16
    activations (a quarter of the positions) does not cost a sound prompt
    the served check.
    ``tiny``: the lead and TWO MoE layers at toy widths, 8 experts top-3,
    keys 32 + 64 and values 32 over a latent of 128."""
    from deepspeed_tpu.models.moe_lm import MoECausalLM, MoEConfig
    dims, moe = {
        "tiny": (dict(n_layer=3, n_head=8, d_model=64, d_ff=32, lead_d_ff=96,
                      vocab_size=512, max_seq=768, kv_lora_rank=128,
                      qk_nope_head_dim=32, qk_rope_head_dim=64,
                      v_head_dim=32, init_std=0.1, embed_init_std=1.0),
                 dict(num_experts=8, k=3, expert_d_ff=32,
                      shared_expert_d_ff=64)),
        "kanana-2-30b-8l": (
            dict(n_layer=8, n_head=32, d_model=2048, d_ff=768,
                 lead_d_ff=6144, vocab_size=128256, max_seq=21248,
                 kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
                 v_head_dim=128, init_std=0.045, embed_init_std=24.0),
            dict(num_experts=128, k=6, expert_d_ff=768,
                 shared_expert_d_ff=1536)),
    }[size]
    param_dtype = over.pop("param_dtype", jnp.float32)
    moe = {**moe, **over.pop("moe", {})}
    cfg = TransformerConfig(**{**dict(
        pos_embedding="rope", rope_theta=1e6, rope_interleaved=True,
        norm="rmsnorm", norm_eps=1e-6, activation="swiglu",
        tie_embeddings=False, attn_bias=False, q_lora_rank=0,
        lead_kinds=("latent_attention",),
        layer_kinds=("latent_attention",)), **dims, **over})
    return MoECausalLM(cfg, MoEConfig(**{**dict(
        dispatch="nodrop", expert_activation="swiglu", scoring="sigmoid",
        norm_topk_prob=True, norm_topk_eps=1e-20,
        routed_scaling_factor=2.448, aux_loss_coef=0.0), **moe}),
        param_dtype=param_dtype)


MODEL_PRESETS: Dict[str, Callable] = {
    "gpt2": gpt2,
    "llama": llama,
    "bloom": bloom,
    "opt": opt,
    "gpt_neox": gpt_neox,
    "olmoe": olmoe,
    "solar_open2": solar_open2,
    "sdar": sdar,
    "smallthinker": smallthinker,
    "granite_hybrid": granite_hybrid,
    "longcat_flash": longcat_flash,
    "lfm2_moe": lfm2_moe,
    "trinity": trinity,
    "deepseek_v3": deepseek_v3,
}


def get_model(family: str, size: str = None, **over) -> CausalLM:
    fn = MODEL_PRESETS[family]
    return fn(size, **over) if size else fn(**over)
