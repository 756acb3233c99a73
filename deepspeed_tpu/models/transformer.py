"""TPU-first decoder/encoder transformer backbone shared by the model zoo.

This is the training-side analogue of the reference's fused transformer
kernels (``csrc/transformer/``, ``deepspeed/ops/transformer/transformer.py``)
re-designed for XLA rather than translated: one stacked-parameter layer block
executed with ``lax.scan`` (single compile for all layers, the layout
ZeRO-3/FSDP wants: gathering one layer's params per scan step bounds live
memory exactly like the reference's fetch/release coordinator), optional
``jax.checkpoint`` rematerialisation (activation checkpointing), einsum-form
attention XLA fuses onto the MXU, and TP/SP sharding expressed as
PartitionSpecs.

Model families configure the block: GPT-2 (learned pos + LN + gelu),
Llama (RoPE + RMSNorm + SwiGLU), BLOOM (alibi), OPT, GPT-NeoX, BERT
(bidirectional). See the thin wrappers in ``deepspeed_tpu/models/``.
"""

from __future__ import annotations

import collections
import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.ops import dispatch


ATTENTION, LINEAR_ATTENTION = "attention", "linear_attention"
#: softmax attention over the last ``attn_window`` positions only
WINDOW_ATTENTION = "window_attention"
#: the Mamba-2 mixer: a state-space recurrence in its SSD form
MAMBA2 = "mamba2"
#: softmax attention over a LATENT cache (multi-head latent attention): a
#: token keeps one row of ``kv_lora_rank + qk_rope_head_dim`` values a layer,
#: no k/v pair and no head axis; ``models/latent_attention.py``
LATENT_ATTENTION = "latent_attention"
#: the gated short convolution (LFM2): y = (C * conv(B * x~)) W_out, a
#: causal depthwise conv of ``conv_kernel`` taps; it keeps the conv's last
#: inputs a request and NO recurrent state
SHORT_CONV = "short_conv"
#: the kinds whose layers keep a conv state, and all but ``SHORT_CONV`` a
#: recurrent state, in a slot a request; what each is, is its record in
#: ``models/state_mixers.py``
STATE_KINDS = (LINEAR_ATTENTION, MAMBA2, SHORT_CONV)


def _mixer(kind: str):
    """The record of a recurrent ``kind``. Imported here and not above:
    ``state_mixers`` imports this module."""
    from deepspeed_tpu.models.state_mixers import STATE_MIXERS
    return STATE_MIXERS[kind]


#: the unmasking rules of a block-diffusion model's generation
UNMASK_RULES = ("sequential", "low_confidence_static", "low_confidence_dynamic")


@dataclasses.dataclass(frozen=True)
class BlockGeneration:
    """How a model generates where it is not one token a step: by diffusion
    over blocks (SDAR's ``block_diffusion_generate``). The sequence is cut
    into blocks of ``block`` positions by absolute position; attention is
    bidirectional inside a block and causal between blocks; a block is
    generated by denoise passes over its ``block`` positions (undecided
    ones fed as ``mask_id``), each deciding some of them AT THEIR OWN
    positions by ``rule``, then committed by one more pass that keeps its
    KV. Pass ``i`` of a block decides ``transfers(i)`` of its masked
    positions (fewer if fewer are left): ``sequential`` the leftmost,
    ``low_confidence_static`` those of largest confidence (the largest
    softmax probability), ``low_confidence_dynamic`` every one whose
    confidence passes ``threshold``, or the static rule's if fewer pass.
    The scheduler and the serving session read this record as they read
    ``cache_spec``: no serving option names a way to generate."""
    block: int = 4
    steps: int = 4
    rule: str = "low_confidence_dynamic"
    threshold: float = 0.9
    mask_id: int = 0

    def __post_init__(self):
        if self.rule not in UNMASK_RULES:
            raise ValueError(f"BlockGeneration.rule={self.rule!r} (expected "
                             + "|".join(UNMASK_RULES) + ")")
        if not 1 <= self.steps <= self.block:
            raise ValueError("BlockGeneration needs 1 <= steps <= block")
        if 8 % self.block:
            raise ValueError("BlockGeneration.block is 1, 2, 4 or 8 (whole "
                             "groups inside the attention kernels' 8-row tiles)")

    def transfers(self, i: int) -> int:
        """Positions pass ``i`` (from 0) of a block decides at least:
        ``block // steps``, the remainder on the first passes."""
        return self.block // self.steps + (i < self.block % self.steps)

    @property
    def data_dependent(self) -> bool:
        """Whether the count a pass decides is data (the host must read a
        pass's result before it can plan the next)."""
        return self.rule == "low_confidence_dynamic"


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 50257
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    d_ff: Optional[int] = None           # default 4*d_model (or 8/3 for swiglu)
    max_seq: int = 1024
    n_kv_head: Optional[int] = None      # GQA; default n_head
    # block style
    pos_embedding: str = "learned"       # learned | rope | alibi | none
    norm: str = "layernorm"              # layernorm | rmsnorm
    activation: str = "gelu"             # gelu (tanh) | gelu_exact | quick_gelu | swiglu | relu
    parallel_residual: bool = False      # gpt-neox style
    # pre (GPT) | post (BERT add&norm) | sandwich: each branch normed on the
    # way in AND on the way out, h + norm_post(branch(norm_pre(h))), a layer's
    # two more norms ``ln_attn_post`` / ``ln_mlp_post`` (the paged serving
    # path alone builds it)
    norm_position: str = "pre"
    causal: bool = True
    tie_embeddings: bool = True
    embed_layernorm: bool = False        # BLOOM word_embeddings_layernorm
    attn_bias: bool = False              # qkv/out biases (gpt2/opt/bloom/neox)
    # RMSNorm of the query and key PROJECTIONS before rope. True: each over
    # its whole width (all heads together, one learned scale of H*Hd /
    # KV*Hd: OLMoE, OLMo-2). "head": each head over its own head_dim, one
    # learned scale of Hd shared by the heads (Qwen3, SDAR)
    qk_norm: Any = False
    # a head's width where it is not d_model // n_head (64 heads of 128
    # under d_model 4,096): ``head_dim`` reads it
    head_size: Optional[int] = None
    # sigmoid output gate on attention: y = (a * sigmoid(x Wg)) Wo, Wg
    # [d_model, n_head * head_dim], no bias
    attn_out_gate: bool = False
    # ONE period of the stack's layer pattern, the kinds of its mixers in
    # order ("attention": softmax attention over the KV cache;
    # "linear_attention": the gated delta rule (KDA) with a recurrent state,
    # the lin_* sizes below; "mamba2": the Mamba-2 state-space recurrence,
    # the ssm_* sizes; "window_attention": softmax attention whose query
    # at i sees keys i - attn_window < j <= i, the attention layer's
    # parameters); the stack is n_layer / len(layer_kinds) periods.
    # "latent_attention": multi-head latent attention, the mla sizes below,
    # its cache one latent row a token).
    # None: every layer is "attention". A kind says what cache it keeps
    # (``cache_spec``): KV blocks, a state slot a request (both recurrent
    # kinds, each with its own state shapes: ``state_shapes``), a ring
    # of KV blocks a request that holds the window and no more, or blocks
    # of latent rows.
    layer_kinds: Optional[Tuple[str, ...]] = None
    # LEADING layers, ahead of the first period: the kinds of their mixers
    # in order, each with a DENSE MLP of ``lead_d_ff`` (None: ``ff_dim``)
    # whatever MLP the periods' layers have (an MoE model's leading dense
    # layers). ``n_layer`` counts them; their parameters are the group
    # ``lead`` of the tree and their cache entries the first of each pool
    lead_kinds: Tuple[str, ...] = ()
    lead_d_ff: Optional[int] = None
    # keys a window_attention layer's query sees, its own among them
    attn_window: int = 0
    # the kinds whose q and k take ``pos_embedding`` where not every
    # attention kind does (rope on the window layers, none on the full
    # ones); None: all of them
    rope_kinds: Optional[Tuple[str, ...]] = None
    # the linear-attention (KDA) mixer: heads and their key = value width
    # (its two factored gate projections have rank lin_head_dim)
    lin_heads: int = 0
    lin_head_dim: int = 0
    # the Mamba-2 mixer: ``ssm_heads`` heads of ``ssm_head_dim`` channels
    # (their product is the mixer's inner width), a state of ``ssm_state``
    # a channel, B and C of ONE group shared by all heads, a causal
    # depthwise conv of ``ssm_conv_kernel`` taps, and the tokens a chunk of
    # the prompt's chunked (SSD) form
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_conv_kernel: int = 4
    ssm_chunk: int = 256
    # the gated short conv: taps of its causal depthwise conv over d_model
    # channels (LFM2's ``conv_L_cache``); a request keeps the last
    # ``conv_kernel - 1`` of the conv's inputs
    conv_kernel: int = 3
    # the latent (MLA) mixer: queries through a bottleneck of ``q_lora_rank``
    # (RMSNorm in it; 0: a DIRECT query, one matrix), ``n_head`` heads of
    # ``qk_nope_head_dim + qk_rope_head_dim``; keys, values from ONE latent of
    # ``kv_lora_rank`` (RMSNorm) a token, expanded a head to
    # ``qk_nope_head_dim + v_head_dim``, beside one roped key part of
    # ``qk_rope_head_dim`` shared by all heads. ``mla_lora_scale``: the
    # query times sqrt(d_model / q_lora_rank) and the normed latent times
    # sqrt(d_model / kv_lora_rank) (LongCat's ``mla_scale_q_lora`` /
    # ``mla_scale_kv_lora``)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    mla_lora_scale: bool = False
    # Granite's scalars, each 1.0 where a model has none: the token
    # embedding is multiplied by ``embedding_multiplier``, every branch by
    # ``residual_multiplier`` before it joins the residual stream, and the
    # logits are divided by ``logits_scaling`` (its ``attention_multiplier``
    # is ``attn_scale``). Applied on the paged serving path; the training
    # forward and the dense-workspace cache refuse a model that sets one.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # numerics
    rope_theta: float = 10000.0
    rope_dim: int = 0                    # 0 = full head dim; else partial
    rope_interleaved: bool = False       # GPT-J pairing vs NeoX half-split
    lm_head_bias: bool = False           # GPT-J's lm_head carries a bias
    norm_eps: float = 1e-5
    # hidden dropout (embedding sum + both residual-branch outputs, GPT-2
    # placement), applied only when the loss path is given an rng — eval and
    # inference paths pass none and stay deterministic. Attention-PROBS
    # dropout is deliberately not implemented: the flash kernel family
    # cannot apply it and a silent einsum-only fallback would change
    # numerics between paths (modern recipes train attention undropped).
    dropout: float = 0.0
    # memory: activation checkpointing per layer. False/"none" = save all
    # activations; True/"full" = save only layer inputs (reference
    # CheckpointFunction semantics); "dots" = save matmul outputs + the flash
    # kernel's (o, lse), recompute the cheap elementwise parts (best MFU);
    # "offload_dots" = save matmul outputs to pinned host memory.
    remat: Any = True
    scan_layers: bool = True
    # sequence/context parallelism over the "sp" mesh axis
    sequence_parallel: str = "none"      # none | ring | ulysses
    # attention kernel: auto = Pallas flash on TPU, XLA einsum elsewhere
    attention_backend: str = "auto"      # auto | flash | xla
    # flash kernel block sizes on the direct / batch-head-sharded kernel
    # paths; None = the kernel's measured defaults (whole-sequence blocks at
    # S <= 1024, 512x512 above). The sp (ring/ulysses) paths keep their own
    # shard-local block tuning and warn if these are set.
    attn_block_q: Optional[int] = None
    attn_block_k: Optional[int] = None
    # block-sparse attention: a SparsityConfig (ops/sparse_attention) whose
    # layout replaces dense attention in every layer — the model-level
    # integration the reference does by module surgery
    # (ops/sparse_attention/sparse_attention_utils.py
    # replace_model_self_attention_with_sparse_self_attention). TPU runs the
    # block-sparse flash kernel; elsewhere the exact dense token-bias form.
    sparse_attention: Optional[Any] = None
    # cross-entropy in sequence chunks of this many tokens: never
    # materialises the full [B, S, vocab] logits (0 = unchunked). Only
    # consulted when the fused CE kernel below is off / unavailable.
    loss_chunk: int = 0
    # vocab-head loss kernel: "auto" = the fused logits-free Pallas
    # cross-entropy kernel (ops/pallas/fused_cross_entropy) on TPU, the XLA
    # loss_chunk streaming path elsewhere; "on" forces the kernel (interpret
    # mode off-TPU — the CPU test tier); "off" keeps the XLA path
    fused_cross_entropy: str = "auto"
    # attention logit scale; None = head_dim**-0.5. GPT-Neo-family models
    # use UNSCALED attention (1.0)
    attn_scale: Optional[float] = None
    # QAT activation fake-quant (dynamic range, straight-through bwd) applied
    # to the attention and MLP inputs; 0 = off. Wired automatically by
    # compression.init_compression from the activation_quantization config
    # section (reference compression/basic_layer.py:118-860 QuantAct)
    act_quant_bits: int = 0
    act_quant_sym: bool = True
    # Megatron-style MANUAL tensor parallelism: the mesh axis name over which
    # attention/mlp weights arrive pre-sliced (column-parallel qkv/up,
    # row-parallel out/down) and the blocks insert the f/g collectives
    # explicitly (_mtp_in/_mtp_out). Set only by the pipeline engine's
    # manual-tp stage factory (models/pipeline.py manual_tp_stage_fn) for
    # execution inside a fully-manual (pp × dp × tp) stage shard_map, where
    # the SPMD partitioner — which otherwise inserts these collectives from
    # the sharding specs — is not available. Reference capability: fused
    # kernels + TP run unchanged under PP (csrc/transformer/inference/csrc/
    # pt_binding.cpp:1668-1793 via deepspeed/runtime/pipe/engine.py:596).
    manual_tp: Optional[str] = None
    # init
    init_std: float = 0.02
    # std of the token embedding's draw where it is not init_std: a cut of a
    # deep stack takes the residual stream its layers see in the stack
    embed_init_std: Optional[float] = None
    # how the model generates: None = one token a step from the last
    # position's logits (every autoregressive model); a BlockGeneration
    # record = diffusion over blocks, which also makes every attention mask
    # of the model block-causal (``causal_block``)
    generation: Optional[BlockGeneration] = None

    @property
    def head_dim(self) -> int:
        return self.head_size or self.d_model // self.n_head

    @property
    def causal_block(self) -> int:
        """The step of the causal mask's staircase: position i sees j iff
        j // causal_block <= i // causal_block (1: the plain triangle)."""
        return self.generation.block if self.generation is not None else 1

    @property
    def period(self) -> Tuple[str, ...]:
        """The kinds of one period of the layer pattern."""
        return tuple(self.layer_kinds) if self.layer_kinds else (ATTENTION,)

    @property
    def n_periods(self) -> int:
        return (self.n_layer - len(self.lead_kinds)) // len(self.period)

    def layers_of(self, kind: str) -> int:
        """The stack's layers of ``kind``, the leading ones among them."""
        return self.n_periods * self.period.count(kind) \
            + tuple(self.lead_kinds).count(kind)

    @property
    def cache_spec(self) -> Dict[str, int]:
        """What the stack keeps between steps, by cache: ``kv`` the layers
        that hold KV blocks, ``state`` those that hold a conv state, and
        all but the short conv's a recurrent state, in a slot a request
        (``STATE_KINDS``), ``window`` those that hold
        the last ``attn_window`` positions' KV in a ring of at most
        ``ring_blocks`` blocks of their own pool, handed out as the request
        grows and named by a table from the host (logical block ``j`` at
        entry ``j % ring_blocks``), ``latent`` those that hold blocks of
        latent rows
        (``latent_row`` values a token, addressed by the same block tables
        as KV blocks). What cannot hold with a state, a ring or a latent
        row yet (a prefix hit, a verify window's rewind, a block moved to
        the host, a head-sharded pool) is refused from here, not by an
        option."""
        return {"kv": self.layers_of(ATTENTION),
                "state": sum(self.layers_of(k) for k in STATE_KINDS),
                "window": self.layers_of(WINDOW_ATTENTION),
                "latent": self.layers_of(LATENT_ATTENTION)}

    @property
    def latent_row(self) -> int:
        """Values a token keeps in a latent layer's cache: the normed
        latent, then the roped key part all heads share."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_pool_row(self) -> int:
        """Lanes a token's row takes in the latent pool: ``latent_row``
        rounded up to whole tiles of 128 lanes, the rest zeros. The chip's
        memory is tiled 128 lanes wide, so a row of 576 values takes 640
        whether the array says so or not, and a kernel's copies are whole
        tiles."""
        return -(-self.latent_row // 128) * 128

    def state_shapes(self, kind: str):
        """What one layer of a recurrent ``kind`` keeps a request: the
        shape of its float32 state (None: the kind keeps none) and of its
        conv state (the last K - 1 inputs of its causal conv)."""
        return _mixer(kind).shapes(self)

    def ring_blocks(self, block_size: int) -> int:
        """Blocks of a request's ring in a window layer: the window's, and
        one more for the block being written."""
        return -(-self.attn_window // block_size) + 1

    def takes_rope(self, kind: str) -> bool:
        return self.pos_embedding == "rope" and (
            self.rope_kinds is None or kind in self.rope_kinds)

    @property
    def kv_heads(self) -> int:
        return self.n_kv_head or self.n_head

    @property
    def ff_dim(self) -> int:
        if self.d_ff is not None:
            return self.d_ff
        if self.activation == "swiglu":
            # keep matmul dims MXU-friendly (multiple of 128)
            d = int(8 * self.d_model / 3)
            return (d + 127) // 128 * 128
        return 4 * self.d_model


# --------------------------------------------------------------------- #
# parameter init

def _check_pattern(cfg: TransformerConfig):
    period, lead = cfg.period, tuple(cfg.lead_kinds)
    kinds = lead + period
    known = (ATTENTION, WINDOW_ATTENTION, LATENT_ATTENTION, *STATE_KINDS)
    bad = [k for k in kinds if k not in known]
    if bad:
        raise ValueError(f"layer_kinds {kinds}: unknown kind {bad[0]!r} "
                         f"(expected {'|'.join(known)})")
    if WINDOW_ATTENTION in kinds and cfg.attn_window < 1:
        raise ValueError("a window_attention layer needs attn_window")
    if cfg.norm_position not in ("pre", "post", "sandwich"):
        raise ValueError(f"norm_position {cfg.norm_position!r} (expected "
                         "pre|post|sandwich)")
    if (cfg.n_layer - len(lead)) % len(period) or cfg.n_layer <= len(lead):
        raise ValueError(f"n_layer {cfg.n_layer} is not {len(lead)} leading "
                         f"layers and whole periods of the layer pattern "
                         f"{period}")
    if LINEAR_ATTENTION in lead:
        raise NotImplementedError(
            "a linear_attention layer ahead of the periods is not built: its "
            "decode form finds its slots in a pool of n_periods layers")
    for kind in STATE_KINDS:
        if kind in kinds:
            _mixer(kind).check(cfg)
    if LATENT_ATTENTION in kinds:
        from deepspeed_tpu.models import latent_attention
        latent_attention.check(cfg)


def _layer_group(cfg: TransformerConfig, kind: str, n: int, ks, dtype,
                 d_ff: Optional[int] = None):
    """The stacked parameters of ``n`` like layers of ``kind`` (leading dim
    ``n``, what ``lax.scan`` runs one compiled block over) from the keys
    ``ks``: the mixer under ``attn`` or its record's key, its norm, the
    dense MLP (of width ``d_ff``; None: ``cfg.ff_dim``) and its norm (a
    window layer's are an attention layer's)."""
    std = cfg.init_std
    D, F = cfg.d_model, d_ff or cfg.ff_dim
    H, KV, Hd = cfg.n_head, cfg.kv_heads, cfg.head_dim
    # attention out & mlp down get depth-scaled init (gpt-2 style)
    out_std = std / math.sqrt(2 * cfg.n_layer)

    def norm_params():
        scale = jnp.ones((n, D), dtype)
        if cfg.norm == "layernorm":
            return {"scale": scale, "bias": jnp.zeros((n, D), dtype)}
        return {"scale": scale}

    def dense(key, shape, scale=std):
        return (jax.random.normal(key, shape) * scale).astype(dtype)

    group = {"ln_attn": norm_params()}
    if kind in STATE_KINDS:
        m = _mixer(kind)
        group[m.key] = m.init(cfg, n, ks[8], dtype, out_std)
    elif kind == LATENT_ATTENTION:
        from deepspeed_tpu.models import latent_attention
        group["attn"] = latent_attention.init(cfg, n, ks[8], dtype, out_std)
    else:
        group["attn"] = {
            "wq": dense(ks[0], (n, D, H * Hd)),
            "wk": dense(ks[1], (n, D, KV * Hd)),
            "wv": dense(ks[2], (n, D, KV * Hd)),
            "wo": dense(ks[3], (n, H * Hd, D), out_std),
            **({"bq": jnp.zeros((n, H * Hd), dtype),
                "bk": jnp.zeros((n, KV * Hd), dtype),
                "bv": jnp.zeros((n, KV * Hd), dtype),
                "bo": jnp.zeros((n, D), dtype)} if cfg.attn_bias else {}),
            **({"q_norm": {"scale": jnp.ones((n, Hd), dtype)},
                "k_norm": {"scale": jnp.ones((n, Hd), dtype)}}
               if cfg.qk_norm == "head" else
               {"q_norm": {"scale": jnp.ones((n, H * Hd), dtype)},
                "k_norm": {"scale": jnp.ones((n, KV * Hd), dtype)}}
               if cfg.qk_norm else {}),
            **({"wg": dense(ks[7], (n, D, H * Hd))}
               if cfg.attn_out_gate else {}),
        }
    group["ln_mlp"] = norm_params()
    if cfg.norm_position == "sandwich":
        group["ln_attn_post"] = norm_params()
        group["ln_mlp_post"] = norm_params()
    group["mlp"] = ({
        "w_gate": dense(ks[4], (n, D, F)),
        "w_up": dense(ks[5], (n, D, F)),
        "w_down": dense(ks[6], (n, F, D), out_std),
    } if cfg.activation == "swiglu" else {
        "w_up": dense(ks[5], (n, D, F)),
        "b_up": jnp.zeros((n, F), dtype),
        "w_down": dense(ks[6], (n, F, D), out_std),
        "b_down": jnp.zeros((n, D), dtype),
    })
    return group


def mixer_params(cfg: TransformerConfig) -> int:
    """The parameters of the stack's mixers as ``_layer_group`` makes them
    (a recurrent layer's: the leaves its kind's ``init`` would make)."""
    H, KV, Hd, D = cfg.n_head, cfg.kv_heads, cfg.head_dim, cfg.d_model
    attn = (D * Hd * (H + 2 * KV) + H * Hd * D
            + (Hd * (H + 2 * KV) + D if cfg.attn_bias else 0)
            + (Hd * (2 if cfg.qk_norm == "head" else H + KV) if cfg.qk_norm else 0)
            + (D * H * Hd if cfg.attn_out_gate else 0))

    def layer(kind):
        if kind == LATENT_ATTENTION:
            from deepspeed_tpu.models import latent_attention
            init = latent_attention.init
        elif kind in STATE_KINDS:
            init = _mixer(kind).init
        else:
            return attn
        return sum(a.size for a in jax.tree.leaves(jax.eval_shape(
            lambda: init(cfg, 1, jax.random.key(0), jnp.float32, 1.0))))

    return cfg.n_periods * sum(layer(k) for k in cfg.period) \
        + sum(layer(k) for k in cfg.lead_kinds)


def dense_mlp_params(cfg: TransformerConfig, d_ff: Optional[int] = None) -> int:
    """The parameters of one layer's dense MLP as ``_layer_group`` makes it,
    ``d_ff`` wide (None: ``cfg.ff_dim``)."""
    D, F = cfg.d_model, d_ff or cfg.ff_dim
    return 3 * D * F if cfg.activation == "swiglu" else 2 * D * F + F + D


def init_params(cfg: TransformerConfig, rng, dtype=jnp.float32) -> Dict[str, Any]:
    """Stacked-layer parameter pytree. Layer weights carry a leading
    ``n_layer`` dim so ``lax.scan`` runs one compiled block for all layers.
    Under a layer pattern (``cfg.layer_kinds``) ``layers`` is a tuple, one
    group a position of the period, each stacked over the periods. Leading
    layers (``cfg.lead_kinds``) are the tuple ``lead``, one group of ONE
    layer each, their MLPs ``cfg.lead_d_ff`` wide; a stack without has no
    such key."""
    _check_pattern(cfg)
    k_emb, k_pos, k_layers, k_head = jax.random.split(rng, 4)
    std = cfg.init_std
    D = cfg.d_model

    def dense(key, shape, scale=std):
        return (jax.random.normal(key, shape) * scale).astype(dtype)

    if cfg.layer_kinds is None:
        layers = _layer_group(cfg, ATTENTION, cfg.n_periods,
                              jax.random.split(k_layers, 8), dtype)
    else:
        layers = tuple(
            _layer_group(cfg, kind, cfg.n_periods,
                         jax.random.split(jax.random.fold_in(k_layers, j), 9),
                         dtype)
            for j, kind in enumerate(cfg.period))
    params: Dict[str, Any] = {
        "embed": {"tokens": dense(k_emb, (cfg.vocab_size, D),
                                  cfg.embed_init_std or std)},
        "layers": layers,
        "ln_f": ({"scale": jnp.ones((D,), dtype), "bias": jnp.zeros((D,), dtype)}
                 if cfg.norm == "layernorm" else {"scale": jnp.ones((D,), dtype)}),
    }
    if cfg.lead_kinds:
        params["lead"] = tuple(
            _layer_group(cfg, kind, 1, jax.random.split(
                jax.random.fold_in(k_layers, 1000 + j), 9), dtype,
                d_ff=cfg.lead_d_ff)
            for j, kind in enumerate(cfg.lead_kinds))
    if cfg.pos_embedding == "learned":
        params["embed"]["positions"] = dense(k_pos, (cfg.max_seq, D))
    if cfg.embed_layernorm:
        params["embed"]["ln"] = ({"scale": jnp.ones((D,), dtype), "bias": jnp.zeros((D,), dtype)}
                                 if cfg.norm == "layernorm" else {"scale": jnp.ones((D,), dtype)})
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(k_head, (D, cfg.vocab_size))
        if cfg.lm_head_bias:
            params["lm_head_bias"] = jnp.zeros((cfg.vocab_size,), dtype)
    return params


def replicated_specs(init) -> Dict[str, Any]:
    """All-None PartitionSpecs in the shape of ``init()``'s tree: what a
    stack with no tensor-parallel form gives ``tp_specs``."""
    return jax.tree.map(lambda a: P(*([None] * a.ndim)), jax.eval_shape(init))


def tp_specs(cfg: TransformerConfig) -> Dict[str, Any]:
    """Tensor-parallel PartitionSpecs: column-shard qkv/up, row-shard out/down
    (Megatron layout over the ``tp`` mesh axis); vocab-shard embeddings.
    ZeRO sharding composes on the remaining free dims. A stack with a layer
    pattern or leading layers is replicated: no tensor-parallel form of its
    mixers is built."""
    if cfg.layer_kinds is not None or cfg.lead_kinds:
        return replicated_specs(lambda: init_params(cfg, jax.random.key(0)))
    ln = {"scale": P(None, None), "bias": P(None, None)} if cfg.norm == "layernorm" else {"scale": P(None, None)}
    specs = {
        "embed": {"tokens": P("tp", None)},
        "layers": {
            "ln_attn": ln,
            "attn": {
                "wq": P(None, None, "tp"),
                "wk": P(None, None, "tp"),
                "wv": P(None, None, "tp"),
                "wo": P(None, "tp", None),
                **({"bq": P(None, "tp"), "bk": P(None, "tp"),
                    "bv": P(None, "tp"), "bo": P(None, None)} if cfg.attn_bias else {}),
                **({"q_norm": {"scale": P(None, None)},
                    "k_norm": {"scale": P(None, None)}} if cfg.qk_norm else {}),
                **({"wg": P(None, None, "tp")} if cfg.attn_out_gate else {}),
            },
            "ln_mlp": ln,
            "mlp": ({
                "w_gate": P(None, None, "tp"),
                "w_up": P(None, None, "tp"),
                "w_down": P(None, "tp", None),
            } if cfg.activation == "swiglu" else {
                "w_up": P(None, None, "tp"),
                "b_up": P(None, "tp"),
                "w_down": P(None, "tp", None),
                "b_down": P(None, None),
            }),
        },
        "ln_f": {"scale": P(None), "bias": P(None)} if cfg.norm == "layernorm" else {"scale": P(None)},
    }
    if cfg.pos_embedding == "learned":
        specs["embed"]["positions"] = P(None, None)
    if cfg.embed_layernorm:
        specs["embed"]["ln"] = ({"scale": P(None), "bias": P(None)}
                                if cfg.norm == "layernorm" else {"scale": P(None)})
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, "tp")
        if cfg.lm_head_bias:
            specs["lm_head_bias"] = P("tp")
    return specs


# --------------------------------------------------------------------- #
# forward


def _w(entry, like):
    """Weight access: transparently dequantises int8 ``Quantized8`` leaves
    (weight-only inference quantisation) to ``like``'s dtype."""
    from deepspeed_tpu.ops.quant import maybe_dequant
    return maybe_dequant(entry, like.dtype)


def _norm(cfg: TransformerConfig, x, p):
    x32 = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        out = x32 * jax.lax.rsqrt(var + cfg.norm_eps) * p["scale"].astype(jnp.float32)
    else:
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        out = (x32 - mean) * jax.lax.rsqrt(var + cfg.norm_eps)
        out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return out.astype(x.dtype)


def _qk_norm(cfg: TransformerConfig, q, k, lp):
    """``cfg.qk_norm``: RMSNorm of the flat query and key projections
    [..., H*Hd] / [..., KV*Hd], over their whole width (True) or head by
    head over ``head_dim`` ("head"). The ONE place every attention path
    (``attention`` and ``_qkv_project``: cached, paged prefill, chunk,
    verify, decode, block) takes it from."""
    if not cfg.qk_norm:
        return q, k
    if cfg.qk_norm not in (True, "head"):
        raise ValueError(f"qk_norm={cfg.qk_norm!r} (expected True|'head')")
    if cfg.manual_tp:
        raise NotImplementedError(
            "qk_norm reduces over all heads; manual-tp stages hold a slice")

    def rms(x, p):
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        return (x32 * jax.lax.rsqrt(var + cfg.norm_eps)
                * p["scale"].astype(jnp.float32)).astype(x.dtype)

    if cfg.qk_norm == "head":
        def heads(x, p):
            return rms(x.reshape(*x.shape[:-1], -1, cfg.head_dim),
                       p).reshape(x.shape)
        return heads(q, lp["q_norm"]), heads(k, lp["k_norm"])
    return rms(q, lp["q_norm"]), rms(k, lp["k_norm"])


def _rope(x, positions, theta: float, rope_dim: int = 0,
          interleaved: bool = False):
    """Rotary position embedding.

    ``rope_dim`` 0/None rotates the full head dim; otherwise only the first
    ``rope_dim`` dims rotate and the tail passes through (GPT-NeoX
    ``rotary_pct < 1`` / GPT-J ``rotary_dim``). ``interleaved`` selects the
    GPT-J pairing (dims (0,1),(2,3),...) instead of the NeoX/Llama
    half-split pairing (dims (i, i+half)).
    """
    B, S, H, Hd = x.shape
    rd = rope_dim or Hd
    xr, tail = x[..., :rd], x[..., rd:]
    half = rd // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[:, :, None].astype(jnp.float32) * freqs[None, None, :]  # [B,S,half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if interleaved:
        x1, x2 = xr[..., 0::2], xr[..., 1::2]
        r1 = x1 * cos - x2 * sin
        r2 = x2 * cos + x1 * sin
        rotated = jnp.stack([r1, r2], axis=-1).reshape(xr.shape)
    else:
        x1, x2 = xr[..., :half], xr[..., half:]
        rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                                  axis=-1)
    if rd != Hd:
        rotated = jnp.concatenate([rotated, tail.astype(rotated.dtype)], axis=-1)
    return rotated.astype(x.dtype)


def _alibi_slopes(n_head: int):
    # standard alibi slope schedule
    start = 2.0**(-8.0 / n_head)
    return jnp.asarray([start**(i + 1) for i in range(n_head)], jnp.float32)


def key_mask_bias(attn_mask):
    """[B, S] 1=keep attention mask → additive key-side bias [B, S]
    (0 keep / -1e9 drop); None passes through. Single producer for every
    attention path (dense, ring, ulysses)."""
    if attn_mask is None:
        return None
    return jnp.where(attn_mask > 0, 0.0, -1e9).astype(jnp.float32)


# sequence length beyond which the XLA fallback attention streams its
# softmax (sequence/_streaming.py) instead of materialising S x S logits;
# the chunk size is deliberately smaller so just-over-threshold sequences
# don't pad a near-full chunk of dead keys
DENSE_STREAM_THRESHOLD = 4096
DENSE_STREAM_CHUNK = 1024


def _dropout(cfg: TransformerConfig, x, key):
    """Inverted dropout; identity when the rate is 0 or no key is given
    (eval / inference). Reference capability: the fused training layer's
    hidden-dropout ratios (csrc/transformer/ds_transformer_cuda.cpp
    dropout kernels; config attn_dropout_ratio/hidden_dropout_ratio)."""
    if not cfg.dropout or key is None:
        return x
    keep = 1.0 - cfg.dropout
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, 0).astype(x.dtype)


def _mtp_in(x, axis):
    """Megatron's ``f`` operator: identity forward, psum backward. Inside a
    manual-tp region the cotangents arriving from the column-parallel
    consumers (qkv / up projections) are per-shard partials; summing them
    here hands the replicated upstream land (residual, LN, embed) a full
    gradient."""
    @jax.custom_vjp
    def f(x):
        return x
    f.defvjp(lambda x: (x, None), lambda _, g: (jax.lax.psum(g, axis),))
    return f(x)


def _mtp_out(x, axis):
    """Megatron's ``g`` operator: psum forward (complete the row-parallel
    matmul's contraction over the sharded inner dim), identity backward (the
    downstream cotangent is already replicated over the axis)."""
    @jax.custom_vjp
    def g(x):
        return jax.lax.psum(x, axis)
    g.defvjp(lambda x: (jax.lax.psum(x, axis), None), lambda _, ct: (ct,))
    return g(x)


@jax.named_scope("attention")
def attention(cfg: TransformerConfig, x, lp, positions, mask_bias):
    """Einsum-form multi-head attention; XLA maps the batched matmuls onto
    the MXU and fuses softmax. (A Pallas flash-attention kernel can be slotted
    in via deepspeed_tpu.ops — see ops/transformer.)

    With ``cfg.manual_tp`` set the weights arrive pre-sliced over the tp
    mesh axis (whole heads per shard) and the block runs Megatron-style:
    f at the input, local-head attention (which reaches the bare flash
    kernel — the context is fully manual), g after the out projection."""
    B, S, D = x.shape
    H, KV, Hd = cfg.n_head, cfg.kv_heads, cfg.head_dim
    if cfg.manual_tp:
        from deepspeed_tpu.comm import bound_axis_size
        tp = bound_axis_size(cfg.manual_tp)
        H //= tp
        KV //= tp
        x = _mtp_in(x, cfg.manual_tp)

    from jax.ad_checkpoint import checkpoint_name
    x = _maybe_act_quant(cfg, x)
    # attn_bias=True REQUIRES all four bias tensors (loud KeyError on a
    # params tree saved without them, consistent with the bo access below)
    bq = lp["bq"] if cfg.attn_bias else 0
    bk = lp["bk"] if cfg.attn_bias else 0
    bv = lp["bv"] if cfg.attn_bias else 0
    q, k = _qk_norm(cfg, x @ _w(lp["wq"], x) + bq, x @ _w(lp["wk"], x) + bk, lp)
    q = checkpoint_name(q.reshape(B, S, H, Hd), "q_proj")
    k = checkpoint_name(k.reshape(B, S, KV, Hd), "k_proj")
    v = checkpoint_name((x @ _w(lp["wv"], x) + bv).reshape(B, S, KV, Hd), "v_proj")

    if cfg.pos_embedding == "rope":
        q = _rope(q, positions, cfg.rope_theta, cfg.rope_dim, cfg.rope_interleaved)
        k = _rope(k, positions, cfg.rope_theta, cfg.rope_dim, cfg.rope_interleaved)

    if cfg.pos_embedding == "alibi":
        # slope values follow the GLOBAL head index; a manual-tp shard
        # carries heads [r*H, (r+1)*H) of the full set
        slopes = _alibi_slopes(cfg.n_head)
        if cfg.manual_tp:
            r = jax.lax.axis_index(cfg.manual_tp)
            slopes = jax.lax.dynamic_slice_in_dim(slopes, r * H, H)
    else:
        slopes = None

    if cfg.sparse_attention is not None:
        if cfg.manual_tp:
            raise NotImplementedError(
                "sparse attention does not compose with manual-tp pipeline "
                "stages (the stage factory refuses this config; pp×tp runs "
                "the vmap/SPMD path instead)")
        out = _sparse_model_attention(cfg, q, k, v, mask_bias, slopes)
        out = checkpoint_name(out.reshape(B, S, H * Hd), "attn_out")
        proj = out @ _w(lp["wo"], out) + (lp["bo"] if cfg.attn_bias else 0)
        return checkpoint_name(proj, "wo_out")

    sp_mesh = _sp_mesh(cfg)
    out = None
    # a block-causal model's staircase: the direct kernel and the einsum
    # take it as a shape of the call; the sharded and streamed forms do not
    stair = {} if cfg.causal_block == 1 else {"causal_block": cfg.causal_block}
    if stair and (sp_mesh is not None or S > DENSE_STREAM_THRESHOLD
                  or (not _use_flash(cfg) and _flash_mesh(cfg) is not None)):
        raise NotImplementedError(
            "a block-causal mask (cfg.generation) is built for the direct "
            "flash kernel and the einsum form only")
    if sp_mesh is not None:
        # GQA kv stays UNREPEATED through the sp collectives (ring ppermute /
        # ulysses all-to-all move H/KV-times less data); the shard bodies
        # broadcast kv heads locally
        from deepspeed_tpu.sequence import sp_attention
        if cfg.attn_block_q or cfg.attn_block_k:
            from deepspeed_tpu.utils.logging import warn_once
            warn_once("attn_block_q/attn_block_k apply to the direct and "
                      "batch/head-sharded flash paths; the sequence-parallel "
                      "kernels keep their own shard-local block tuning")
        out = sp_attention(q, k, v, mesh=sp_mesh, impl=cfg.sequence_parallel,
                           causal=cfg.causal, mask_bias=mask_bias,
                           alibi_slopes=slopes, scale=cfg.attn_scale)
        form = "sp"
    else:
        # kernel paths first — the Pallas kernel beats the XLA streaming
        # core at every length it can run
        use_direct = _use_flash(cfg)
        fmesh = None if use_direct else _flash_mesh(cfg)
        if use_direct or fmesh is not None:
            # GQA kv goes in UNREPEATED — the flash kernel index-maps query
            # head h to kv head h // (H/KV), so HBM/VMEM kv traffic stays at
            # KV heads (H/KV× less on llama-style GQA)
            if use_direct:
                from deepspeed_tpu.ops.pallas import flash_attention
                out = flash_attention(q, k, v, mask_bias=mask_bias,
                                      causal=cfg.causal, alibi_slopes=slopes,
                                      scale=cfg.attn_scale,
                                      block_q=cfg.attn_block_q,
                                      block_k=cfg.attn_block_k, **stair)
                form = "flash"
            else:
                out = _flash_sharded(cfg, q, k, v, mask_bias, slopes, fmesh)
                form = "flash_sharded"
        if out is None and S > DENSE_STREAM_THRESHOLD:
            # long sequences off the kernel paths (pipeline stage vmap,
            # sp-less CPU, shapes outside the kernel envelope): stream the
            # softmax through the shared chunked core instead of
            # materialising the S x S logits — pure jnp, so it vmaps over
            # pipeline stages and partitions under pp where a Pallas call
            # cannot go. GQA kv goes in unrepeated when no kernel was tried
            # (the core broadcasts per chunk).
            from deepspeed_tpu.sequence._streaming import chunked_attention
            mb = None if mask_bias is None else mask_bias.astype(jnp.float32)
            out, _ = chunked_attention(q, k, v, mb, slopes, jnp.int32(0),
                                       jnp.int32(0), cfg.causal,
                                       DENSE_STREAM_CHUNK, q.dtype,
                                       cfg.attn_scale)
            form = "chunked_stream"
    if out is None:
        form = "einsum"
        # GQA kv goes in UNREPEATED — mha_attention contracts grouped query
        # heads [KV, G] against the raw kv, no H/KV× copy
        from deepspeed_tpu.ops.attention import mha_attention
        out = mha_attention(q, k, v,
                            mask_bias=None if mask_bias is None else mask_bias[:, None, None, :],
                            causal=cfg.causal, alibi_slopes=slopes,
                            scale=cfg.attn_scale, **stair)
    dispatch.record("attention", form,
                    f"B={B} S={S} H={H} KV={k.shape[2]} Hd={Hd}")
    out = checkpoint_name(out.reshape(B, S, H * Hd), "attn_out")
    if cfg.attn_out_gate:
        out = out * jax.nn.sigmoid(x @ _w(lp["wg"], x))
    proj = out @ _w(lp["wo"], out)
    if cfg.manual_tp:
        # row-parallel wo: each shard contracted its local heads only —
        # complete the sum, then add the replicated bias ONCE
        proj = _mtp_out(proj, cfg.manual_tp)
    proj = proj + (lp["bo"] if cfg.attn_bias else 0)
    return checkpoint_name(proj, "wo_out")


def _sparse_model_attention(cfg: TransformerConfig, q, k, v, mask_bias, slopes):
    """Model-level block-sparse attention (cfg.sparse_attention set): every
    layer computes softmax over the sparsity layout's support only. TPU
    single-device/full-manual contexts run the block-sparse flash kernel
    (zero blocks skipped fwd+bwd); everywhere else the exact dense
    token-bias einsum, which vmaps and partitions like the other fallbacks.
    Reference capability: sparse_attention_utils.py module surgery swapping
    BertSparseSelfAttention into the encoder."""
    from deepspeed_tpu.ops.sparse_attention.sparse_self_attention import (
        sparse_attention_core)
    B, S, H, Hd = q.shape
    if k.shape[2] != H:
        raise NotImplementedError(
            "sparse attention requires n_kv_head == n_head (MHA)")
    if slopes is not None:
        raise NotImplementedError("sparse attention does not compose with alibi")
    if _sp_mesh(cfg) is not None:
        raise NotImplementedError(
            "sparse attention does not compose with sequence parallelism")
    sc = cfg.sparse_attention
    # Dense/base configs carry no directionality — cfg.causal alone governs
    mode = getattr(sc, "attention", None)
    if mode is not None and (mode == "unidirectional") != bool(cfg.causal):
        raise ValueError(f"sparsity config attention={mode!r} disagrees with "
                         f"the model's causal={cfg.causal}")
    layout = sc.make_layout(S)
    if layout.shape[0] != H:
        raise ValueError(f"sparsity config num_heads={layout.shape[0]} != "
                         f"model n_head={H}")
    # the kernel wants layout blocks that are legal VMEM tiles; smaller
    # blocks (or CPU) take the exact dense form (make_layout already
    # rejected S not divisible by the block; the core rejects dense
    # fallbacks past its DENSE_SPARSE_MAX_SEQ — single guard, single
    # message)
    mb = None if mask_bias is None else mask_bias.astype(jnp.float32)
    if sc.block >= 128 and sc.block % 8 == 0:  # legal VMEM tile sizes only
        if _use_flash(cfg):
            return sparse_attention_core(q, k, v, layout, sc.block,
                                         bool(cfg.causal), mb,
                                         scale=cfg.attn_scale, use_pallas=True)
        fmesh = _flash_mesh(cfg)
        if fmesh is not None:
            # multi-chip dp/fsdp×tp(×ep) mesh: the layout rides the head
            # axis through the shard_map so every shard keeps the
            # block-sparse kernel
            out = _flash_sharded(cfg, q, k, v, mb, None, fmesh,
                                 block_layout=layout)
            if out is not None:
                return out
    return sparse_attention_core(q, k, v, layout, sc.block, bool(cfg.causal),
                                 mb, scale=cfg.attn_scale, use_pallas=False)


def _inside_full_manual(mesh) -> bool:
    """True when every mesh axis of size > 1 is a manual axis of the current
    trace — i.e. we are inside a shard_map over all partitioned axes, so
    array data is fully device-local and a bare ``pallas_call`` is legal.
    This is how attention under the pipeline engine's stage shard_map
    reaches the flash kernel (runtime/pipe/engine.py)."""
    for name, size in mesh.shape.items():
        if size > 1:
            try:
                # probe only: axis_index raises NameError iff the axis is
                # not bound in the current trace
                jax.lax.axis_index(name)
            except NameError:
                return False
    return True


def _bare_pallas_legal() -> bool:
    """Whether a bare (unwrapped) ``pallas_call`` is legal here: single-device
    meshes, or a fully-manual shard_map context (every partitioned mesh axis
    already local, e.g. the pipeline engine's stage bodies). Elsewhere XLA's
    SPMD partitioner would have to partition the call, which it cannot —
    the single invariant behind both the flash-attention and fused-CE
    dispatches."""
    import deepspeed_tpu.comm as dist
    return not (dist.has_mesh() and dist.get_mesh().devices.size > 1
                and not _inside_full_manual(dist.get_mesh()))


def _use_flash(cfg: TransformerConfig) -> bool:
    """Direct (unwrapped) Pallas flash attention where a bare pallas_call is
    legal (:func:`_bare_pallas_legal`). Other multi-device meshes go through
    :func:`_flash_sharded` (shard_map over batch/head axes) instead."""
    if cfg.attention_backend not in ("flash", "auto"):
        return False
    if not _bare_pallas_legal():
        return False
    if cfg.attention_backend == "flash":
        return True
    return dispatch.on_tpu()


def _flash_mesh(cfg: TransformerConfig):
    """The active mesh when the shard_map-wrapped flash kernel applies:
    every axis of size > 1 must be one the kernel can shard without
    communication — batch over dp/fsdp, heads over tp — or one attention is
    replicated over (ep: expert parallelism shards only the expert MLPs, so
    attention math is identical across the axis). Pipeline / sequence axes
    fall back to the einsum form (attention there runs under the stage vmap /
    the sp paths, where a shard_map cannot be placed)."""
    if cfg.attention_backend not in ("flash", "auto"):
        return None
    if cfg.attention_backend == "auto" and not dispatch.on_tpu():
        return None
    import deepspeed_tpu.comm as dist
    if not dist.has_mesh():
        return None
    mesh = dist.get_mesh()
    if mesh.devices.size == 1:
        return None
    for name, size in mesh.shape.items():
        if size > 1 and name not in ("dp", "fsdp", "tp", "ep"):
            return None
        if size > 1:
            # already inside a shard_map/pmap over this axis (e.g. the 1-bit
            # optimizer step)? a nested shard_map is illegal — use einsum
            # (axis_index as the bound-axis probe, see _inside_full_manual)
            try:
                jax.lax.axis_index(name)
                return None
            except NameError:
                pass
    return mesh


def _shard_axes(mesh, B: int, H: int, KV: int = None):
    """Batch/head mesh-axis split shared by the shard_map-wrapped kernels:
    returns (batch_axes, head_axis, nb, nh), or None when the sizes don't
    divide the axes."""
    batch_axes = tuple(a for a in ("dp", "fsdp") if mesh.shape.get(a, 1) > 1)
    head_axis = "tp" if mesh.shape.get("tp", 1) > 1 else None
    nb = 1
    for a in batch_axes:
        nb *= mesh.shape[a]
    nh = mesh.shape["tp"] if head_axis else 1
    if B % nb or H % nh or (KV is not None and KV % nh):
        return None
    return batch_axes, head_axis, nb, nh


def _flash_sharded(cfg: TransformerConfig, q, k, v, mask_bias, slopes, mesh,
                   block_layout=None):
    """Flash attention under a dp/fsdp×tp mesh: shard_map over the batch and
    head axes (no cross-shard communication — attention is pointwise in batch
    and head), so the Pallas kernel runs per-shard instead of silently
    falling back to O(S²) einsum attention on multi-chip meshes.
    ``block_layout`` [H, nb, nb] rides the head axis, so block-SPARSE
    attention keeps the kernel on multi-chip meshes too.
    Returns None when the shard sizes don't divide (caller falls back)."""
    from deepspeed_tpu.utils.jax_compat import shard_map

    B, S, H, Hd = q.shape
    KV = k.shape[2]
    split = _shard_axes(mesh, B, H, KV)
    if split is None and KV != H and _shard_axes(mesh, B, H) is not None:
        # KV heads don't divide the tp axis (e.g. 8 kv heads, tp=16): repeat
        # kv to H heads so each shard still runs the kernel — pays the GQA
        # repeat copy but keeps the flash path
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
        KV = H
        split = _shard_axes(mesh, B, H)
    if split is None:
        return None
    batch_axes, head_axis, nb, nh = split

    qspec = P(batch_axes or None, None, head_axis, None)
    mspec = P(batch_axes or None, None)
    sspec = P(head_axis)

    from deepspeed_tpu.ops.pallas import flash_attention

    # None mask/slopes stay None INSIDE the shard_map (instead of zero
    # arrays) so the kernel's plain-causal fast path engages per shard
    operands = [q, k, v]
    specs = [qspec, qspec, qspec]
    if mask_bias is not None:
        operands.append(mask_bias.astype(jnp.float32))
        specs.append(mspec)
    if slopes is not None:
        operands.append(jnp.asarray(slopes, jnp.float32).reshape(H))
        specs.append(sspec)
    if block_layout is not None:
        operands.append(jnp.asarray(block_layout, jnp.float32))
        specs.append(P(head_axis))

    def inner(qs, ks, vs, *rest):
        rest = list(rest)
        ms = rest.pop(0) if mask_bias is not None else None
        ss = rest.pop(0) if slopes is not None else None
        bl = rest.pop(0) if block_layout is not None else None
        return flash_attention(qs, ks, vs, mask_bias=ms, causal=cfg.causal,
                               alibi_slopes=ss, scale=cfg.attn_scale,
                               block_q=cfg.attn_block_q,
                               block_k=cfg.attn_block_k,
                               block_layout=bl)

    wrapped = shard_map(inner, mesh=mesh, in_specs=tuple(specs),
                       out_specs=qspec, check_vma=False)
    return wrapped(*operands)


def _decode_sharded(q1, ck, cv, pos, pad_bias, slopes, mesh, scale=None):
    """Decode-attention kernel under a dp/fsdp×tp mesh: shard_map over batch
    (q/cache/pad_bias) and heads (q + KV cache + slopes) — decode attention
    is pointwise in batch and head, so shards need no communication and the
    multi-chip TP serving path keeps the fused kernel instead of the
    O(B·H·Smax) einsum with a repeated GQA cache.
    Returns None when shard sizes don't divide or the per-shard shape is
    outside the kernel envelope (caller falls back)."""
    from deepspeed_tpu.utils.jax_compat import shard_map

    B, H, Hd = q1.shape
    Smax, KV = ck.shape[1], ck.shape[2]
    split = _shard_axes(mesh, B, H, KV)
    if split is None:
        return None
    batch_axes, head_axis, nb, nh = split
    # per-shard kernel envelope, checked here because the shard_map body
    # cannot fall back per-shard
    if (H // nh) % (KV // nh) or Hd % 64 or Smax % 128:
        return None

    from deepspeed_tpu.ops.pallas.decode_attention import decode_attention

    qspec = P(batch_axes or None, head_axis, None)
    cspec = P(batch_axes or None, None, head_axis, None)
    operands = [q1, ck, cv, jnp.asarray(pos, jnp.int32)]
    specs = [qspec, cspec, cspec, P()]
    if pad_bias is not None:
        operands.append(pad_bias.astype(jnp.float32))
        specs.append(P(batch_axes or None, None))
    if slopes is not None:
        operands.append(jnp.asarray(slopes, jnp.float32).reshape(H))
        specs.append(P(head_axis))

    def inner(qs, cks, cvs, ps, *rest):
        rest = list(rest)
        ms = rest.pop(0) if pad_bias is not None else None
        ss = rest.pop(0) if slopes is not None else None
        return decode_attention(qs, cks, cvs, ps, pad_bias=ms, alibi_slopes=ss,
                                scale=scale)

    wrapped = shard_map(inner, mesh=mesh, in_specs=tuple(specs),
                        out_specs=qspec, check_vma=False)
    return wrapped(*operands)


def _paged_shard_ok(mesh, H: int, KV: int, Hd: int, bs: int) -> bool:
    """Whether the shard_map'd paged kernel applies on ``mesh``: heads and
    KV heads must divide the tp axis, and the PER-SHARD shape must sit
    inside the kernel envelope (a shard_map body cannot fall back
    per-shard, so the check happens out here)."""
    from deepspeed_tpu.ops.pallas.paged_decode_attention import \
        paged_envelope_ok
    nh = mesh.shape.get("tp", 1)
    if H % nh or KV % nh:
        return False
    return paged_envelope_ok(H // nh, KV // nh, Hd, bs)


def _paged_decode_sharded(q1, kp, vp, block_tables, pos, pad_bias, slopes,
                          mesh, scale=None, dummy_block=0):
    """Paged decode-attention kernel under an SPMD mesh: shard_map over the
    KV-HEAD axis — q splits over ``tp`` by heads and the block pools' merged
    ``KV*Hd`` rows by whole kv heads, while block tables, positions and the
    logical-position bias stay REPLICATED
    (per-shard block indices are identical; the head split is the only
    partition, so shards need no communication). dp/fsdp/ep axes replicate
    the whole fused step: continuous batching is ONE program over all
    running rows and the pool is shared state, not batch data. This is how
    multi-chip TP serving keeps the scalar-prefetched Pallas kernel
    instead of falling back to the gather + einsum path.
    Returns None when :func:`_paged_shard_ok` rejects the split."""
    from deepspeed_tpu.utils.jax_compat import shard_map

    B, H, Hd = q1.shape
    bs, KV = kp.shape[1], kp.shape[2] // Hd
    if not _paged_shard_ok(mesh, H, KV, Hd, bs):
        return None
    head_axis = "tp" if mesh.shape.get("tp", 1) > 1 else None

    from deepspeed_tpu.ops.pallas.paged_decode_attention import \
        paged_decode_attention

    qspec = P(None, head_axis, None)
    pspec = P(None, None, head_axis)
    operands = [q1, kp, vp, jnp.asarray(block_tables, jnp.int32),
                jnp.asarray(pos, jnp.int32),
                jnp.asarray(dummy_block, jnp.int32)]
    specs = [qspec, pspec, pspec, P(), P(), P()]
    if pad_bias is not None:
        operands.append(pad_bias.astype(jnp.float32))
        specs.append(P(None, None))
    if slopes is not None:
        # contiguous head chunks of H/nh = G * (KV/nh) heads: each shard's
        # slopes regroup to its own (KV_shard, G) exactly like q does
        operands.append(jnp.asarray(slopes, jnp.float32).reshape(H))
        specs.append(P(head_axis))

    def inner(qs, kps, vps, bts, ps, dummy, *rest):
        rest = list(rest)
        ms = rest.pop(0) if pad_bias is not None else None
        ss = rest.pop(0) if slopes is not None else None
        return paged_decode_attention(qs, kps, vps, bts, ps, pad_bias=ms,
                                      alibi_slopes=ss, scale=scale,
                                      dummy_block=dummy)

    wrapped = shard_map(inner, mesh=mesh, in_specs=tuple(specs),
                        out_specs=qspec, check_vma=False)
    return wrapped(*operands)


def _sp_mesh(cfg: TransformerConfig):
    """The active mesh when sequence parallelism is configured AND the mesh
    carries an sp axis of size > 1; else None (dense attention)."""
    if cfg.sequence_parallel == "none":
        return None
    import deepspeed_tpu.comm as dist
    if not dist.has_mesh():
        return None
    mesh = dist.get_mesh()
    if "sp" in mesh.shape and mesh.shape["sp"] > 1:
        return mesh
    return None


def _remat_policy(remat):
    """Map the config's remat setting to a jax.checkpoint policy (None =
    full remat, the reference's save-only-inputs CheckpointFunction).

    ``"dots"`` keeps every matmul result of size tokens x width (the
    projections, the MLP's products; not an einsum attention's batched
    S x S scores) AND the flash kernel's named residuals: its output is the
    attention's matmul result at ``[B, S, D]``, which the dots policy cannot
    see inside a ``pallas_call``. Kept, they cost tokens x d_model x 2 B +
    heads x tokens x 4 B a layer (a tenth more than the nine projection-sized
    units ``dots`` keeps at ``d_ff = 4 d``) and the backward runs the ``dq`` /
    ``dkv`` kernels on them instead of first re-running the forward kernel.
    A block without a flash kernel has no such names and traces as before."""
    if remat is True or remat == "full":
        return None
    from deepspeed_tpu.ops.pallas.flash_attention import \
        RESIDUAL_NAMES as _FLASH_RESIDUALS
    pols = jax.checkpoint_policies
    if remat == "dots":
        return pols.save_from_both_policies(
            pols.dots_with_no_batch_dims_saveable,
            pols.save_only_these_names(*_FLASH_RESIDUALS))
    if remat == "selective":
        # save only the [tokens, D]-sized projections (cheap to store) plus
        # the flash kernel's (o, lse) residuals — so backward runs the flash
        # backward kernels WITHOUT re-running the forward kernel — and
        # recompute the d_ff-sized up/gate activations in backward
        return pols.save_only_these_names(
            "q_proj", "k_proj", "v_proj", "attn_out", "wo_out", "ff_down",
            *_FLASH_RESIDUALS)
    if remat == "offload_dots":
        return pols.offload_dot_with_no_batch_dims("device", "pinned_host")
    raise ValueError(f"unknown remat policy {remat!r} (expected True/'full', "
                     "'dots', 'selective', 'offload_dots', False/'none')")


def _maybe_act_quant(cfg: TransformerConfig, x):
    """QAT activation fake-quant at the matmul inputs (the reference's
    QuantAct placement); dynamic per-tensor range, STE backward."""
    if cfg.act_quant_bits:
        from deepspeed_tpu.compression.functional import quantize_activation
        return quantize_activation(x, cfg.act_quant_bits, cfg.act_quant_sym)
    return x


@jax.named_scope("mlp")
def mlp(cfg: TransformerConfig, x, lp):
    from jax.ad_checkpoint import checkpoint_name
    x = _maybe_act_quant(cfg, x)
    if cfg.manual_tp:
        x = _mtp_in(x, cfg.manual_tp)
    if cfg.activation == "swiglu":
        out = (jax.nn.silu(x @ _w(lp["w_gate"], x)) * (x @ _w(lp["w_up"], x))) @ _w(lp["w_down"], x)
        if cfg.manual_tp:
            out = _mtp_out(out, cfg.manual_tp)
        return checkpoint_name(out, "ff_down")
    h = x @ _w(lp["w_up"], x) + lp["b_up"]
    if cfg.activation == "gelu":
        h = jax.nn.gelu(h, approximate=True)
    elif cfg.activation == "gelu_exact":
        h = jax.nn.gelu(h, approximate=False)  # BERT's erf gelu
    elif cfg.activation == "quick_gelu":
        h = h * jax.nn.sigmoid(1.702 * h)  # CLIP's QuickGELU
    else:
        h = jax.nn.relu(h)
    out = h @ _w(lp["w_down"], x)
    if cfg.manual_tp:
        # row-parallel w_down: sum the per-shard partials, replicated bias once
        out = _mtp_out(out, cfg.manual_tp)
    return checkpoint_name(out + lp["b_down"], "ff_down")


def block(cfg: TransformerConfig, x, lp, positions, mask_bias, rng=None):
    ka = km = None
    if rng is not None and cfg.dropout:
        ka, km = jax.random.split(rng)
    if cfg.norm_position == "post":
        # BERT-style add&norm: residual first, LN after (reference's fused
        # encoder layer, csrc/transformer/ds_transformer_cuda.cpp pre/post
        # layernorm modes)
        a = _dropout(cfg, attention(cfg, x, lp["attn"], positions, mask_bias), ka)
        x = _norm(cfg, x + a, lp["ln_attn"])
        return _norm(cfg, x + _dropout(cfg, mlp(cfg, x, lp["mlp"]), km), lp["ln_mlp"])
    a = _dropout(cfg, attention(cfg, _norm(cfg, x, lp["ln_attn"]), lp["attn"],
                                positions, mask_bias), ka)
    if cfg.parallel_residual:
        m = _dropout(cfg, mlp(cfg, _norm(cfg, x, lp["ln_mlp"]), lp["mlp"]), km)
        return x + a + m
    x = x + a
    m = _dropout(cfg, mlp(cfg, _norm(cfg, x, lp["ln_mlp"]), lp["mlp"]), km)
    return x + m


def forward(cfg: TransformerConfig, params, tokens, attn_mask=None):
    """tokens [B, S] int32 → logits [B, S, vocab]."""
    x = hidden_states(cfg, params, tokens, attn_mask)
    return x @ _head_weight(cfg, params) + _head_bias(params)


# --------------------------------------------------------------------- #
# KV-cache inference path (reference: preallocated workspace + KV append,
# csrc/transformer/inference/includes/inference_context.h:49, softmax_context
# csrc/transformer/inference/csrc/pt_binding.cpp:1668-1793, layer-past
# handling deepspeed/model_implementations/transformers/ds_transformer.py:18).
# TPU design: a donated fixed-shape [L, B, Smax, KV, Hd] cache updated with
# dynamic_update_slice inside one jitted program per (prefill, decode) shape
# — no per-token recompilation, O(Smax) attention per generated token.

def init_kv_cache(cfg: TransformerConfig, batch_size: int, max_len: Optional[int] = None,
                  dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Preallocated KV cache: k/v [n_layer, B, max_len, kv_heads, head_dim]."""
    Smax = max_len or cfg.max_seq
    shape = (cfg.n_layer, batch_size, Smax, cfg.kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _attn_out(cfg: TransformerConfig, out, x, lp):
    """The output projection of every cache path: ``out`` [B, T, H*Hd] the
    heads' outputs, ``x`` the block input they were computed from. With
    ``cfg.attn_out_gate``: (out * sigmoid(x Wg)) Wo."""
    if cfg.attn_out_gate:
        out = out * jax.nn.sigmoid(x @ _w(lp["wg"], x))
    return out @ _w(lp["wo"], out) + (lp["bo"] if cfg.attn_bias else 0)


def _flat(y):
    """A projection's flat output [rows, H*Hd], held as it is ahead of the
    split into heads. Without it XLA:TPU sinks the ``reshape(.., H, Hd)``
    into the product, batches it over heads and wants the weight as
    [H, Hd, D]: the layer's matrix is then sliced out of its [L, ...] stack
    and transposed, a copy of the whole weight twice a layer a step, to
    multiply a few dozen rows by it. With it the product is the plain
    [rows, D] x [D, H*Hd] its siblings (``wv``, ``wo``) are, reads the
    stack where it lies, and what is relaid for the kernel is the small
    output (PERF.md section 6, PR 57). The identity: same product, same
    precision. For the projections whose output is split right after the
    product (or inside a per-head norm): not the training ``attention()``,
    whose thousands of rows make the compiler's layout the better trade."""
    return jax.lax.optimization_barrier(y)


def _qkv_project(cfg: TransformerConfig, x, lp, positions,
                 kind: str = ATTENTION):
    """Shared decode-side q/k/v projection: act-quant (QAT parity with the
    training path — or prefill/decode logits diverge from forward()),
    optional attn biases (attn_bias=True REQUIRES all four bias tensors —
    loud KeyError on a params tree saved without them), head reshape, rope
    where the layer's ``kind`` takes it.
    Returns (q [B,T,H,Hd], k [B,T,KV,Hd], v [B,T,KV,Hd])."""
    B, T, D = x.shape
    H, KV, Hd = cfg.n_head, cfg.kv_heads, cfg.head_dim
    x = _maybe_act_quant(cfg, x)
    bq = lp["bq"] if cfg.attn_bias else 0
    bk = lp["bk"] if cfg.attn_bias else 0
    bv = lp["bv"] if cfg.attn_bias else 0
    # a norm over the whole vector reads the flat output itself, and the
    # compiler leaves that product plain unasked
    held = (lambda y: y) if cfg.qk_norm is True else _flat
    q, k = _qk_norm(cfg, held(x @ _w(lp["wq"], x) + bq),
                    held(x @ _w(lp["wk"], x) + bk), lp)
    q = q.reshape(B, T, H, Hd)
    k = k.reshape(B, T, KV, Hd)
    v = (x @ _w(lp["wv"], x) + bv).reshape(B, T, KV, Hd)
    if cfg.takes_rope(kind):
        q = _rope(q, positions, cfg.rope_theta, cfg.rope_dim, cfg.rope_interleaved)
        k = _rope(k, positions, cfg.rope_theta, cfg.rope_dim, cfg.rope_interleaved)
    return q, k, v


def _grouped_cache_einsum(cfg: TransformerConfig, q, ck, cv, positions,
                          pad_bias, kpos=None, window: int = 0):
    """Grouped-head einsum of q [B,T,H,Hd] against an UNREPEATED cache
    ck/cv [B,S,KV,Hd] with per-row causal masking at ``positions`` (query
    heads reshaped [KV, G]: head h reads kv head h // G, matching the
    kernels' index maps — off-kernel decode skips the H/KV× cache copy).
    The single masked-softmax core shared by the dense-workspace and paged
    fallback paths. ``kpos`` [B, S]: the keys' positions where they are not
    ``arange(S)`` (a window layer's ring; negative = holds nothing);
    ``window`` > 0: a query at i sees i - window < j <= i.
    Returns [B, T, H*Hd]."""
    B, T, H, Hd = q.shape
    S, KV = ck.shape[1], ck.shape[2]
    G = H // KV
    scale = Hd**-0.5 if cfg.attn_scale is None else cfg.attn_scale
    q5 = q.reshape(B, T, KV, G, Hd)
    scores = jnp.einsum("btcgd,bscd->bcgts", q5, ck,
                        preferred_element_type=jnp.float32) * scale
    ring = kpos is not None
    kpos = kpos[:, None, None, None, :] if ring else \
        jnp.arange(S, dtype=jnp.int32)[None, None, None, None, :]     # [1,1,1,1,S]
    qpos = positions[:, None, None, :, None]                          # [B,1,1,T,1]
    valid = kpos <= qpos                                              # causal + cache bound
    if cfg.causal_block > 1:
        # block-causal: everything of the query's own block, and before it
        valid = kpos // cfg.causal_block <= qpos // cfg.causal_block
    if ring:
        valid = valid & (kpos >= 0)
    if window:
        valid = valid & (kpos > qpos - window)
    if cfg.pos_embedding == "alibi":
        slopes5 = _alibi_slopes(H).reshape(KV, G)
        scores = scores + slopes5[None, :, :, None, None] * (kpos - qpos).astype(jnp.float32)
    scores = jnp.where(valid, scores, -1e30)
    if pad_bias is not None:
        scores = scores + pad_bias[:, None, None, None, :]
    probs = jax.nn.softmax(scores, axis=-1).astype(cv.dtype)
    return jnp.einsum("bcgts,bscd->btcgd", probs, cv).reshape(B, T, H * Hd)


def _cached_attention(cfg: TransformerConfig, x, lp, positions, pos, ck, cv, pad_bias):
    """Attention for T new tokens against the (updated) KV cache.

    x [B, T, D]; positions [B, T] global positions of the new tokens —
    the engine contract is ``positions == pos + arange(T)`` per row (rope
    uses the array; causal/alibi geometry in both the streaming and dense
    branches assumes that contiguous layout); pos [] int32 tokens already
    cached; ck/cv [B, Smax, KV, Hd]. Returns (out [B, T, D], new ck, cv)."""
    B, T, D = x.shape
    H, KV, Hd = cfg.n_head, cfg.kv_heads, cfg.head_dim
    Smax = ck.shape[1]

    q, k, v = _qkv_project(cfg, x, lp, positions)

    ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, pos, 0, 0))
    cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, pos, 0, 0))

    if T == 1:
        # fused decode kernel: streams the cache once, no GQA repeat copy
        # (reference softmax_context, pt_binding.cpp:1668-1793) — direct on
        # one device, shard_map over batch/head axes on dp/fsdp×tp meshes
        slopes = _alibi_slopes(H) if cfg.pos_embedding == "alibi" else None
        o = None
        if _use_flash(cfg):
            from deepspeed_tpu.ops.pallas.decode_attention import decode_attention
            o = decode_attention(q[:, 0], ck, cv, pos, pad_bias=pad_bias,
                                 alibi_slopes=slopes, scale=cfg.attn_scale)
        else:
            dmesh = _flash_mesh(cfg)
            if dmesh is not None:
                o = _decode_sharded(q[:, 0], ck, cv, pos, pad_bias,
                                    slopes, dmesh, scale=cfg.attn_scale)
        if o is not None:
            out = o.reshape(B, 1, H * Hd)
            out = _attn_out(cfg, out, x, lp)
            return out, ck, cv

    if Smax > DENSE_STREAM_THRESHOLD:
        # long-workspace prefill AND kernel-less decode: stream the softmax
        # over cache chunks (O(T·chunk) live memory, no rep-expanded cache
        # copy) instead of the O(T·Smax) einsum below. The core derives
        # query positions as pos + arange(T) — identical to the engine
        # contract this function documents (positions = pos + arange), which
        # the dense path below also assumes per batch row.
        from deepspeed_tpu.sequence._streaming import chunked_attention
        slopes = _alibi_slopes(H) if cfg.pos_embedding == "alibi" else None
        pb = None if pad_bias is None else pad_bias.astype(jnp.float32)
        o, _ = chunked_attention(q, ck, cv, pb, slopes,
                                 jnp.asarray(pos, jnp.int32), jnp.int32(0),
                                 True, DENSE_STREAM_CHUNK, q.dtype,
                                 cfg.attn_scale)
        out = o.reshape(B, T, H * Hd)
        out = _attn_out(cfg, out, x, lp)
        return out, ck, cv

    out = _grouped_cache_einsum(cfg, q, ck, cv, positions, pad_bias)
    out = _attn_out(cfg, out, x, lp)
    return out, ck, cv


def cached_embed(cfg: TransformerConfig, params, tokens, pos, dtype):
    """Embedding for the cached path: tokens [B, T] at cache offset ``pos``
    — a scalar (whole-batch offset, the dense workspace path) or a [B]
    vector (per-request offsets, the paged continuous-batching path)."""
    B, T = tokens.shape
    x = params["embed"]["tokens"][tokens].astype(dtype)
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    positions = jnp.asarray(pos, jnp.int32).reshape(-1, 1) \
        + jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))
    if cfg.pos_embedding == "learned":
        x = x + params["embed"]["positions"][positions].astype(x.dtype)
    if cfg.embed_layernorm:
        x = _norm(cfg, x, params["embed"]["ln"])
    return x, positions


def _decode_block(cfg: TransformerConfig, h, lp, mix_fn, mlp_fn=None,
                  scope: str = ATTENTION):
    """The ONE pre-LN residual wiring of every cache-decode block (dense
    workspace via :func:`cached_block`, paged prefill and paged decode):
    ``mix_fn(x_normed)`` returns (mixer_out, *the mixer's new caches): k and
    v of an attention layer, state and conv state of a recurrent one (KDA,
    Mamba-2), under ``scope``; ``mlp_fn(cfg, x_normed, lp, mixer_in)``
    overrides the dense MLP (MoE; ``mixer_in`` is what the mixer read, for a
    router placed before attention). A branch joins the residual stream
    times ``cfg.residual_multiplier`` where the model has one, and under
    ``norm_position="sandwich"`` through its own norm (``ln_attn_post`` /
    ``ln_mlp_post``, scope ``branch_norm`` inside the mixer's scope and
    inside ``mlp``). Returns (h, *new caches)."""
    mfn = mlp_fn if mlp_fn is not None else (
        lambda c, xx, lpp, _: mlp(c, xx, lpp["mlp"]))
    # 1.0 puts no multiply into a model's step that has no multiplier
    rm = cfg.residual_multiplier
    scaled = (lambda b: b) if rm == 1.0 else (lambda b: b * rm)

    def joins(b, post, where):
        """Branch ``b`` as it joins the stream: through the layer's norm
        ``post`` under a sandwich norm, as it is otherwise."""
        if cfg.norm_position != "sandwich":
            return scaled(b)
        with jax.named_scope(where), jax.named_scope("branch_norm"):
            return scaled(_norm(cfg, b, lp[post]))

    xa = _norm(cfg, h, lp["ln_attn"])
    with jax.named_scope(scope):
        a, *caches = mix_fn(xa)
    a = joins(a, "ln_attn_post", scope)
    if cfg.parallel_residual:
        m = mfn(cfg, _norm(cfg, h, lp["ln_mlp"]), lp, xa)
        return (h + a + joins(m, "ln_mlp_post", "mlp"), *caches)
    h = h + a
    m = mfn(cfg, _norm(cfg, h, lp["ln_mlp"]), lp, xa)
    return (h + joins(m, "ln_mlp_post", "mlp"), *caches)


def cached_block(cfg: TransformerConfig, h, lp, ck, cv, positions, pos,
                 pad_bias=None, mlp_fn=None):
    """ONE layer of the KV-cache path: pre-LN attention against + append to
    the layer's cache. Shared by the compiled scan in :func:`forward_cached`
    and ZeRO-Inference weight streaming (per-layer host→device loop,
    ``inference/engine.py``). ``mlp_fn(cfg, x_normed, lp, mixer_in)``
    overrides the dense MLP (the MoE zoo passes its routed experts)."""
    return _decode_block(
        cfg, h, lp,
        lambda xn: _cached_attention(cfg, xn, lp["attn"], positions, pos,
                                     ck, cv, pad_bias),
        mlp_fn)


@jax.named_scope("vocab_head")
def cached_head(cfg: TransformerConfig, params, x):
    """Final norm + logits projection of the cached path (the logits over
    ``cfg.logits_scaling`` where the model has one)."""
    x = _norm(cfg, x, params["ln_f"])
    logits = x @ _head_weight(cfg, params) + _head_bias(params)
    return logits if cfg.logits_scaling == 1.0 else logits / cfg.logits_scaling


def forward_cached(cfg: TransformerConfig, params, tokens, cache, pos, pad_bias=None,
                   mlp_fn=None):
    """tokens [B, T] (T static: prompt chunk or 1) attended against + appended
    to ``cache`` at offset ``pos`` ([] int32). Returns (logits [B, T, vocab],
    new cache). ``pad_bias`` [B, Smax] additive f32 masks cache slots of
    left-padded prompts; ``mlp_fn`` see :func:`cached_block`."""
    _paged_path_only(cfg, "the dense-workspace KV cache")
    if cfg.norm_position == "post":
        raise ValueError("norm_position='post' is not supported by the "
                         "KV-cache decode path (pre-LN only)")
    if cfg.sparse_attention is not None:
        # decoding attends position-by-position against the whole cache; a
        # training-time block layout does not transfer — reject rather than
        # silently decode dense and diverge from forward()
        raise NotImplementedError(
            "sparse_attention is not supported by the KV-cache decode path; "
            "serve with the dense forward() or drop the sparsity config")
    x, positions = cached_embed(cfg, params, tokens, pos, cache["k"].dtype)

    def run_block(h, xs):
        lp, ck, cv = xs
        h, nck, ncv = cached_block(cfg, h, lp, ck, cv, positions, pos, pad_bias,
                                   mlp_fn)
        return h, (nck, ncv)

    x, (nk, nv) = jax.lax.scan(run_block, x, (params["layers"], cache["k"], cache["v"]))
    logits = cached_head(cfg, params, x)
    return logits, {"k": nk, "v": nv}


# --------------------------------------------------------------------- #
# Paged KV cache (vLLM PagedAttention / Orca continuous batching, TPU form):
# KV lives in fixed-size block POOLS [n_layer, num_blocks, block_size, KV*Hd]
# shared by every in-flight request; each request owns a block table mapping
# its logical blocks to pool blocks. Memory is bounded by tokens in flight
# (not B × Smax), requests at different depths decode in one fused step, and
# retiring a request frees its blocks for the next admission.
#
# The pool has ONE representation from the engine's workspace to the
# kernel's DMA. A token's kv heads are merged into one row of KV*Hd lanes:
# with Hd 64 as its own minor dimension (half a lane tile) the device would
# store the pool transposed and convert every layer's slice on the way into
# and out of the row-major kernel. And the four forward_paged_* programs
# thread the pools as the layer scan's CARRY, viewed as
# [n_layer*num_blocks, block_size, KV*Hd] (:func:`_scan_paged_layers`), so a
# step touches the rows it reads and writes and nothing else of the pool.

def init_paged_kv_cache(cfg: TransformerConfig, num_blocks: int,
                        block_size: int, dtype=jnp.bfloat16,
                        state_slots: int = 0,
                        window_blocks: Optional[int] = None) -> Dict[str, Any]:
    """Paged pools, a dict that each kind of layer adds to. Attention
    layers: k/v [kv layers, num_blocks, block_size, kv_heads * Hd] (kv head
    ``g`` of a token at ``[g*Hd, (g+1)*Hd)`` of its row). Block 0 is
    conventionally the allocator's dummy block (padding tokens and inactive
    decode rows write there; nothing ever reads it). Recurrent layers
    (``STATE_KINDS``): ``state`` and ``conv``, each a tuple of one array a
    recurrent POSITION of the period, [periods, state_slots, *state shape]
    float32 and [periods, state_slots, *conv shape] with the kind's own
    shapes (``cfg.state_shapes``): a slot a running request and slot 0 the
    dummy; ``state`` holds None at the position of a kind that keeps a conv
    state alone (the short conv). (One array a position, not one for all:
    the period's layers are unrolled, so their offsets into a shared array
    would be constants, XLA would fold a later layer's read through the
    earlier layer's update back to the buffer as it came in, and a buffer
    read after it is written cannot be updated in place: the whole pool
    was copied in and out, twice its size a step.) Window layers: ``wk``
    and ``wv`` [window layers, ``window_blocks``, block_size, kv_heads *
    Hd]: a request holds at most R = ``cfg.ring_blocks(block_size)`` of
    those blocks in every window layer as a RING (position ``p`` in the
    block at entry ``(p // block_size) % R`` of its TABLE FROM THE HOST,
    which ``BlockAllocator`` fills as the request grows), and block 0 is
    their dummy. A request never holds more window blocks than blocks of
    the full pool, so ``min(num_blocks, rows * R + 1)`` of them cannot run
    dry (admission reads the full layers' pool alone). ``window_blocks``
    None: ``(state_slots - 1) * R + 1``, a whole ring a slot, what a
    caller sizes that addresses rings by slot (``ring_tables``).
    Latent-attention
    layers: ``c`` [latent layers, num_blocks, block_size,
    cfg.latent_pool_row], ONE row a token (no k/v pair, no head axis; its
    ``cfg.latent_row`` values, then zeros to whole lane tiles), its blocks those of the
    same tables and block 0 the same dummy; a stack whose every attention
    is latent has no ``k`` / ``v`` at all (``pool_geometry``). LEADING
    layers (``cfg.lead_kinds``) hold the first entries of each: layers
    0.. of ``k`` / ``v`` / ``wk`` / ``c``, and the first arrays of ``state``
    and ``conv``, [1, state_slots, ...] each. This is the one home of the
    shapes."""
    spec = cfg.cache_spec
    row = cfg.kv_heads * cfg.head_dim
    shape = (spec["kv"], num_blocks, block_size, row)
    pools = {}
    if spec["kv"] or not spec["latent"]:
        pools.update(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))
    if spec["latent"]:
        pools["c"] = jnp.zeros((spec["latent"], num_blocks, block_size,
                                cfg.latent_pool_row), dtype)
    if (spec["state"] or spec["window"] and window_blocks is None) \
            and state_slots < 2:
        raise ValueError("a stack with recurrent state, or window layers "
                         "whose pool is sized a ring a slot, needs "
                         "state_slots >= 2 (the dummy and one a running "
                         "request)")
    if spec["window"]:
        if window_blocks is None:
            window_blocks = (state_slots - 1) * cfg.ring_blocks(block_size) + 1
        wshape = (spec["window"], window_blocks, block_size, row)
        pools["wk"] = jnp.zeros(wshape, dtype)
        pools["wv"] = jnp.zeros(wshape, dtype)
    if spec["state"]:
        # a leading layer's arrays first, [1, slots, ...] each; a kind that
        # keeps no recurrent state (its state shape None) allocates none,
        # and its None keeps the two tuples aligned by position
        shapes = [((n, state_slots), cfg.state_shapes(k))
                  for n, kinds in ((1, cfg.lead_kinds),
                                   (cfg.n_periods, cfg.period))
                  for k in kinds if k in STATE_KINDS]
        pools["state"] = tuple(
            None if st is None else jnp.zeros(lead + st, jnp.float32)
            for lead, (st, _) in shapes)
        pools["conv"] = tuple(jnp.zeros(lead + cv, dtype)
                              for lead, (_, cv) in shapes)
    return pools


def pool_geometry(pools):
    """(num_blocks, block_size, dtype) of the block pools: the KV blocks',
    or the latent rows' where the stack keeps no KV."""
    ref = pools["k"] if "k" in pools else pools["c"]
    return ref.shape[1], ref.shape[2], ref.dtype


def _pool_scatter(pool, kv_new, slots):
    """Write per-token k or v [N, KV, Hd] into a pool [blocks, bs, KV*Hd]
    at flat slots [N] (block_id * bs + offset)."""
    Nb, bs, F = pool.shape
    flat = pool.reshape(Nb * bs, F)
    rows = kv_new.reshape(-1, F).astype(pool.dtype)
    return flat.at[slots].set(rows).reshape(pool.shape)


def _paged_gather(pool, block_tables, kv_heads: int):
    """Dense [B, max_blocks*bs, KV, Hd] gather of each request's cache via
    its block table — the einsum fallback when the paged kernel is
    off-envelope or the mesh/SPMD context forbids a bare pallas_call. Only
    the GATHERED rows are split back into heads."""
    B, F = block_tables.shape[0], pool.shape[2]
    return pool[block_tables].reshape(B, -1, kv_heads, F // kv_heads)


def ring_tables(slots, R: int):
    """WHOLE rings addressed by slot, for a caller that hands the paged
    programs slots and no table from the host (a sizing tool, a kernel's
    test; the engine's tables are the allocator's): the window layers'
    block tables [B, R] of the requests in ``slots`` [B], slot ``s``
    holding blocks ``1 + (s - 1) R .. s R``, slot 0 (an idle row) the dummy
    block in every entry."""
    slots = jnp.asarray(slots, jnp.int32).reshape(-1, 1)
    own = 1 + (slots - 1) * R + jnp.arange(R, dtype=jnp.int32)[None, :]
    return jnp.where(slots > 0, own, 0)


def _ring_kpos(last, R: int, bs: int):
    """The positions a ring's slots hold when its request's newest position
    is ``last`` [B] (-1: nothing yet): [B, R * bs], ring block ``r`` the
    newest logical block ``j <= last // bs`` with ``j % R == r``; negative
    where a slot holds nothing of the request's: no such block, or a slot
    of the newest block past ``last`` (what the block R before it left)."""
    last = jnp.asarray(last, jnp.int32).reshape(-1, 1)               # [B, 1]
    pb = last // bs
    j = pb - (pb - jnp.arange(R, dtype=jnp.int32)[None, :]) % R      # [B, R]
    kpos = (j[:, :, None] * bs
            + jnp.arange(bs, dtype=jnp.int32)).reshape(-1, R * bs)
    return jnp.where(kpos <= last, kpos, -1)


def _ring_slots(table, positions, last, bs: int):
    """Flat slots of a window layer's pool view for ``positions`` [T] of ONE
    request whose ring is ``table`` [R] and whose newest real position is
    ``last``: the last R logical blocks up to ``last`` go to the ring (no
    two of their positions share a slot), everything else (older blocks,
    which no later query can see, and a bucket's padding) to the dummy."""
    R = table.shape[0]
    blk = positions // bs
    keep = (positions <= last) & (blk > last // bs - R)
    return jnp.where(keep, table[blk % R] * bs, 0) + positions % bs


def _scan_paged_layers(cfg: TransformerConfig, params, pools, x, attn_fn,
                       mlp_fn=None, lin_fn=None, win_fn=None, lat_fn=None):
    """The ONE way the paged programs thread the pools through the layer
    stack: as the carry of a scan over the PERIODS of the layer pattern (a
    period of one attention layer where the stack has no pattern), a
    period's layers unrolled inside it, each pool viewed with its layer
    axis merged into the next (a bitcast): k and v as
    [layers*num_blocks, bs, KV*Hd], state and conv as [layers*slots, ...].
    Attention layer ``l`` lives at blocks ``[l*num_blocks, (l+1)*num_blocks)``
    of that view, so ``attn_fn(x_normed, lp_attn, kp, vp, block0, slot0)``
    reads and writes it through ``block_tables + block0`` / ``slots +
    slot0``; recurrent layer ``l`` (any kind of ``STATE_KINDS``) at rows
    ``[l*slots, (l+1)*slots)``, ``lin_fn(mixer, x_normed, lp_mixer, state,
    conv, row0)`` with ``mixer`` the kind's record; window layer ``l`` at
    blocks ``[l*ring blocks, ...)`` of ``wk`` / ``wv``, ``win_fn(x_normed,
    lp_attn, wk, wv, block0)``; latent-attention layer ``l`` at blocks
    ``[l*num_blocks, ...)`` of ``c``, ``lat_fn(x_normed, lp_attn, cp, block0,
    slot0)``. No per-layer slice of
    a pool exists and nothing is stacked. An ``mlp_fn`` may return ``(out,
    aux)``: ``aux`` (an MoE layer's per-expert counts) comes back stacked
    over the layers, else None. An ``mlp_fn`` with ``stack_keys`` (leaves of
    a layer's ``mlp`` that it reads from the stack IN PLACE, as the pools
    are read: an MoE's expert matrices under the grouped kernel) gets those
    leaves WHOLE, ``[n_periods, ...]`` each, as ``stack=(leaves, p)`` with
    ``p`` the layer's index in them; they are closed over and no slice of
    them is made (``stack_root``: the key of a layer's group they lie under
    where that is not ``mlp``; a group without it hands none). An
    ``mlp_fn`` that ``carries`` a branch across a period's sub-blocks (a
    shortcut MoE) is called ``mlp_fn(cfg, x_normed, lp, mixer_in, held,
    last)`` and returns ``(out, held)``: ``held`` is what the sub-block
    before it returned (None to the period's first), ``last`` whether this
    is the period's last. Returns (x, new pools, aux)."""
    period, lead = cfg.period, tuple(cfg.lead_kinds)
    n_att, n_win = period.count(ATTENTION), period.count(WINDOW_ATTENTION)
    n_lat = period.count(LATENT_ATTENTION)
    # layers a period and leading layers, by block pool: the leading
    # layers' entries come first in every pool
    per = {ATTENTION: (n_att, lead.count(ATTENTION)),
           WINDOW_ATTENTION: (n_win, lead.count(WINDOW_ATTENTION)),
           LATENT_ATTENTION: (n_lat, lead.count(LATENT_ATTENTION))}
    l_lin = sum(k in STATE_KINDS for k in lead)
    Nb, bs, _ = pool_geometry(pools)
    n_slots = pools["conv"][0].shape[1] if "conv" in pools else 0
    Nwb = pools["wk"].shape[1] if "wk" in pools else 0
    groups = params["layers"] if cfg.layer_kinds is not None \
        else (params["layers"],)
    stack_keys = getattr(mlp_fn, "stack_keys", ())
    carries = getattr(mlp_fn, "carries", False)
    if stack_keys:
        root = getattr(mlp_fn, "stack_root", "mlp")
        whole = [{k: g[root][k] for k in stack_keys} if root in g else None
                 for g in groups]
        groups = tuple(
            {**g, root: {k: w for k, w in g[root].items()
                         if k not in stack_keys}} if root in g else g
            for g in groups)

    def run_layer(h, flat, kind, lp, mfn, l, i, p):
        """One layer of ``kind`` on the flat pools (tuples as lists):
        ``l`` its index among the layers that share its block pool, or
        ``i`` its position in the state tuples and ``p`` its index in that
        array's layers."""
        if kind == ATTENTION:
            h, flat["k"], flat["v"] = _decode_block(
                cfg, h, lp,
                lambda xn: attn_fn(xn, lp["attn"], flat["k"], flat["v"],
                                   l * Nb, l * (Nb * bs)),
                mfn)
        elif kind == WINDOW_ATTENTION:
            h, flat["wk"], flat["wv"] = _decode_block(
                cfg, h, lp,
                lambda xn: win_fn(xn, lp["attn"], flat["wk"], flat["wv"],
                                  l * Nwb),
                mfn, scope=WINDOW_ATTENTION)
        elif kind == LATENT_ATTENTION:
            h, flat["c"] = _decode_block(
                cfg, h, lp,
                lambda xn: lat_fn(xn, lp["attn"], flat["c"], l * Nb,
                                  l * (Nb * bs)),
                mfn, scope=LATENT_ATTENTION)
        else:
            m = _mixer(kind)
            h, flat["state"][i], flat["conv"][i] = _decode_block(
                cfg, h, lp,
                lambda xn: lin_fn(m, xn, lp[m.key], flat["state"][i],
                                  flat["conv"][i], p * n_slots),
                mfn, scope=kind)
        return h

    def numbered(kinds):
        """Each of ``kinds`` with its index among those of them that share
        its pool (the state tuples are one pool for every recurrent kind)."""
        seen = collections.Counter()
        out = []
        for kind in kinds:
            pool = "state" if kind in STATE_KINDS else kind
            out.append((kind, seen[pool]))
            seen[pool] += 1
        return out

    def as_lists(flat):
        return {n: list(a) if isinstance(a, tuple) else a
                for n, a in flat.items()}

    def as_tuples(flat):
        return {n: tuple(a) if isinstance(a, list) else a
                for n, a in flat.items()}

    def run_period(carry, xs):
        h, flat = carry
        flat = as_lists(flat)
        lps, p = xs
        aux = []
        held = None
        mfn = mlp_fn
        if mlp_fn is not None:
            def mfn(c, xx, lpp, xa):
                nonlocal held
                kw = {"stack": (whole[j], p)} \
                    if stack_keys and whole[j] is not None else {}
                if carries:
                    out, held = mlp_fn(c, xx, lpp, xa, held,
                                       j == len(period) - 1, **kw)
                else:
                    out = mlp_fn(c, xx, lpp, xa, **kw)
                if isinstance(out, tuple):
                    out, a = out
                    aux.append(a)
                return out
        for j, ((kind, i), lp) in enumerate(zip(numbered(period), lps)):
            l = None
            if kind in per:
                n, first = per[kind]
                l = p * n + (first + i)
            h = run_layer(h, flat, kind, lp, mfn, l, l_lin + i, p)
        out = None if not aux else aux[0] if len(aux) == 1 else jnp.stack(aux)
        return (h, as_tuples(flat)), out

    flat = jax.tree.map(
        lambda a: a.reshape(a.shape[0] * a.shape[1], *a.shape[2:]), pools)
    if lead:
        # the leading layers, unrolled, each with its own dense MLP
        # whatever ``mlp_fn`` the periods' layers take
        flat = as_lists(flat)
        with jax.named_scope("lead"):
            for (kind, i), group in zip(numbered(lead), params["lead"]):
                x = run_layer(x, flat, kind,
                              jax.tree.map(lambda a: a[0], group), None,
                              i, i, 0)
        flat = as_tuples(flat)
    (x, flat), aux = jax.lax.scan(
        run_period, (x, flat),
        (groups, jnp.arange(cfg.n_periods, dtype=jnp.int32)))
    if aux is not None and aux.ndim > 2 and len(period) > 1:
        # a period's layers that gave one each, stacked: one row a layer
        aux = aux.reshape(-1, *aux.shape[2:])
    return x, jax.tree.map(lambda a, b: a.reshape(b.shape), flat, pools), aux


def paged_real_rows(pools, slots):
    """Which positions of a paged step are real: padding (a prompt bucket's
    tail, an inactive decode or verify row) is what the engine routes to the
    dummy block 0, so a flat slot under ``block_size`` is padding. What an
    MoE MLP keeps away from its experts."""
    return slots >= pool_geometry(pools)[1]


def _paged_decode_attention(cfg: TransformerConfig, x, lp, positions, pos,
                            kp, vp, block_tables, pad_bias, window: int = 0,
                            dummy_block=0):
    """One fused decode step over all running requests against the paged
    pools: x [B, 1, D] (one new token per request), pos [B] per-request
    cache depths, kp/vp [blocks, bs, KV*Hd], block_tables [B, max_blocks]
    (``dummy_block``: what an inactive row's entries name, the layer's first
    block of the stacked pools; the kernel copies nothing for such a row).
    ``window`` > 0 (a window layer): the tables are the requests' RINGS, as
    the host handed them (logical block ``j`` at entry ``j % width``; an
    entry not handed out yet names the dummy block and lies past ``pos``),
    and a row reads positions ``pos - window < j <= pos`` only.
    Returns (out [B, 1, D], new kp, vp)."""
    B, T, D = x.shape
    H = cfg.n_head
    bs = kp.shape[1]

    q, k, v = _qkv_project(cfg, x, lp, positions,
                           WINDOW_ATTENTION if window else ATTENTION)

    # each request's new k/v lands at its block-table slot; inactive rows
    # carry a zeroed table and write into the dummy block. Their attention
    # is the kernel's zeros (it copies nothing for them) or, from the gather
    # + einsum form, the dummy block's first value: nothing reads either
    # (``paged_real_rows`` keeps them from the experts, the sampler's pick
    # for them is dropped)
    rows = jnp.arange(B)
    blk = pos // bs
    if window:
        blk = blk % block_tables.shape[1]
    slots = block_tables[rows, blk] * bs + pos % bs
    kp = _pool_scatter(kp, k[:, 0], slots)
    vp = _pool_scatter(vp, v[:, 0], slots)

    slopes = _alibi_slopes(H) if cfg.pos_embedding == "alibi" else None
    band = {"window": window} if window else {}
    o = None
    if _use_flash(cfg):
        from deepspeed_tpu.ops.pallas.paged_decode_attention import \
            paged_decode_attention
        o = paged_decode_attention(q[:, 0], kp, vp, block_tables, pos,
                                   pad_bias=pad_bias, alibi_slopes=slopes,
                                   scale=cfg.attn_scale,
                                   dummy_block=dummy_block, **band)
        form = "paged_kernel"
    elif not window:
        # SPMD mesh (a bare pallas_call is illegal): shard_map the kernel
        # over the KV-head/tp axis — the head-sharded pool's shards each
        # stream their local heads, tables stay replicated
        pmesh = _flash_mesh(cfg)
        if pmesh is not None:
            o = _paged_decode_sharded(q[:, 0], kp, vp, block_tables, pos,
                                      pad_bias, slopes, pmesh,
                                      scale=cfg.attn_scale,
                                      dummy_block=dummy_block)
            form = "paged_kernel_sharded"
    if o is not None:
        out = o.reshape(B, 1, H * cfg.head_dim)
    else:
        form = "gather_einsum"
        # gather + grouped einsum (the dense cache path's masked-softmax
        # core with per-request qpos) — partitionable, the CPU tier default
        if window:
            band["kpos"] = _ring_kpos(pos, block_tables.shape[1], bs)
        out = _grouped_cache_einsum(
            cfg, q, _paged_gather(kp, block_tables, cfg.kv_heads),
            _paged_gather(vp, block_tables, cfg.kv_heads), positions, pad_bias,
            **band)
    dispatch.record("paged_window_decode" if window else "paged_decode", form,
                    f"B={B} H={H} KV={cfg.kv_heads} Hd={cfg.head_dim} bs={bs}")
    out = _attn_out(cfg, out, x, lp)
    return out, kp, vp


def _paged_prefill_attention(cfg: TransformerConfig, x, lp, positions,
                             kp, vp, slots, window: int = 0):
    """Prefill attention of ONE fresh request: causal self-attention over
    its own prompt (a fresh request has no prior context to read), with the
    prompt's k/v scattered into the request's pool blocks. x [1, T, D];
    slots [T] flat pool slots (pad positions routed to the dummy block).
    ``window`` > 0 (a window layer): the attention is the band over the
    fresh k/v, and ``slots`` keep what the request's ring keeps."""
    B, T, D = x.shape
    H, Hd = cfg.n_head, cfg.head_dim

    q, k, v = _qkv_project(cfg, x, lp, positions,
                           WINDOW_ATTENTION if window else ATTENTION)

    kp = _pool_scatter(kp, k, slots)
    vp = _pool_scatter(vp, v, slots)

    slopes = _alibi_slopes(H) if cfg.pos_embedding == "alibi" else None
    mask = {} if cfg.causal_block == 1 else {"causal_block": cfg.causal_block}
    if window:
        mask["window"] = window
    out = None
    if _use_flash(cfg):
        from deepspeed_tpu.ops.pallas import flash_attention
        out = flash_attention(q, k, v, causal=True, alibi_slopes=slopes,
                              scale=cfg.attn_scale, block_q=cfg.attn_block_q,
                              block_k=cfg.attn_block_k, **mask)
        form = "flash"
    if out is None:
        from deepspeed_tpu.ops.attention import mha_attention
        out = mha_attention(q, k, v, causal=True, alibi_slopes=slopes,
                            scale=cfg.attn_scale, **mask)
        form = "einsum"
    dispatch.record("paged_window_prefill" if window else "paged_prefill",
                    form, f"T={T}")
    out = out.reshape(B, T, H * Hd)
    out = _attn_out(cfg, out, x, lp)
    return out, kp, vp


def _paged_chunk_attention(cfg: TransformerConfig, x, lp, positions,
                           kp, vp, block_tables, slots):
    """Prefill-chunk attention of ONE request that already has cached
    context: the chunk's k/v are scattered into the request's pool blocks
    at ``slots``, then its queries attend causally over EVERYTHING the
    request has cached — the prefix-cache hit / earlier chunks PLUS this
    chunk — via the paged gather path and the shared masked-softmax core
    (``_grouped_cache_einsum`` with per-row query positions; the same
    machinery the off-kernel paged decode uses, so numerics match it).
    x [1, T, D] (T the chunk bucket, pads routed to the dummy block);
    positions [1, T] global positions ``start + arange(T)``."""
    KV = cfg.kv_heads
    q, k, v = _qkv_project(cfg, x, lp, positions)

    kp = _pool_scatter(kp, k, slots)
    vp = _pool_scatter(vp, v, slots)

    # gather the request's whole block table (static width) and let the
    # causal mask (kpos <= qpos) hide everything beyond the chunk's last
    # real token — unwritten tail blocks and dummy-mapped table slots all
    # sit at higher logical positions than any live query
    out = _grouped_cache_einsum(cfg, q, _paged_gather(kp, block_tables, KV),
                                _paged_gather(vp, block_tables, KV),
                                positions, None)
    out = _attn_out(cfg, out, x, lp)
    return out, kp, vp


def _paged_path_only(cfg: TransformerConfig, what: str):
    """What only the paged serving path builds: a sandwich norm, a layer
    pattern, leading layers, and the embedding, residual and logit
    multipliers."""
    if cfg.norm_position == "sandwich":
        raise NotImplementedError(
            f"{what} is not built for norm_position='sandwich' (a norm on "
            "each branch's way out, ln_attn_post / ln_mlp_post): it runs on "
            "the paged serving path only")
    if cfg.layer_kinds is not None or cfg.lead_kinds:
        raise NotImplementedError(
            f"{what} is not built for a stack with a layer pattern "
            f"({tuple(cfg.lead_kinds)} + {cfg.period}): it runs on the "
            "paged serving path only")
    if (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling) != (1.0, 1.0, 1.0):
        raise NotImplementedError(
            f"{what} does not apply embedding_multiplier, "
            "residual_multiplier or logits_scaling: a model that sets one "
            "runs on the paged serving path only")


def _check_paged_config(cfg: TransformerConfig):
    if cfg.norm_position == "post" or not cfg.causal:
        raise ValueError("the paged KV path serves pre-LN (or sandwich-"
                         "normed) causal LMs only")
    if cfg.sparse_attention is not None:
        raise NotImplementedError(
            "sparse_attention is not supported by the paged KV decode path")


def _lat_fn(pools, fn):
    """The ``lat_fn`` of a paged program (None for a stack without latent
    rows): ``fn`` with ``models/latent_attention.py`` as its first argument
    (imported here and not above: that module imports this one)."""
    if "c" not in pools:
        return None
    from deepspeed_tpu.models import latent_attention
    return lambda *a: fn(latent_attention, *a)


def _no_latent_form(pools, what: str):
    """What no form reads a latent row for yet."""
    if "c" in pools:
        raise NotImplementedError(
            f"{what} is not built for latent rows (cache_spec['latent']): "
            "whole-prompt prefill and decode alone read them")


def _lin_prefill_fn(cfg: TransformerConfig, pools, state_slot, n_valid, fresh):
    """The ``lin_fn`` of a prefill or chunk program (None for a stack
    without state): the request's slot in each recurrent layer, through the
    layer's kind's own chunked form."""
    if "state" not in pools:
        return None
    if state_slot is None:
        raise ValueError("a stack with recurrent state needs the request's "
                         "state slot")
    return lambda mixer, xn, lp, st, cv, row0: mixer.prefill(
        cfg, xn, lp, st, cv, row0 + state_slot, n_valid, fresh)


def _win_prefill_fn(cfg: TransformerConfig, pools, state_slot, positions,
                    last_idx, window_table=None):
    """The ``win_fn`` of a prefill program (None for a stack without window
    layers): the request's ring ``window_table`` [R] from the host (an
    entry not handed out names the dummy block; absent: the whole ring of
    ``state_slot``), and the slots of it that the prompt's positions
    keep."""
    if "wk" not in pools:
        return None
    bs = pools["wk"].shape[2]
    if window_table is not None:
        table = jnp.asarray(window_table, jnp.int32).reshape(-1)
    elif state_slot is None:
        raise ValueError("a stack with window layers needs the request's "
                         "window table (or its slot, for a whole ring)")
    else:
        table = ring_tables(state_slot, cfg.ring_blocks(bs))[0]
    slots = _ring_slots(table, positions[0], positions[0, last_idx], bs)
    return lambda xn, lp, kp, vp, block0: _paged_prefill_attention(
        cfg, xn, lp, positions, kp, vp, slots + block0 * bs,
        window=cfg.attn_window)


def forward_paged_prefill(cfg: TransformerConfig, params, tokens, pools,
                          slots, last_idx, mlp_fn=None, state_slot=None,
                          window_table=None):
    """Prefill ONE admitted request into its allocated blocks.

    tokens [1, T] right-padded prompt (T the compile bucket); slots [T]
    flat pool slots per prompt position (block_table[t // bs] * bs + t % bs,
    pads routed to the dummy block); last_idx [] int32 index of the last
    real prompt token. Returns (logits [1, vocab] at last_idx, new pools) —
    junk pad positions are causally invisible to the sampled position.
    ``state_slot`` [] int32 (a stack with recurrent state): the request's
    slot, whose state a whole prefill starts from zero. ``window_table``
    [R] int32 (a stack with window layers): the request's blocks of the
    window pool, which the prefill fills with the prompt's last blocks
    (absent: the whole ring of ``state_slot``)."""
    _check_paged_config(cfg)
    x, positions = cached_embed(cfg, params, tokens, jnp.int32(0),
                                pool_geometry(pools)[2])

    x, pools, _ = _scan_paged_layers(
        cfg, params, pools, x,
        lambda xn, lp, kp, vp, block0, slot0: _paged_prefill_attention(
            cfg, xn, lp, positions, kp, vp, slots + slot0),
        mlp_fn, _lin_prefill_fn(cfg, pools, state_slot, last_idx + 1, True),
        _win_prefill_fn(cfg, pools, state_slot, positions, last_idx,
                        window_table),
        _lat_fn(pools, lambda la, xn, lp, cp, block0, slot0: la.prefill(
            cfg, xn, lp, positions, cp, slots + slot0)))
    # head on the sampled position only: the [1, vocab] projection, not
    # the whole bucket's [T, vocab]
    xl = jax.lax.dynamic_slice_in_dim(x, last_idx, 1, axis=1)
    return cached_head(cfg, params, xl)[:, 0, :], pools


def forward_paged_prefill_chunk(cfg: TransformerConfig, params, tokens,
                                pools, block_tables, slots, start_pos,
                                last_idx, mlp_fn=None, state_slot=None):
    """Prefill ONE CHUNK of a request that already has ``start_pos`` tokens
    cached in its blocks (a prefix-cache hit, or earlier chunks of a
    Sarathi-style chunked prefill).

    tokens [1, T] the chunk, right-padded to the compile bucket;
    block_tables [1, max_blocks] the request's table (unused entries 0 =
    dummy); slots [T] flat pool slots per chunk position
    (block_table[(start+t) // bs] * bs + (start+t) % bs, pads routed to the
    dummy block); start_pos [] int32 tokens already cached; last_idx []
    int32 index WITHIN the chunk of its last real token. Returns
    (logits [1, vocab] at last_idx, new pools) — intermediate chunks
    discard the logits, the final chunk samples from them. ``state_slot``
    [] int32 (a stack with recurrent state): the request's slot, zeroed by
    the chunk that starts at position 0 and carried over the later ones.
    A stack with window layers is refused: a chunk longer than a block
    would write over ring positions its first queries still read, and no
    form reads the ring beside the chunk's own k/v (the engine refuses
    ``prefill_chunk_tokens`` from ``cache_spec``)."""
    _check_paged_config(cfg)
    if "wk" in pools:
        raise NotImplementedError(
            "a prefill chunk is not built for a stack with window layers: "
            "their rings take whole prompts only")
    _no_latent_form(pools, "a prefill chunk")
    x, positions = cached_embed(cfg, params, tokens, start_pos,
                                pools["k"].dtype)

    x, pools, _ = _scan_paged_layers(
        cfg, params, pools, x,
        lambda xn, lp, kp, vp, block0, slot0: _paged_chunk_attention(
            cfg, xn, lp, positions, kp, vp, block_tables + block0,
            slots + slot0),
        mlp_fn, _lin_prefill_fn(cfg, pools, state_slot, last_idx + 1,
                                start_pos == 0))
    xl = jax.lax.dynamic_slice_in_dim(x, last_idx, 1, axis=1)
    return cached_head(cfg, params, xl)[:, 0, :], pools


def copy_paged_block(pools, src, dst):
    """Device copy of one pool block across every layer (the scheduler's
    copy-on-write split: a request restarting mid-block inside a SHARED
    block gets a private copy before it writes). src/dst [] int32."""
    return {**pools, **{n: pools[n].at[:, dst].set(pools[n][:, src])
                        for n in ("k", "v", "c") if n in pools}}


def _paged_verify_attention(cfg: TransformerConfig, x, lp, positions,
                            kp, vp, block_tables, slots, dummy_block=0):
    """Verify attention over ALL running requests at once: each row's
    speculation window (its pending last token + proposed candidates) has
    its k/v scattered into the row's pool blocks at ``slots`` ([B, W] flat
    slots, pads and inactive rows routed to the dummy block), then every
    window query attends causally over the row's whole table with per-row
    position WINDOWS ``positions[b, t] = pos_b + t``.

    Token-identity with plain decode requires the SAME attention
    implementation the decode step dispatches to — an argmax near-tie
    resolved differently between two numerically-equivalent kernels would
    flip an accepted token. So where the decode step takes the Pallas
    paged kernel, verify runs the kernel once per window position
    (scatter position t, query position t — exactly the t sequential
    decode steps it replaces, still one compiled program); everywhere
    else both use the gather + grouped-einsum masked-softmax core (W = 1
    degenerates to the off-kernel decode exactly)."""
    B, W, D = x.shape
    H, KV, Hd = cfg.n_head, cfg.kv_heads, cfg.head_dim

    q, k, v = _qkv_project(cfg, x, lp, positions)

    # kernel dispatch mirrors the decode step's exactly (direct where a
    # bare pallas_call is legal, shard_map over the KV-head axis on SPMD
    # meshes) — token identity demands verify resolve argmax near-ties
    # with the SAME implementation decode would have used
    direct = _use_flash(cfg)
    pmesh = None
    if not direct:
        pmesh = _flash_mesh(cfg)
        if pmesh is not None and not _paged_shard_ok(
                pmesh, H, KV, Hd, kp.shape[1]):
            pmesh = None
    if direct or pmesh is not None:
        from deepspeed_tpu.ops.pallas.paged_decode_attention import \
            paged_decode_attention
        slopes = _alibi_slopes(H) if cfg.pos_embedding == "alibi" else None
        outs = []
        for t in range(W):
            kp = _pool_scatter(kp, k[:, t], slots[:, t])
            vp = _pool_scatter(vp, v[:, t], slots[:, t])
            if direct:
                o = paged_decode_attention(q[:, t], kp, vp, block_tables,
                                           positions[:, t],
                                           alibi_slopes=slopes,
                                           scale=cfg.attn_scale,
                                           dummy_block=dummy_block)
            else:
                o = _paged_decode_sharded(q[:, t], kp, vp, block_tables,
                                          positions[:, t], None, slopes,
                                          pmesh, scale=cfg.attn_scale,
                                          dummy_block=dummy_block)
            if o is None:
                break          # off-envelope: the einsum core below
            outs.append(o)
        if len(outs) == W:
            out = jnp.stack(outs, axis=1).reshape(B, W, H * Hd)
            out = _attn_out(cfg, out, x, lp)
            return out, kp, vp

    # re-scattering already-written positions is idempotent (same values
    # to the same slots), so the off-envelope break above lands here clean
    kp = _pool_scatter(kp, k, slots.reshape(-1))
    vp = _pool_scatter(vp, v, slots.reshape(-1))

    # causal mask (kpos <= qpos) bounds each window query at its own
    # position: candidate t sees the cached context plus window tokens
    # <= t, junk pad queries see junk but nothing reads their logits
    out = _grouped_cache_einsum(cfg, q, _paged_gather(kp, block_tables, KV),
                                _paged_gather(vp, block_tables, KV),
                                positions, None)
    out = _attn_out(cfg, out, x, lp)
    return out, kp, vp


def forward_paged_verify(cfg: TransformerConfig, params, tokens, pools,
                         block_tables, slots, pos, mlp_fn=None):
    """One fused VERIFY step of speculative decoding over all running
    requests: the paged-decode math over ``W = k + 1`` positions per
    request in one program.

    tokens [B, W] — row b is its pending last sampled token followed by
    its proposed candidate continuation, right-padded to the window
    bucket; slots [B, W] flat pool slots per window position
    (block_table[(pos+t) // bs] * bs + (pos+t) % bs, pads and inactive
    rows routed to the dummy block); pos [B] per-request cache depths.
    Returns (logits [B, W, vocab] at EVERY window position, new pools).

    Greedy acceptance is host-side: argmax at window offset t is the
    token plain greedy decode would emit after candidates 1..t, so the
    longest candidate prefix matched plus the first-mismatch token is
    token-identical to t+1 sequential decode steps. Rejected candidates'
    k/v stay in the pools beyond the committed position — never read
    (attention masks at each row's pos) and overwritten as decode
    advances; the scheduler handles pos rewind + prefix-cache rollback."""
    _check_paged_config(cfg)
    if cfg.cache_spec["state"] or cfg.cache_spec["window"]:
        raise NotImplementedError(
            "a verify window rewinds to the last accepted position: a "
            "recurrent state cannot be rewound (no snapshot is kept), and a "
            "window layer's ring has written over what the rewind would read")
    _no_latent_form(pools, "a verify window")
    x, positions = cached_embed(cfg, params, tokens, pos, pools["k"].dtype)

    x, pools, _ = _scan_paged_layers(
        cfg, params, pools, x,
        lambda xn, lp, kp, vp, block0, slot0: _paged_verify_attention(
            cfg, xn, lp, positions, kp, vp, block_tables + block0,
            slots + slot0, dummy_block=block0),
        mlp_fn)
    return cached_head(cfg, params, x), pools


def forward_paged_decode(cfg: TransformerConfig, params, tokens, pools,
                         block_tables, pos, pad_bias=None, mlp_fn=None,
                         state_slots=None, window_tables=None):
    """One fused decode step over ALL running requests: tokens [B, 1] (each
    request's last sampled token), block_tables [B, max_blocks], pos [B]
    per-request cache depths. Returns (logits [B, vocab], new pools), and
    third what an ``mlp_fn`` that returns ``(out, aux)`` gave, stacked over
    the layers (an MoE model's [L, E + 1] assignment counts).
    ``state_slots`` [B] int32 (a stack with recurrent state): each row's
    slot, 0 (the dummy) for an inactive row. ``window_tables`` [B, R] int32
    (a stack with window layers): each row's ring of window-pool blocks
    from the host, logical block ``j`` at entry ``j % R``, the dummy block
    where none is handed out yet (absent: the whole ring of each row's
    slot)."""
    _check_paged_config(cfg)
    x, positions = cached_embed(cfg, params, tokens, pos,
                                pool_geometry(pools)[2])
    if "c" in pools and pad_bias is not None:
        _no_latent_form(pools, "a key-side pad bias")
    lin_fn = win_fn = None
    if state_slots is None and (
            "state" in pools or "wk" in pools and window_tables is None):
        raise ValueError("a stack with recurrent state needs the rows' "
                         "slots, one with window layers their window "
                         "tables (or their slots, for whole rings)")
    if "state" in pools:
        lin_fn = lambda mixer, xn, lp, st, cv, row0: mixer.decode(  # noqa: E731
            cfg, xn, lp, st, cv, row0, state_slots)
    if "wk" in pools:
        rings = jnp.asarray(window_tables, jnp.int32) \
            if window_tables is not None else ring_tables(
                state_slots, cfg.ring_blocks(pools["wk"].shape[2]))
        win_fn = lambda xn, lp, kp, vp, block0: _paged_decode_attention(  # noqa: E731
            cfg, xn, lp, positions, pos, kp, vp, rings + block0, None,
            window=cfg.attn_window, dummy_block=block0)

    # the decode step derives its write slots from the (layer-offset) table
    x, pools, aux = _scan_paged_layers(
        cfg, params, pools, x,
        lambda xn, lp, kp, vp, block0, slot0: _paged_decode_attention(
            cfg, xn, lp, positions, pos, kp, vp, block_tables + block0,
            pad_bias, dummy_block=block0),
        mlp_fn, lin_fn, win_fn,
        _lat_fn(pools, lambda la, xn, lp, cp, block0, slot0: la.decode(
            cfg, xn, lp, positions, pos, cp, block_tables + block0)))
    logits = cached_head(cfg, params, x)[:, 0, :]
    return (logits, pools) if aux is None else (logits, pools, aux)


def _paged_block_attention(cfg: TransformerConfig, x, lp, positions, pos,
                           kp, vp, block_tables, dummy_block=0):
    """Attention of one block-generation pass over all its entries: x [W,
    Bg, D], the ``Bg = cfg.causal_block`` positions ``pos .. pos + Bg - 1``
    of each ENTRY's block (``pos`` a multiple of Bg, so a block never
    straddles a pool block). An entry is a block of some row: its open
    block, or (a rider) the whole block before it, under the same block
    table at ``pos - Bg``. EVERY entry's k/v go to its pool slots FIRST (a
    later pass over the same block, or its commit, overwrites them: a
    denoise pass's KV is not kept by being written over), then an entry's
    Bg queries read positions ``<= pos + Bg - 1``: everything before the
    block and the block itself, both directions. So a rider's final k/v is
    in the pools before its row's open block reads it, and the rider never
    sees the open block: one launch is the commit pass followed by the
    denoise pass. The Bg queries of an entry see the SAME keys, so they
    ride the paged kernel on its position axis (Bg x H / KV query rows a kv
    head), in ONE read of the entry's KV.
    Returns (out [W, Bg, D], kp, vp)."""
    W, Bg, D = x.shape
    H, KV, Hd = cfg.n_head, cfg.kv_heads, cfg.head_dim
    bs = kp.shape[1]

    q, k, v = _qkv_project(cfg, x, lp, positions)

    slots = (block_tables[jnp.arange(W), pos // bs] * bs + pos % bs)[:, None] \
        + jnp.arange(Bg, dtype=jnp.int32)
    kp = _pool_scatter(kp, k, slots.reshape(-1))
    vp = _pool_scatter(vp, v, slots.reshape(-1))

    o = None
    if _use_flash(cfg):
        from deepspeed_tpu.ops.pallas.paged_decode_attention import \
            paged_decode_attention
        # the block's positions are the kernel's own axis: one read of the
        # row's KV for all Bg of them
        o = paged_decode_attention(q, kp, vp, block_tables, pos + (Bg - 1),
                                   scale=cfg.attn_scale,
                                   dummy_block=dummy_block)
    if o is not None:
        form = "paged_kernel"
        out = o.reshape(W, Bg, H * Hd)
    else:
        form = "gather_einsum"
        # the shared masked-softmax core under the model's block mask: a
        # query's own block ends at pos + Bg - 1
        out = _grouped_cache_einsum(
            cfg, q, _paged_gather(kp, block_tables, KV),
            _paged_gather(vp, block_tables, KV), positions, None)
    dispatch.record("paged_block", form,
                    f"W={W} Bg={Bg} H={H} KV={KV} Hd={Hd} bs={bs}")
    out = _attn_out(cfg, out, x, lp)
    return out, kp, vp


def forward_paged_block(cfg: TransformerConfig, params, tokens, pools,
                        block_tables, pos, mlp_fn=None, n_logits=None):
    """One pass of block generation over ALL its entries (a model with a
    ``generation`` record): tokens [N, Bg], each entry's block with its
    undecided positions as the mask id; block_tables [N, max_blocks]; pos
    [N] the depths the entries' blocks start at (multiples of Bg; an idle
    entry has a zeroed table and writes the dummy block). An entry is a
    row's open block or, behind those, a rider: the whole block of a row
    whose open block is the next one, under the same table (the serving
    session's layout: ``inference/blockgen.py``). ``n_logits``: the head
    runs over the first ``n_logits`` entries alone (riders decide nothing;
    None: all). Returns (logits [n_logits, Bg, vocab] at EVERY position of
    those blocks, each position's own; new pools) and third what an
    ``mlp_fn`` that returns ``(out, aux)`` gave. A denoise pass, a commit
    and a rider are this same program: what differs is what the caller
    does with the logits and whether ``pos`` moves on."""
    _check_paged_config(cfg)
    if cfg.generation is None or cfg.cache_spec["state"] \
            or cfg.cache_spec["window"]:
        raise NotImplementedError(
            "a block pass needs a model that generates by blocks "
            "(cfg.generation) and keeps no recurrent state and no window")
    _no_latent_form(pools, "a block pass")
    if pools["k"].shape[2] % cfg.causal_block:
        raise ValueError("a pool block must hold whole generation blocks")
    x, positions = cached_embed(cfg, params, tokens, pos, pools["k"].dtype)
    x, pools, aux = _scan_paged_layers(
        cfg, params, pools, x,
        lambda xn, lp, kp, vp, block0, slot0: _paged_block_attention(
            cfg, xn, lp, positions, pos, kp, vp, block_tables + block0,
            dummy_block=block0),
        mlp_fn)
    logits = cached_head(cfg, params, x if n_logits is None else x[:n_logits])
    return (logits, pools) if aux is None else (logits, pools, aux)


def run_layers(cfg: TransformerConfig, x, layer_params, positions, mask_bias,
               rng=None):
    """Run the stacked layer blocks over ``x`` with the config's remat policy
    and scan/unroll choice — shared by :func:`hidden_states` and non-token
    encoders (e.g. the CLIP vision tower). ``rng`` (training loss paths
    only) seeds per-layer dropout keys; None keeps every path deterministic
    and the traced program identical to the dropout-free form."""
    _paged_path_only(cfg, "the training forward (and its scan's backward)")
    with_keys = rng is not None and bool(cfg.dropout)
    n_layer = jax.tree.leaves(layer_params)[0].shape[0]

    def run_block(h, xs):
        lp, key = xs if with_keys else (xs, None)
        out = block(cfg, h, lp, positions, mask_bias, rng=key)
        return out, None

    if cfg.remat and cfg.remat != "none":
        run_block = jax.checkpoint(run_block, policy=_remat_policy(cfg.remat),
                                   prevent_cse=False)

    xs = (layer_params, jax.random.split(rng, n_layer)) if with_keys else layer_params
    if cfg.scan_layers:
        x, _ = jax.lax.scan(run_block, x, xs)
    else:
        for i in range(n_layer):
            x, _ = run_block(x, jax.tree.map(lambda a: a[i], xs))
    return x


def hidden_states(cfg: TransformerConfig, params, tokens, attn_mask=None,
                  rng=None):
    """tokens [B, S] int32 → final normed hidden states [B, S, D] (the
    forward body without the vocab projection). ``rng`` enables dropout
    (training loss paths); None — the default for forward/inference —
    is deterministic."""
    if cfg.norm_position == "post":
        # post-LN stacks end inside the last block and have no ln_f; the
        # LM paths here are pre-LN only — build on run_layers directly
        # (see models/bert.py) instead of silently mixing the two schemes
        raise ValueError("norm_position='post' is not supported by the LM "
                         "forward paths; use run_layers (e.g. BertModel)")
    B, S = tokens.shape
    x = params["embed"]["tokens"][tokens]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
    if cfg.pos_embedding == "learned":
        x = x + params["embed"]["positions"][:S][None, :, :]
    if cfg.embed_layernorm:
        x = _norm(cfg, x, params["embed"]["ln"])
    k_embed = k_layers = None
    if rng is not None and cfg.dropout:
        k_embed, k_layers = jax.random.split(rng)
    x = _dropout(cfg, x, k_embed)

    x = run_layers(cfg, x, params["layers"], positions, key_mask_bias(attn_mask),
                   rng=k_layers)
    return _norm(cfg, x, params["ln_f"])


def _head_weight(cfg: TransformerConfig, params):
    """[D, vocab] projection (tied embedding transpose or lm_head)."""
    if cfg.tie_embeddings:
        return params["embed"]["tokens"].T
    return _w(params["lm_head"], params["embed"]["tokens"])


def _head_bias(params):
    """Optional [vocab] logits bias (GPT-J's lm_head carries one)."""
    return params.get("lm_head_bias", 0)


def _token_ce(logits, labels, valid):
    """Per-token nll and valid count from [N, V] f32 logits."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum((lse - gold) * valid), jnp.sum(valid)


def chunked_vocab_ce(h, w, hb, safe_labels, valid, chunk: int):
    """Mean token cross-entropy for a vocab head ``h @ w + hb`` from
    [B, S, D] features. With ``chunk > 0`` dividing B*S, the projection +
    CE stream over token chunks inside a rematerialised scan, so the
    [B, S, vocab] fp32 logits are never materialised — shared by the
    causal ``lm_loss`` and the BERT MLM loss."""
    B, S, D = h.shape
    vf = valid.astype(jnp.float32)
    if chunk <= 0 or (B * S) % chunk != 0:
        logits = (h @ w + hb).astype(jnp.float32)
        nll, n = _token_ce(logits.reshape(B * S, -1),
                           safe_labels.reshape(-1), vf.reshape(-1))
        return nll / jnp.maximum(n, 1)

    nc = (B * S) // chunk
    hf = h.reshape(nc, chunk, D)
    lf = safe_labels.reshape(nc, chunk)
    vff = vf.reshape(nc, chunk)

    def body(carry, inp):
        hc, lc, vc = inp
        logits = (hc @ w + hb).astype(jnp.float32)
        nll, n = _token_ce(logits, lc, vc)
        s_nll, s_n = carry
        return (s_nll + nll, s_n + n), None

    # full remat: the chunk logits are recomputed in backward, never stored
    body = jax.checkpoint(body, prevent_cse=False)
    (nll, n), _ = jax.lax.scan(body, (jnp.float32(0), jnp.float32(0)),
                               (hf, lf, vff))
    return nll / jnp.maximum(n, 1)


def _use_fused_ce(cfg) -> bool:
    """Whether the vocab head should run the fused logits-free Pallas CE
    kernel. ``cfg`` is any config carrying ``fused_cross_entropy`` (the zoo's
    TransformerConfig or BertConfig). "auto" mirrors the flash-attention
    dispatch: TPU only, and only where a bare ``pallas_call`` is legal —
    single-device meshes or a fully-manual shard_map context; multi-device
    SPMD land falls back to the partitionable XLA streaming path."""
    mode = getattr(cfg, "fused_cross_entropy", "auto")
    if mode == "off":
        return False
    if mode == "on":
        return True
    if mode != "auto":
        raise ValueError(f"fused_cross_entropy={mode!r} (expected "
                         "'auto', 'on' or 'off')")
    return dispatch.on_tpu() and _bare_pallas_legal()


@jax.named_scope("vocab_head")
def vocab_head_ce(cfg, h, w, hb, safe_labels, valid):
    """Mean token CE for a vocab head ``h @ w + hb`` — the single dispatch
    every zoo loss head goes through. With ``cfg.fused_cross_entropy``
    selecting the kernel (see :func:`_use_fused_ce`), the fused logits-free
    Pallas CE runs the projection + loss without ever materialising the
    [tokens, vocab] logits in ANY precision; otherwise the XLA
    :func:`chunked_vocab_ce` streaming path (``cfg.loss_chunk``) applies."""
    if _use_fused_ce(cfg):
        from deepspeed_tpu.ops.pallas.fused_cross_entropy import (
            fused_cross_entropy)
        bias = None if isinstance(hb, (int, float)) else hb
        dispatch.record("vocab_head", "fused_ce",
                        f"D={h.shape[-1]} V={w.shape[-1]}")
        return fused_cross_entropy(h, w, safe_labels, bias=bias, valid=valid)
    chunk = getattr(cfg, "loss_chunk", 0)
    dispatch.record("vocab_head", "loss_chunk", f"chunk={chunk}")
    return chunked_vocab_ce(h, w, hb, safe_labels, valid, chunk)


def lm_loss(cfg: TransformerConfig, params, batch, rng=None,
            ignore_index: int = -100):
    """Next-token cross-entropy. batch: dict(input_ids[B,S], optional
    labels[B,S], optional attention_mask[B,S]).

    The vocab head goes through :func:`vocab_head_ce`: by default the fused
    logits-free Pallas CE kernel on TPU (the analogue of the reference's
    fused softmax-xent kernels — HBM traffic O(B·S·D) instead of O(B·S·V)),
    else the ``cfg.loss_chunk`` XLA streaming scan."""
    tokens = batch["input_ids"]
    labels = batch.get("labels")
    if labels is None:
        labels = jnp.concatenate([tokens[:, 1:], jnp.full_like(tokens[:, :1], ignore_index)], axis=1)
    x = hidden_states(cfg, params, tokens, batch.get("attention_mask"), rng=rng)
    w = _head_weight(cfg, params)
    B, S, D = x.shape

    valid = (labels != ignore_index)
    safe_labels = jnp.where(valid, labels, 0)

    hb = _head_bias(params)
    return vocab_head_ce(cfg, x, w, hb, safe_labels, valid)
