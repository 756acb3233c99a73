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

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.ops import dispatch


ATTENTION, LINEAR_ATTENTION = "attention", "linear_attention"


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
    norm_position: str = "pre"           # pre (GPT) | post (BERT add&norm)
    causal: bool = True
    tie_embeddings: bool = True
    embed_layernorm: bool = False        # BLOOM word_embeddings_layernorm
    attn_bias: bool = False              # qkv/out biases (gpt2/opt/bloom/neox)
    # RMSNorm of the query and key PROJECTIONS, each over its whole width
    # (all heads together, one learned scale of H*Hd / KV*Hd), before the
    # split into heads and before rope (OLMoE, OLMo-2)
    qk_norm: bool = False
    # a head's width where it is not d_model // n_head (64 heads of 128
    # under d_model 4,096): ``head_dim`` reads it
    head_size: Optional[int] = None
    # sigmoid output gate on attention: y = (a * sigmoid(x Wg)) Wo, Wg
    # [d_model, n_head * head_dim], no bias
    attn_out_gate: bool = False
    # ONE period of the stack's layer pattern, the kinds of its mixers in
    # order ("attention": softmax attention over the KV cache;
    # "linear_attention": the gated delta rule with a recurrent state, the
    # lin_* sizes below); the stack is n_layer / len(layer_kinds) periods.
    # None: every layer is "attention". A kind says what cache it keeps
    # (``cache_spec``): KV blocks, or a state slot a request.
    layer_kinds: Optional[Tuple[str, ...]] = None
    # the linear-attention (KDA) mixer: heads and their key = value width
    # (its two factored gate projections have rank lin_head_dim)
    lin_heads: int = 0
    lin_head_dim: int = 0
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
    # CheckpointFunction semantics); "dots" = save matmul outputs, recompute
    # the cheap elementwise/attention parts (best MFU when it fits HBM);
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

    @property
    def head_dim(self) -> int:
        return self.head_size or self.d_model // self.n_head

    @property
    def period(self) -> Tuple[str, ...]:
        """The kinds of one period of the layer pattern."""
        return tuple(self.layer_kinds) if self.layer_kinds else (ATTENTION,)

    @property
    def n_periods(self) -> int:
        return self.n_layer // len(self.period)

    def layers_of(self, kind: str) -> int:
        return self.n_periods * self.period.count(kind)

    @property
    def cache_spec(self) -> Dict[str, int]:
        """What the stack keeps between steps, by cache: ``kv`` the layers
        that hold KV blocks, ``state`` those that hold a recurrent state
        and a conv state in a slot a request. What cannot hold with a
        state yet (a prefix hit, a verify window's rewind, a block moved
        to the host) is refused from here, not by an option."""
        return {"kv": self.layers_of(ATTENTION),
                "state": self.layers_of(LINEAR_ATTENTION)}

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
    kinds = cfg.period
    bad = [k for k in kinds if k not in (ATTENTION, LINEAR_ATTENTION)]
    if bad:
        raise ValueError(f"layer_kinds {kinds}: unknown kind {bad[0]!r} "
                         f"(expected {ATTENTION}|{LINEAR_ATTENTION})")
    if cfg.n_layer % len(kinds):
        raise ValueError(f"n_layer {cfg.n_layer} is not whole periods of "
                         f"the layer pattern {kinds}")
    if LINEAR_ATTENTION in kinds and not (cfg.lin_heads and cfg.lin_head_dim):
        raise ValueError("a linear_attention layer needs lin_heads and "
                         "lin_head_dim")


def _layer_group(cfg: TransformerConfig, kind: str, n: int, ks, dtype):
    """The stacked parameters of ``n`` like layers of ``kind`` (leading dim
    ``n``, what ``lax.scan`` runs one compiled block over) from the keys
    ``ks``: the mixer under ``attn`` or ``lin``, its norm, the dense MLP and
    its norm."""
    std = cfg.init_std
    D, F = cfg.d_model, cfg.ff_dim
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
    if kind == ATTENTION:
        group["attn"] = {
            "wq": dense(ks[0], (n, D, H * Hd)),
            "wk": dense(ks[1], (n, D, KV * Hd)),
            "wv": dense(ks[2], (n, D, KV * Hd)),
            "wo": dense(ks[3], (n, H * Hd, D), out_std),
            **({"bq": jnp.zeros((n, H * Hd), dtype),
                "bk": jnp.zeros((n, KV * Hd), dtype),
                "bv": jnp.zeros((n, KV * Hd), dtype),
                "bo": jnp.zeros((n, D), dtype)} if cfg.attn_bias else {}),
            **({"q_norm": {"scale": jnp.ones((n, H * Hd), dtype)},
                "k_norm": {"scale": jnp.ones((n, KV * Hd), dtype)}}
               if cfg.qk_norm else {}),
            **({"wg": dense(ks[7], (n, D, H * Hd))}
               if cfg.attn_out_gate else {}),
        }
    else:
        group["lin"] = _init_linear_attention(cfg, n, ks[8], dtype, out_std)
    group["ln_mlp"] = norm_params()
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


def init_params(cfg: TransformerConfig, rng, dtype=jnp.float32) -> Dict[str, Any]:
    """Stacked-layer parameter pytree. Layer weights carry a leading
    ``n_layer`` dim so ``lax.scan`` runs one compiled block for all layers.
    Under a layer pattern (``cfg.layer_kinds``) ``layers`` is a tuple, one
    group a position of the period, each stacked over the periods."""
    _check_pattern(cfg)
    k_emb, k_pos, k_layers, k_head = jax.random.split(rng, 4)
    std = cfg.init_std
    D = cfg.d_model

    def dense(key, shape, scale=std):
        return (jax.random.normal(key, shape) * scale).astype(dtype)

    if cfg.layer_kinds is None:
        layers = _layer_group(cfg, ATTENTION, cfg.n_layer,
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
    pattern is replicated: no tensor-parallel form of its mixers is built."""
    if cfg.layer_kinds is not None:
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
    [..., H*Hd] / [..., KV*Hd] over their whole width. The ONE place every
    attention path (``attention`` and ``_qkv_project``: cached, paged
    prefill, chunk, verify, decode) takes it from."""
    if not cfg.qk_norm:
        return q, k
    if cfg.manual_tp:
        raise NotImplementedError(
            "qk_norm reduces over all heads; manual-tp stages hold a slice")

    def rms(x, p):
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        return (x32 * jax.lax.rsqrt(var + cfg.norm_eps)
                * p["scale"].astype(jnp.float32)).astype(x.dtype)

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
                                      block_k=cfg.attn_block_k)
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
                            scale=cfg.attn_scale)
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
                          mesh, scale=None):
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
                jnp.asarray(pos, jnp.int32)]
    specs = [qspec, pspec, pspec, P(), P()]
    if pad_bias is not None:
        operands.append(pad_bias.astype(jnp.float32))
        specs.append(P(None, None))
    if slopes is not None:
        # contiguous head chunks of H/nh = G * (KV/nh) heads: each shard's
        # slopes regroup to its own (KV_shard, G) exactly like q does
        operands.append(jnp.asarray(slopes, jnp.float32).reshape(H))
        specs.append(P(head_axis))

    def inner(qs, kps, vps, bts, ps, *rest):
        rest = list(rest)
        ms = rest.pop(0) if pad_bias is not None else None
        ss = rest.pop(0) if slopes is not None else None
        return paged_decode_attention(qs, kps, vps, bts, ps, pad_bias=ms,
                                      alibi_slopes=ss, scale=scale)

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
    full remat, the reference's save-only-inputs CheckpointFunction)."""
    if remat is True or remat == "full":
        return None
    pols = jax.checkpoint_policies
    if remat == "dots":
        return pols.dots_with_no_batch_dims_saveable
    if remat == "selective":
        # save only the [tokens, D]-sized projections (cheap to store) plus
        # the flash kernel's (o, lse) residuals — so backward runs the flash
        # backward kernels WITHOUT re-running the forward kernel — and
        # recompute the d_ff-sized up/gate activations in backward
        return pols.save_only_these_names(
            "q_proj", "k_proj", "v_proj", "attn_out", "wo_out", "ff_down",
            "flash_o", "flash_lse")
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


def _qkv_project(cfg: TransformerConfig, x, lp, positions):
    """Shared decode-side q/k/v projection: act-quant (QAT parity with the
    training path — or prefill/decode logits diverge from forward()),
    optional attn biases (attn_bias=True REQUIRES all four bias tensors —
    loud KeyError on a params tree saved without them), head reshape, rope.
    Returns (q [B,T,H,Hd], k [B,T,KV,Hd], v [B,T,KV,Hd])."""
    B, T, D = x.shape
    H, KV, Hd = cfg.n_head, cfg.kv_heads, cfg.head_dim
    x = _maybe_act_quant(cfg, x)
    bq = lp["bq"] if cfg.attn_bias else 0
    bk = lp["bk"] if cfg.attn_bias else 0
    bv = lp["bv"] if cfg.attn_bias else 0
    q, k = _qk_norm(cfg, x @ _w(lp["wq"], x) + bq, x @ _w(lp["wk"], x) + bk, lp)
    q = q.reshape(B, T, H, Hd)
    k = k.reshape(B, T, KV, Hd)
    v = (x @ _w(lp["wv"], x) + bv).reshape(B, T, KV, Hd)
    if cfg.pos_embedding == "rope":
        q = _rope(q, positions, cfg.rope_theta, cfg.rope_dim, cfg.rope_interleaved)
        k = _rope(k, positions, cfg.rope_theta, cfg.rope_dim, cfg.rope_interleaved)
    return q, k, v


def _grouped_cache_einsum(cfg: TransformerConfig, q, ck, cv, positions,
                          pad_bias):
    """Grouped-head einsum of q [B,T,H,Hd] against an UNREPEATED cache
    ck/cv [B,S,KV,Hd] with per-row causal masking at ``positions`` (query
    heads reshaped [KV, G]: head h reads kv head h // G, matching the
    kernels' index maps — off-kernel decode skips the H/KV× cache copy).
    The single masked-softmax core shared by the dense-workspace and paged
    fallback paths. Returns [B, T, H*Hd]."""
    B, T, H, Hd = q.shape
    S, KV = ck.shape[1], ck.shape[2]
    G = H // KV
    scale = Hd**-0.5 if cfg.attn_scale is None else cfg.attn_scale
    q5 = q.reshape(B, T, KV, G, Hd)
    scores = jnp.einsum("btcgd,bscd->bcgts", q5, ck,
                        preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(S, dtype=jnp.int32)[None, None, None, None, :]  # [1,1,1,1,S]
    qpos = positions[:, None, None, :, None]                          # [B,1,1,T,1]
    valid = kpos <= qpos                                              # causal + cache bound
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
    v of an attention layer, state and conv state of a linear-attention
    one, under ``scope``; ``mlp_fn(cfg, x_normed, lp)`` overrides the dense
    MLP (MoE). Returns (h, *new caches)."""
    mfn = mlp_fn if mlp_fn is not None else (
        lambda c, xx, lpp: mlp(c, xx, lpp["mlp"]))
    with jax.named_scope(scope):
        a, *caches = mix_fn(_norm(cfg, h, lp["ln_attn"]))
    if cfg.parallel_residual:
        m = mfn(cfg, _norm(cfg, h, lp["ln_mlp"]), lp)
        return (h + a + m, *caches)
    h = h + a
    m = mfn(cfg, _norm(cfg, h, lp["ln_mlp"]), lp)
    return (h + m, *caches)


def cached_block(cfg: TransformerConfig, h, lp, ck, cv, positions, pos,
                 pad_bias=None, mlp_fn=None):
    """ONE layer of the KV-cache path: pre-LN attention against + append to
    the layer's cache. Shared by the compiled scan in :func:`forward_cached`
    and ZeRO-Inference weight streaming (per-layer host→device loop,
    ``inference/engine.py``). ``mlp_fn(cfg, x_normed, lp)`` overrides the
    dense MLP (the MoE zoo passes its routed experts)."""
    return _decode_block(
        cfg, h, lp,
        lambda xn: _cached_attention(cfg, xn, lp["attn"], positions, pos,
                                     ck, cv, pad_bias),
        mlp_fn)


@jax.named_scope("vocab_head")
def cached_head(cfg: TransformerConfig, params, x):
    """Final norm + logits projection of the cached path."""
    x = _norm(cfg, x, params["ln_f"])
    return x @ _head_weight(cfg, params) + _head_bias(params)


def forward_cached(cfg: TransformerConfig, params, tokens, cache, pos, pad_bias=None,
                   mlp_fn=None):
    """tokens [B, T] (T static: prompt chunk or 1) attended against + appended
    to ``cache`` at offset ``pos`` ([] int32). Returns (logits [B, T, vocab],
    new cache). ``pad_bias`` [B, Smax] additive f32 masks cache slots of
    left-padded prompts; ``mlp_fn`` see :func:`cached_block`."""
    _no_layer_pattern(cfg, "the dense-workspace KV cache")
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
# Linear attention: the channel-wise gated delta rule (Kimi Delta Attention)
#
#   q, k, v = silu(conv(x Wq)), silu(conv(x Wk)), silu(conv(x Wv))
#   qh = q / |q| * dk^-0.5,  kh = k / |k|                 (per head)
#   g  = -exp(A_log) * softplus(x Wf1 Wf2 + dt_bias) <= 0 (per channel)
#   beta = KDA_BETA_SCALE * sigmoid(x Wb)                 (per head)
#   S' = diag(exp(g)) S;  S = S' + beta kh (v - S'^T kh)^T;  o = S^T qh
#   y  = (RMSNorm_head(o) * sigmoid(x Wg1 Wg2 + bg)) Wo
#
# A request's state (S, float32 [H, dk, dv] a layer) and conv state (the
# last K-1 conv inputs) live in a SLOT of two pools beside the KV pools:
# ``state`` [periods, slots, H, dk, dv] and ``conv`` [periods, slots, K-1,
# 3*H*dk], one array of each a linear-attention position of the period
# (init_paged_kv_cache says why). Slot 0 is the dummy (inactive decode rows and nothing else).
# Decode updates the state where it lives and never gathers it: on TPU a
# Pallas kernel over the live rows' slots, elsewhere the one-token update
# over the WHOLE pool slice of a layer, slot-major (_kda_decode); prefill
# runs a chunked form.

_HI = jax.lax.Precision.HIGHEST
#: tokens a chunk of the chunked form; every exponent inside is a decay
#: between two positions of one chunk, so <= 0
KDA_CHUNK = 64
#: taps of the causal depthwise conv over q, k and v
KDA_CONV_KERNEL = 4
#: beta = 2 sigmoid(.): eigenvalues of the state's transition in (-1, 1)
KDA_BETA_SCALE = 2.0


def _init_linear_attention(cfg: TransformerConfig, n: int, key, dtype, out_std):
    D, H, dk, K = cfg.d_model, cfg.lin_heads, cfg.lin_head_dim, KDA_CONV_KERNEL
    r = dk                      # the rank of the two factored gates
    std = cfg.init_std
    ks = jax.random.split(key, 12)

    def dense(k, shape, scale=std):
        return (jax.random.normal(k, shape) * scale).astype(dtype)

    # the family's own draws (fla KimiDeltaAttention): a step's decay
    # exp(-A * dt) lies where a trained model's does
    a = jax.random.uniform(ks[9], (n, H), minval=1.0, maxval=16.0)
    dt = jax.random.uniform(ks[10], (n, H * dk), minval=1e-3, maxval=0.1)
    return {
        "wq": dense(ks[0], (n, D, H * dk)),
        "wk": dense(ks[1], (n, D, H * dk)),
        "wv": dense(ks[2], (n, D, H * dk)),
        "wo": dense(ks[3], (n, H * dk, D), out_std),
        # depthwise, causal: tap K-1 multiplies the current input; q | k | v
        "conv_w": dense(ks[4], (n, K, 3 * H * dk), K ** -0.5),
        "wf1": dense(ks[5], (n, D, r)), "wf2": dense(ks[6], (n, r, H * dk)),
        "A_log": jnp.log(a).astype(dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "wb": dense(ks[7], (n, D, H)),
        "wg1": dense(ks[8], (n, D, r)), "wg2": dense(ks[11], (n, r, H * dk)),
        "bg": jnp.zeros((n, H * dk), dtype),
        "o_norm": {"scale": jnp.ones((n, dk), dtype)},
    }


def _kda_project(cfg: TransformerConfig, x, lp, conv_ctx):
    """x [N, T, D], conv_ctx [N, K-1, 3*H*dk] the conv inputs before x ->
    (qh, kh, v [N, T, H, dk], g [N, T, H, dk] <= 0, beta [N, T, H], all
    float32, and the conv window [N, T+K-1, 3*H*dk] the next conv state is
    cut from)."""
    N, T, _ = x.shape
    H, dk, K = cfg.lin_heads, cfg.lin_head_dim, KDA_CONV_KERNEL
    f32 = jnp.float32
    u = jnp.concatenate([x @ _w(lp["wq"], x), x @ _w(lp["wk"], x),
                         x @ _w(lp["wv"], x)], axis=-1)
    win = jnp.concatenate([conv_ctx.astype(u.dtype), u], axis=1)
    with jax.named_scope("short_conv"):
        w = lp["conv_w"].astype(f32)
        y = sum(win[:, j:j + T].astype(f32) * w[j] for j in range(K))
        y = jax.nn.silu(y).reshape(N, T, 3, H, dk)
    q, k, v = y[:, :, 0], y[:, :, 1], y[:, :, 2]
    qh = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) * dk ** -0.5
    kh = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    f = ((x @ _w(lp["wf1"], x)) @ _w(lp["wf2"], x)).astype(f32)
    g = -jnp.exp(lp["A_log"].astype(f32))[:, None] * jax.nn.softplus(
        f.reshape(N, T, H, dk) + lp["dt_bias"].astype(f32).reshape(H, dk))
    beta = KDA_BETA_SCALE * jax.nn.sigmoid(
        (x @ _w(lp["wb"], x)).astype(f32))
    return qh, kh, v, g, beta, win


def _kda_output(cfg: TransformerConfig, o, x, lp):
    """o [N, T, H, dv] float32 -> (RMSNorm_head(o) * sigmoid(gate)) Wo."""
    N, T, H, dv = o.shape
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.norm_eps) \
        * lp["o_norm"]["scale"].astype(jnp.float32)
    gate = ((x @ _w(lp["wg1"], x)) @ _w(lp["wg2"], x) + lp["bg"]).astype(jnp.float32)
    y = (o.reshape(N, T, H * dv) * jax.nn.sigmoid(gate)).astype(x.dtype)
    return y @ _w(lp["wo"], y)


def kda_recurrent_step(S, qh, kh, v, g, beta):
    """The one-token update, any leading dims: S [..., dk, dv] float32,
    qh, kh, g [..., dk], v [..., dv], beta [...] -> (o [..., dv], new S).
    S is read twice and written once: o = S'^T qh + (kh . qh) u comes out
    of the same pass as S'^T kh."""
    S1 = S * jnp.exp(g)[..., None]
    r = jnp.sum(S1 * kh[..., None], axis=-2)
    p = jnp.sum(S1 * qh[..., None], axis=-2)
    u = beta[..., None] * (v - r)
    o = p + jnp.sum(qh * kh, axis=-1, keepdims=True) * u
    return o, S1 + kh[..., None] * u[..., None, :]


def _inv_unit_lower(A):
    """(I + A)^-1 for A [..., C, C] strictly lower triangular, row by row
    (forward substitution on the identity), all leading dims at once."""
    C = A.shape[-1]
    eye = jnp.eye(C, dtype=A.dtype)

    def row(t, inv):
        a_t = jax.lax.dynamic_index_in_dim(A, t, axis=-2, keepdims=False)
        new = eye[t] - jnp.einsum("...s,...sj->...j", a_t, inv, precision=_HI)
        return jax.lax.dynamic_update_index_in_dim(inv, new, t, axis=-2)

    return jax.lax.fori_loop(0, C, row, jnp.zeros_like(A))


def kda_chunked(S0, qh, kh, v, g, beta, chunk: int = KDA_CHUNK):
    """The recurrence over T tokens of one sequence in chunks: S0 [H, dk,
    dv], qh, kh, g [T, H, dk], v [T, H, dv], beta [T, H] (T whole chunks)
    -> (o [T, H, dv], S_T). Inside a chunk, with G_t the cumulated log
    decay, u_t = beta_t (v_t - S'_t^T kh_t) solves (I + A) U = beta (V -
    (K e^G) S0), A_ts = beta_t sum_c kh_tc kh_sc e^(G_tc - G_sc) (s < t);
    o_t = S0^T (qh_t e^G_t) + sum_{s<=t} (qh_t . kh_s e^(G_t - G_s)) u_s;
    S_C = e^G_C S0 + sum_s (kh_s e^(G_C - G_s)) u_s^T. Decays are only ever
    taken between two positions (s <= t), never as e^-G: that overflows
    float32 within a chunk at this family's decay range."""
    T, H, dk = qh.shape
    C, n = chunk, T // chunk
    cut = lambda a: jnp.moveaxis(a.reshape(n, C, *a.shape[1:]), 2, 1)  # noqa: E731
    q, k, vv, gg = cut(qh), cut(kh), cut(v), cut(g)        # [n, H, C, d]
    b = cut(beta[..., None])                               # [n, H, C, 1]
    G = jnp.cumsum(gg, axis=2)
    t_idx = jnp.arange(C)
    incl = t_idx[:, None] >= t_idx[None, :]                # s <= t
    decay = jnp.exp(jnp.where(incl[:, :, None],
                              G[:, :, :, None, :] - G[:, :, None, :, :],
                              -jnp.inf))                   # [n, H, t, s, dk]
    a_qk = jnp.sum(q[:, :, :, None, :] * k[:, :, None, :, :] * decay, -1)
    a_kk = jnp.sum(k[:, :, :, None, :] * k[:, :, None, :, :] * decay, -1)
    a_kk = jnp.where(t_idx[:, None] > t_idx[None, :], a_kk, 0.0) * b
    inv = _inv_unit_lower(a_kk)                            # [n, H, C, C]
    eG = jnp.exp(G)
    g_end = G[:, :, -1:, :]
    w = jnp.einsum("nhts,nhsd->nhtd", inv, b * k * eG, precision=_HI)
    vb = jnp.einsum("nhts,nhsd->nhtd", inv, b * vv, precision=_HI)
    qd, kr = q * eG, k * jnp.exp(g_end - G)
    d_end = jnp.exp(g_end[:, :, 0, :])                     # [n, H, dk]

    def step(S, xs):
        w_i, vb_i, qd_i, aqk_i, kr_i, d_i = xs
        u = vb_i - jnp.einsum("htk,hkv->htv", w_i, S, precision=_HI)
        o = jnp.einsum("htk,hkv->htv", qd_i, S, precision=_HI) \
            + jnp.einsum("hts,hsv->htv", aqk_i, u, precision=_HI)
        S = d_i[..., None] * S \
            + jnp.einsum("htk,htv->hkv", kr_i, u, precision=_HI)
        return S, o

    S, o = jax.lax.scan(step, S0, (w, vb, qd, a_qk, kr, d_end))
    return jnp.moveaxis(o, 1, 2).reshape(T, H, -1), S


def _kda_prefill(cfg: TransformerConfig, x, lp, state, conv, slot, n_valid,
                 fresh):
    """The mixer over a prompt, or a chunk of one, of ONE request: x [1, T,
    D] (T a compile bucket, the first ``n_valid`` positions real), ``slot``
    the request's row of the layer in ``state`` [rows, H, dk, dv] and
    ``conv`` [rows, K-1, 3*H*dk]. ``fresh`` (a bool, traced or not): the
    request's first piece starts from zero, whatever the slot's last holder
    left there. Padding has decay 1 and beta 0: the state after the bucket
    is the state after position ``n_valid``."""
    T = x.shape[1]
    K = KDA_CONV_KERNEL
    keep = jnp.logical_not(fresh)
    ctx = jax.lax.dynamic_slice_in_dim(conv, slot, 1, axis=0)
    ctx = jnp.where(keep, ctx, jnp.zeros_like(ctx))
    qh, kh, v, g, beta, win = _kda_project(cfg, x, lp, ctx)
    real = (jnp.arange(T) < n_valid)[None, :, None]
    g = jnp.where(real[..., None], g, 0.0)
    beta = jnp.where(real, beta, 0.0)
    S0 = jax.lax.dynamic_slice_in_dim(state, slot, 1, axis=0)[0]
    S0 = jnp.where(keep, S0, jnp.zeros_like(S0))
    with jax.named_scope("kda_state_update"):
        o, S = kda_chunked(S0, qh[0], kh[0], v[0], g[0], beta[0])
    state = jax.lax.dynamic_update_slice_in_dim(state, S[None], slot, axis=0)
    tail = jax.lax.dynamic_slice_in_dim(win, n_valid, K - 1, axis=1)
    conv = jax.lax.dynamic_update_slice_in_dim(
        conv, tail.astype(conv.dtype), slot, axis=0)
    return _kda_output(cfg, o[None], x, lp), state, conv


def _kda_slot_update(state, qh, kh, v, g, beta, slots, base, n_slots: int):
    """The plain-XLA form of a decode step's state update, and what the
    tests compare the kernel against: ``kda_recurrent_step`` over the
    layer's WHOLE slice of the pool (rows ``base .. base + n_slots``) in
    slot order, the rows' vectors ([B, H, d], by row) scattered to their
    slots; a slot no row holds keeps its state (decay 1, beta 0). Returns
    (o [B, H, dv] by row, the pool)."""
    def by_slot(a):
        return jnp.zeros((n_slots, *a.shape[1:]), a.dtype).at[slots].set(a)

    S = jax.lax.dynamic_slice_in_dim(state, base, n_slots, axis=0)
    o, S = kda_recurrent_step(S, by_slot(qh), by_slot(kh), by_slot(v),
                              by_slot(g), by_slot(beta))
    return o[slots], jax.lax.dynamic_update_slice_in_dim(state, S, base, axis=0)


def _kda_state_update(cfg: TransformerConfig, state, qh, kh, v, g, beta,
                      slots, base, n_slots: int):
    """A decode step's state update in one of two forms of the same float32
    arithmetic, chosen as the paged kernel is (``_use_flash``: the backend
    and the shape, never the model):

    * ``kda_kernel`` (TPU): ``ops/pallas/kda_decode_update.py`` reads each
      LIVE row's state once and writes it once, addressed row -> slot, the
      rows' vectors taken by row. A step's state traffic is the live rows';
      the dummy and every slot no live row holds are not touched.
    * ``slot_update`` (elsewhere, and shapes the kernel cannot tile):
      ``_kda_slot_update``. Its traffic is that of ALL the layer's slots,
      read twice and written once."""
    step = (qh, kh, v, g, beta, slots, base)
    out = None
    if _use_flash(cfg):
        from deepspeed_tpu.ops.pallas.kda_decode_update import \
            kda_decode_update
        out = kda_decode_update(state, *step)
    dispatch.record("kda_decode", "slot_update" if out is None else "kda_kernel",
                    f"B={qh.shape[0]} H={qh.shape[1]} dk={qh.shape[2]} "
                    f"dv={v.shape[2]} slots={n_slots}")
    return out or _kda_slot_update(state, *step, n_slots)


def _kda_decode(cfg: TransformerConfig, x, lp, state, conv, base, slots,
                n_slots: int):
    """One token a row: x [B, 1, D], ``slots`` [B] each row's state slot
    (0, the dummy, for an inactive row), the layer's slots at rows ``base ..
    base + n_slots`` of ``state`` and ``conv``. The state is updated where
    it lives (``_kda_state_update``): on TPU by a kernel over the live rows'
    slots, so a step's state traffic goes with the live rows; elsewhere over
    the layer's whole slice, in slot order."""
    rows = base + slots
    ctx = conv[rows]
    qh, kh, v, g, beta, win = _kda_project(cfg, x, lp, ctx)
    conv = conv.at[rows].set(win[:, 1:].astype(conv.dtype))
    with jax.named_scope("kda_state_update"):
        o, state = _kda_state_update(cfg, state, qh[:, 0], kh[:, 0], v[:, 0],
                                     g[:, 0], beta[:, 0], slots, base, n_slots)
    return _kda_output(cfg, o[:, None], x, lp), state, conv


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
                        state_slots: int = 0) -> Dict[str, Any]:
    """Paged pools, a dict that each kind of layer adds to. Attention
    layers: k/v [kv layers, num_blocks, block_size, kv_heads * Hd] (kv head
    ``g`` of a token at ``[g*Hd, (g+1)*Hd)`` of its row). Block 0 is
    conventionally the allocator's dummy block (padding tokens and inactive
    decode rows write there; nothing ever reads it). Linear-attention
    layers: ``state`` and ``conv``, each a tuple of one array a
    linear-attention POSITION of the period, [periods, state_slots, H, dk,
    dv] float32 and [periods, state_slots, K-1, 3*H*dk]: a slot a running
    request and slot 0 the dummy. (One array a position, not one for all:
    the period's layers are unrolled, so their offsets into a shared array
    would be constants, XLA would fold a later layer's read through the
    earlier layer's update back to the buffer as it came in, and a buffer
    read after it is written cannot be updated in place: the whole pool
    was copied in and out, twice its size a step.) This is the one home of
    the shapes."""
    spec = cfg.cache_spec
    shape = (spec["kv"], num_blocks, block_size, cfg.kv_heads * cfg.head_dim)
    pools = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if spec["state"]:
        if state_slots < 2:
            raise ValueError("a stack with recurrent state needs state_slots "
                             ">= 2 (the dummy and one a running request)")
        H, dk, K = cfg.lin_heads, cfg.lin_head_dim, KDA_CONV_KERNEL
        n_lin, P_ = cfg.period.count(LINEAR_ATTENTION), cfg.n_periods
        pools["state"] = tuple(
            jnp.zeros((P_, state_slots, H, dk, dk), jnp.float32)
            for _ in range(n_lin))
        pools["conv"] = tuple(
            jnp.zeros((P_, state_slots, K - 1, 3 * H * dk), dtype)
            for _ in range(n_lin))
    return pools


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


def _scan_paged_layers(cfg: TransformerConfig, params, pools, x, attn_fn,
                       mlp_fn=None, lin_fn=None):
    """The ONE way the paged programs thread the pools through the layer
    stack: as the carry of a scan over the PERIODS of the layer pattern (a
    period of one attention layer where the stack has no pattern), a
    period's layers unrolled inside it, each pool viewed with its layer
    axis merged into the next (a bitcast): k and v as
    [layers*num_blocks, bs, KV*Hd], state and conv as [layers*slots, ...].
    Attention layer ``l`` lives at blocks ``[l*num_blocks, (l+1)*num_blocks)``
    of that view, so ``attn_fn(x_normed, lp_attn, kp, vp, block0, slot0)``
    reads and writes it through ``block_tables + block0`` / ``slots +
    slot0``; linear-attention layer ``l`` at rows ``[l*slots, (l+1)*slots)``,
    ``lin_fn(x_normed, lp_lin, state, conv, row0)``. No per-layer slice of
    a pool exists and nothing is stacked. An ``mlp_fn`` may return ``(out,
    aux)``: ``aux`` (an MoE layer's per-expert counts) comes back stacked
    over the layers, else None. Returns (x, new pools, aux)."""
    period = cfg.period
    n_att, n_lin = period.count(ATTENTION), period.count(LINEAR_ATTENTION)
    Nb, bs = pools["k"].shape[1:3]
    n_slots = pools["state"][0].shape[1] if n_lin else 0
    groups = params["layers"] if cfg.layer_kinds is not None \
        else (params["layers"],)

    def run_period(carry, xs):
        h, flat = carry
        flat = {n: list(a) if isinstance(a, tuple) else a
                for n, a in flat.items()}
        lps, p = xs
        aux = []
        mfn = mlp_fn
        if mlp_fn is not None:
            def mfn(c, xx, lpp):
                out = mlp_fn(c, xx, lpp)
                if isinstance(out, tuple):
                    out, a = out
                    aux.append(a)
                return out
        i_att = i_lin = 0
        for kind, lp in zip(period, lps):
            if kind == ATTENTION:
                l = p * n_att + i_att
                i_att += 1
                h, flat["k"], flat["v"] = _decode_block(
                    cfg, h, lp,
                    lambda xn: attn_fn(xn, lp["attn"], flat["k"], flat["v"],
                                       l * Nb, l * (Nb * bs)),
                    mfn)
            else:
                i = i_lin
                i_lin += 1
                h, flat["state"][i], flat["conv"][i] = _decode_block(
                    cfg, h, lp,
                    lambda xn: lin_fn(xn, lp["lin"], flat["state"][i],
                                      flat["conv"][i], p * n_slots),
                    mfn, scope=LINEAR_ATTENTION)
        flat = {n: tuple(a) if isinstance(a, list) else a
                for n, a in flat.items()}
        out = None if not aux else aux[0] if len(aux) == 1 else jnp.stack(aux)
        return (h, flat), out

    flat = jax.tree.map(
        lambda a: a.reshape(a.shape[0] * a.shape[1], *a.shape[2:]), pools)
    (x, flat), aux = jax.lax.scan(
        run_period, (x, flat),
        (groups, jnp.arange(cfg.n_periods, dtype=jnp.int32)))
    if aux is not None and len(period) > 1:
        aux = aux.reshape(cfg.n_layer, *aux.shape[2:])
    return x, jax.tree.map(lambda a, b: a.reshape(b.shape), flat, pools), aux


def paged_real_rows(pools, slots):
    """Which positions of a paged step are real: padding (a prompt bucket's
    tail, an inactive decode or verify row) is what the engine routes to the
    dummy block 0, so a flat slot under ``block_size`` is padding. What an
    MoE MLP keeps away from its experts."""
    return slots >= pools["k"].shape[2]


def _paged_decode_attention(cfg: TransformerConfig, x, lp, positions, pos,
                            kp, vp, block_tables, pad_bias):
    """One fused decode step over all running requests against the paged
    pools: x [B, 1, D] (one new token per request), pos [B] per-request
    cache depths, kp/vp [blocks, bs, KV*Hd], block_tables [B, max_blocks].
    Returns (out [B, 1, D], new kp, vp)."""
    B, T, D = x.shape
    H = cfg.n_head
    bs = kp.shape[1]

    q, k, v = _qkv_project(cfg, x, lp, positions)

    # each request's new k/v lands at its block-table slot; inactive rows
    # carry a zeroed table and write into the dummy block
    slots = block_tables[jnp.arange(B), pos // bs] * bs + pos % bs
    kp = _pool_scatter(kp, k[:, 0], slots)
    vp = _pool_scatter(vp, v[:, 0], slots)

    slopes = _alibi_slopes(H) if cfg.pos_embedding == "alibi" else None
    o = None
    if _use_flash(cfg):
        from deepspeed_tpu.ops.pallas.paged_decode_attention import \
            paged_decode_attention
        o = paged_decode_attention(q[:, 0], kp, vp, block_tables, pos,
                                   pad_bias=pad_bias, alibi_slopes=slopes,
                                   scale=cfg.attn_scale)
        form = "paged_kernel"
    else:
        # SPMD mesh (a bare pallas_call is illegal): shard_map the kernel
        # over the KV-head/tp axis — the head-sharded pool's shards each
        # stream their local heads, tables stay replicated
        pmesh = _flash_mesh(cfg)
        if pmesh is not None:
            o = _paged_decode_sharded(q[:, 0], kp, vp, block_tables, pos,
                                      pad_bias, slopes, pmesh,
                                      scale=cfg.attn_scale)
            form = "paged_kernel_sharded"
    if o is not None:
        out = o.reshape(B, 1, H * cfg.head_dim)
    else:
        form = "gather_einsum"
        # gather + grouped einsum (the dense cache path's masked-softmax
        # core with per-request qpos) — partitionable, the CPU tier default
        out = _grouped_cache_einsum(
            cfg, q, _paged_gather(kp, block_tables, cfg.kv_heads),
            _paged_gather(vp, block_tables, cfg.kv_heads), positions, pad_bias)
    dispatch.record("paged_decode", form,
                    f"B={B} H={H} KV={cfg.kv_heads} Hd={cfg.head_dim} bs={bs}")
    out = _attn_out(cfg, out, x, lp)
    return out, kp, vp


def _paged_prefill_attention(cfg: TransformerConfig, x, lp, positions,
                             kp, vp, slots):
    """Prefill attention of ONE fresh request: causal self-attention over
    its own prompt (a fresh request has no prior context to read), with the
    prompt's k/v scattered into the request's pool blocks. x [1, T, D];
    slots [T] flat pool slots (pad positions routed to the dummy block)."""
    B, T, D = x.shape
    H, Hd = cfg.n_head, cfg.head_dim

    q, k, v = _qkv_project(cfg, x, lp, positions)

    kp = _pool_scatter(kp, k, slots)
    vp = _pool_scatter(vp, v, slots)

    slopes = _alibi_slopes(H) if cfg.pos_embedding == "alibi" else None
    out = None
    if _use_flash(cfg):
        from deepspeed_tpu.ops.pallas import flash_attention
        out = flash_attention(q, k, v, causal=True, alibi_slopes=slopes,
                              scale=cfg.attn_scale, block_q=cfg.attn_block_q,
                              block_k=cfg.attn_block_k)
        form = "flash"
    if out is None:
        from deepspeed_tpu.ops.attention import mha_attention
        out = mha_attention(q, k, v, causal=True, alibi_slopes=slopes,
                            scale=cfg.attn_scale)
        form = "einsum"
    dispatch.record("paged_prefill", form, f"T={T}")
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


def _no_layer_pattern(cfg: TransformerConfig, what: str):
    if cfg.layer_kinds is not None:
        raise NotImplementedError(
            f"{what} is not built for a stack with a layer pattern "
            f"({cfg.period}): it runs on the paged serving path only")


def _check_paged_config(cfg: TransformerConfig):
    if cfg.norm_position == "post" or not cfg.causal:
        raise ValueError("the paged KV path serves pre-LN causal LMs only")
    if cfg.sparse_attention is not None:
        raise NotImplementedError(
            "sparse_attention is not supported by the paged KV decode path")


def _lin_prefill_fn(cfg: TransformerConfig, pools, state_slot, n_valid, fresh):
    """The ``lin_fn`` of a prefill or chunk program (None for a stack
    without state): the request's slot in each linear-attention layer."""
    if "state" not in pools:
        return None
    if state_slot is None:
        raise ValueError("a stack with recurrent state needs the request's "
                         "state slot")
    return lambda xn, lp, st, cv, row0: _kda_prefill(
        cfg, xn, lp, st, cv, row0 + state_slot, n_valid, fresh)



def forward_paged_prefill(cfg: TransformerConfig, params, tokens, pools,
                          slots, last_idx, mlp_fn=None, state_slot=None):
    """Prefill ONE admitted request into its allocated blocks.

    tokens [1, T] right-padded prompt (T the compile bucket); slots [T]
    flat pool slots per prompt position (block_table[t // bs] * bs + t % bs,
    pads routed to the dummy block); last_idx [] int32 index of the last
    real prompt token. Returns (logits [1, vocab] at last_idx, new pools) —
    junk pad positions are causally invisible to the sampled position.
    ``state_slot`` [] int32 (a stack with recurrent state): the request's
    slot, which a whole prefill starts from zero."""
    _check_paged_config(cfg)
    x, positions = cached_embed(cfg, params, tokens, jnp.int32(0),
                                pools["k"].dtype)

    x, pools, _ = _scan_paged_layers(
        cfg, params, pools, x,
        lambda xn, lp, kp, vp, block0, slot0: _paged_prefill_attention(
            cfg, xn, lp, positions, kp, vp, slots + slot0),
        mlp_fn, _lin_prefill_fn(cfg, pools, state_slot, last_idx + 1, True))
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
    the chunk that starts at position 0 and carried over the later ones."""
    _check_paged_config(cfg)
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
    return {**pools,
            "k": pools["k"].at[:, dst].set(pools["k"][:, src]),
            "v": pools["v"].at[:, dst].set(pools["v"][:, src])}


def _paged_verify_attention(cfg: TransformerConfig, x, lp, positions,
                            kp, vp, block_tables, slots):
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
                                           scale=cfg.attn_scale)
            else:
                o = _paged_decode_sharded(q[:, t], kp, vp, block_tables,
                                          positions[:, t], None, slopes,
                                          pmesh, scale=cfg.attn_scale)
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
    if cfg.cache_spec["state"]:
        raise NotImplementedError(
            "a verify window rewinds to the last accepted position, and a "
            "recurrent state cannot be rewound (no snapshot is kept)")
    x, positions = cached_embed(cfg, params, tokens, pos, pools["k"].dtype)

    x, pools, _ = _scan_paged_layers(
        cfg, params, pools, x,
        lambda xn, lp, kp, vp, block0, slot0: _paged_verify_attention(
            cfg, xn, lp, positions, kp, vp, block_tables + block0,
            slots + slot0),
        mlp_fn)
    return cached_head(cfg, params, x), pools


def forward_paged_decode(cfg: TransformerConfig, params, tokens, pools,
                         block_tables, pos, pad_bias=None, mlp_fn=None,
                         state_slots=None):
    """One fused decode step over ALL running requests: tokens [B, 1] (each
    request's last sampled token), block_tables [B, max_blocks], pos [B]
    per-request cache depths. Returns (logits [B, vocab], new pools), and
    third what an ``mlp_fn`` that returns ``(out, aux)`` gave, stacked over
    the layers (an MoE model's [L, E + 1] assignment counts).
    ``state_slots`` [B] int32 (a stack with recurrent state): each row's
    state slot, 0 (the dummy) for an inactive row."""
    _check_paged_config(cfg)
    x, positions = cached_embed(cfg, params, tokens, pos, pools["k"].dtype)
    lin_fn = None
    if "state" in pools:
        if state_slots is None:
            raise ValueError("a stack with recurrent state needs the rows' "
                             "state slots")
        n_slots = pools["state"][0].shape[1]
        lin_fn = lambda xn, lp, st, cv, row0: _kda_decode(  # noqa: E731
            cfg, xn, lp, st, cv, row0, state_slots, n_slots)

    # the decode step derives its write slots from the (layer-offset) table
    x, pools, aux = _scan_paged_layers(
        cfg, params, pools, x,
        lambda xn, lp, kp, vp, block0, slot0: _paged_decode_attention(
            cfg, xn, lp, positions, pos, kp, vp, block_tables + block0,
            pad_bias),
        mlp_fn, lin_fn)
    logits = cached_head(cfg, params, x)[:, 0, :]
    return (logits, pools) if aux is None else (logits, pools, aux)


def run_layers(cfg: TransformerConfig, x, layer_params, positions, mask_bias,
               rng=None):
    """Run the stacked layer blocks over ``x`` with the config's remat policy
    and scan/unroll choice — shared by :func:`hidden_states` and non-token
    encoders (e.g. the CLIP vision tower). ``rng`` (training loss paths
    only) seeds per-layer dropout keys; None keeps every path deterministic
    and the traced program identical to the dropout-free form."""
    _no_layer_pattern(cfg, "the training forward (and its scan's backward)")
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
