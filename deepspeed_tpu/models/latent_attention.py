"""Multi-head latent attention (MLA): the ``latent_attention`` layer kind.

    cq = N(x Wqa);  q = cq Wqb -> H heads of (dn + dr)          [x s_q]
    (``q_lora_rank`` 0, a DIRECT query: q = x Wq, one matrix [D, H (dn +
    dr)], no bottleneck, no norm and no s_q: DeepSeek-V3's ``q_lora_rank``
    null, Kanana-2)
    [ckv | kr] = x Wkva;  ckv = N(ckv) [x s_kv];  kr ONE head for all H
    rope on q's last dr and on kr;  [k_nope | v] = ckv Wkvb -> H x (dn + dv)
    scores (q . [k_nope | kr]) / sqrt(dn + dr), causal softmax in float32
    y = concat(H x dv) Wo

with ``s_q = sqrt(d_model / q_lora_rank)`` and ``s_kv = sqrt(d_model /
kv_lora_rank)`` under ``cfg.mla_lora_scale`` (LongCat-Flash), else 1.

What a token keeps is ONE row a layer, ``[ckv (normed, scaled) | kr
(roped)]``, ``cfg.latent_row`` values (zeros after them to
``cfg.latent_pool_row`` lanes, whole 128-lane tiles): the pool ``c`` of
``init_paged_kv_cache``, blocks addressed by the same tables as KV blocks.
Two paths read it, and they agree (tests/unit/test_longcat_flash.py):

* ``prefill`` EXPANDS: the fresh prompt's rows go through ``Wkvb`` to H
  heads of keys (dn + dr, the shared ``kr`` repeated) and values (dv), and
  causal attention runs over them (the flash kernel with the values padded
  to the keys' width where a bare Pallas call is legal, else the einsum);
* ``decode`` ABSORBS: ``q' = q_nope Wkvb_K`` (a head: dn -> R), scores
  ``q' . ckv + q_rope . kr`` over the cached ROWS, ``o_lat = p . ckv``,
  ``o = o_lat Wkvb_V``: no key or value of the context is ever expanded,
  and a row is read once (ops/pallas/latent_decode_attention.py; off its
  envelope or off a bare Pallas call, the gather + einsum of the same).

No chunk, verify or block-generation form reads a latent row yet: those
programs refuse the pool, and the engine refuses what rides on them from
``cache_spec["latent"]``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.transformer import (TransformerConfig, _flat,
                                              _rope, _use_flash, _w)
from deepspeed_tpu.ops import dispatch

#: the eps of the two bottleneck norms (the published ``q_a_layernorm`` /
#: ``kv_a_layernorm`` are built without one and take the class default)
LORA_EPS = 1e-6


def check(cfg: TransformerConfig):
    sizes = (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
             cfg.v_head_dim)
    if min(sizes) < 1 or cfg.qk_rope_head_dim % 2 or cfg.q_lora_rank < 0:
        raise ValueError(
            "a latent_attention layer needs kv_lora_rank, qk_nope_head_dim, "
            "v_head_dim, an even qk_rope_head_dim and a q_lora_rank of 0 (a "
            f"direct query) or more (got {sizes}, {cfg.q_lora_rank})")
    if cfg.pos_embedding != "rope":
        raise ValueError("a latent_attention layer's shared key part is "
                         "roped: pos_embedding='rope'")


def init(cfg: TransformerConfig, n: int, key, dtype, out_std):
    """The stacked parameters of ``n`` latent-attention mixers."""
    D, H, R, Q = cfg.d_model, cfg.n_head, cfg.kv_lora_rank, cfg.q_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 5)

    def dense(k, shape, scale=cfg.init_std):
        return (jax.random.normal(k, shape) * scale).astype(dtype)

    query = {"wq_a": dense(ks[0], (n, D, Q)),
             "q_norm": {"scale": jnp.ones((n, Q), dtype)},
             "wq_b": dense(ks[1], (n, Q, H * (dn + dr)))} if Q else \
        {"wq": dense(ks[0], (n, D, H * (dn + dr)))}
    return {
        **query,
        "wkv_a": dense(ks[2], (n, D, R + dr)),
        "kv_norm": {"scale": jnp.ones((n, R), dtype)},
        "wkv_b": dense(ks[3], (n, R, H * (dn + dv))),
        "wo": dense(ks[4], (n, H * dv, D), out_std),
    }


def _rms(x, p, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)
            * p["scale"].astype(jnp.float32)).astype(x.dtype)


def project(cfg: TransformerConfig, x, lp, positions):
    """x [B, T, D] -> (q_nope [B, T, H, dn], q_rope [B, T, H, dr] roped,
    rows [B, T, R + dr]: what the cache keeps of these tokens)."""
    B, T, D = x.shape
    H, R = cfg.n_head, cfg.kv_lora_rank
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    rope = lambda a: _rope(a, positions, cfg.rope_theta, 0,  # noqa: E731
                           cfg.rope_interleaved)
    if cfg.q_lora_rank:
        cq = _rms(x @ _w(lp["wq_a"], x), lp["q_norm"], LORA_EPS)
        q = _flat(cq @ _w(lp["wq_b"], x))
    else:
        q = _flat(x @ _w(lp["wq"], x))
    q = q.reshape(B, T, H, dn + dr)
    kv = x @ _w(lp["wkv_a"], x)
    ckv = _rms(kv[..., :R], lp["kv_norm"], LORA_EPS)
    if cfg.mla_lora_scale:
        if cfg.q_lora_rank:
            q = q * math.sqrt(D / cfg.q_lora_rank)
        ckv = ckv * math.sqrt(D / R)
    kr = rope(kv[..., None, R:])[:, :, 0]
    return q[..., :dn], rope(q[..., dn:]), jnp.concatenate([ckv, kr], -1)


def _expansion(cfg: TransformerConfig, lp, like):
    """``Wkvb`` a head: (keys' part [R, H, dn], values' part [R, H, dv])."""
    w = _w(lp["wkv_b"], like).reshape(cfg.kv_lora_rank, cfg.n_head, -1)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def _scale(cfg: TransformerConfig) -> float:
    if cfg.attn_scale is not None:
        return cfg.attn_scale
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def _lanes(a, width: int):
    """``a`` with zeros after its last axis up to the pool's ``width``."""
    return jnp.pad(a, ((0, 0),) * (a.ndim - 1) + ((0, width - a.shape[-1]),))


def _scatter(cp, rows, slots):
    """Write tokens' rows [..., latent_row] into the pool view [blocks, bs,
    pool row] at flat slots [N]."""
    flat = cp.reshape(-1, cp.shape[-1])
    rows = _lanes(rows.reshape(-1, rows.shape[-1]), cp.shape[-1])
    return flat.at[slots].set(rows.astype(cp.dtype)).reshape(cp.shape)


def expanded_attention(cfg: TransformerConfig, q_nope, q_rope, rows, lp):
    """Causal attention of a fresh sequence over its own rows, the latent
    EXPANDED to H heads of keys and values. Returns [B, T, H * dv]."""
    B, T, H, dn = q_nope.shape
    R, dv = cfg.kv_lora_rank, cfg.v_head_dim
    wk, wv = _expansion(cfg, lp, rows)
    ckv, kr = rows[..., :R], rows[..., R:]
    k = jnp.concatenate(
        [jnp.einsum("btr,rhd->bthd", ckv, wk),
         jnp.broadcast_to(kr[:, :, None, :], (B, T, H, kr.shape[-1]))], -1)
    v = jnp.einsum("btr,rhd->bthd", ckv, wv)
    q = jnp.concatenate([q_nope, q_rope], -1)
    if _use_flash(cfg):
        from deepspeed_tpu.ops.pallas import flash_attention
        # one head size a call: the values ride at the keys' width
        vp = jnp.pad(v, ((0, 0),) * 3 + ((0, q.shape[-1] - dv),))
        out = flash_attention(q, k, vp, causal=True, scale=_scale(cfg),
                              block_q=cfg.attn_block_q,
                              block_k=cfg.attn_block_k)[..., :dv]
        form = "flash"
    else:
        from deepspeed_tpu.ops.attention import mha_attention
        # Dqk != Dv: the einsum takes the two widths as they are
        out = mha_attention(q, k, v, causal=True, scale=_scale(cfg))
        form = "einsum"
    dispatch.record("latent_prefill", form, f"T={T} H={H} Dqk={q.shape[-1]} "
                    f"Dv={dv}")
    return out.reshape(B, T, H * dv)


def prefill(cfg: TransformerConfig, x, lp, positions, cp, slots):
    """Prefill attention of ONE fresh request: its rows scattered into its
    pool blocks at ``slots`` [T] (pads to the dummy block), causal attention
    over the prompt itself through the expanded form. x [1, T, D]. Returns
    (out [1, T, D], cp)."""
    q_nope, q_rope, rows = project(cfg, x, lp, positions)
    cp = _scatter(cp, rows, slots)
    with jax.named_scope("latent_prefill"):
        out = expanded_attention(cfg, q_nope, q_rope, rows, lp)
    return out @ _w(lp["wo"], out), cp


def absorbed_attention(cfg: TransformerConfig, q_nope, q_rope, lp, cp,
                       block_tables, pos):
    """One new token a request against its cached rows, the expansion
    ABSORBED: q_nope / q_rope [B, H, dn / dr], cp [blocks, bs, pool row]
    with the new rows written. Returns [B, H * dv]."""
    B, H, _ = q_nope.shape
    R = cfg.kv_lora_rank
    wk, wv = _expansion(cfg, lp, q_nope)
    q = _lanes(jnp.concatenate(
        [jnp.einsum("bhd,rhd->bhr", q_nope, wk), q_rope], -1), cp.shape[-1])
    o = None
    if _use_flash(cfg):
        from deepspeed_tpu.ops.pallas.latent_decode_attention import \
            latent_decode_attention
        o = latent_decode_attention(q, cp, block_tables, pos, latent=R,
                                    scale=_scale(cfg))
        form = "latent_kernel"
    if o is None:
        form = "gather_einsum"
        c = cp[block_tables].reshape(B, -1, cp.shape[-1])      # [B, S, row]
        s = jnp.einsum("bhr,bsr->bhs", q, c,
                       preferred_element_type=jnp.float32) * _scale(cfg)
        kpos = jnp.arange(c.shape[1], dtype=jnp.int32)[None, None, :]
        s = jnp.where(kpos <= pos[:, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(c.dtype)
        o = jnp.einsum("bhs,bsr->bhr", p, c[..., :R])
    dispatch.record("latent_decode", form,
                    f"B={B} H={H} R={R} row={cp.shape[-1]} bs={cp.shape[1]}")
    return jnp.einsum("bhr,rhd->bhd", o, wv).reshape(B, -1)


def decode(cfg: TransformerConfig, x, lp, positions, pos, cp, block_tables):
    """One fused decode step over all running requests: x [B, 1, D], pos
    [B] cache depths, block_tables [B, max_blocks] (an inactive row's is
    zeroed: it writes the dummy block). Returns (out [B, 1, D], cp)."""
    B = x.shape[0]
    bs = cp.shape[1]
    q_nope, q_rope, rows = project(cfg, x, lp, positions)
    slots = block_tables[jnp.arange(B), pos // bs] * bs + pos % bs
    cp = _scatter(cp, rows, slots)
    with jax.named_scope("latent_decode"):
        out = absorbed_attention(cfg, q_nope[:, 0], q_rope[:, 0], lp, cp,
                                 block_tables, pos)[:, None]
    return out @ _w(lp["wo"], out), cp
