"""The plain reference: a pre-LayerNorm decoder in straight ``jax.numpy``.

float32 throughout under ``jax.default_matmul_precision("highest")``; no
kernels, no cache, no batching tricks, no scan. Written from the published
equations (Vaswani et al. 2017 with the pre-LN placement of GPT-2; ALiBi from
Press et al. 2022; BLOOM, BigScience 2022; OPT, Zhang et al. 2022), not from
``models/transformer.py``. It is fed the program's weights through the name
map of its configuration (``reference/maps/<config>.json``).

For one sequence ``t[0..S)``::

    x_0   = E[t] (+ P[0..S) for learned positions) ; x_0 = LN_emb(x_0) if the
            model has an embedding LayerNorm (BLOOM)
    a     = LN_1(x) ; q, k, v = a Wq + bq, a Wk + bk, a Wv + bv  (per head, hd)
    s_ij  = q_i . k_j / sqrt(hd)  (+ m_h (j - i) for ALiBi, m_h = 2^(-8 h / H),
            h = 1..H) ; j > i masked ; p = softmax_j(s)
    x     = x + (p v) Wo + bo
    x     = x + act(LN_2(x) W1 + b1) W2 + b2      act = ReLU (OPT) or tanh-GELU
    h     = LN_f(x_L) ; logits = h E^T (tied head)
    loss  = mean over i < S-1 of -log softmax(logits_i)[t_{i+1}]

Departures from the published models, each because the program under test
makes it (``models/presets.py``): OPT's position table is indexed from 0 (the
published checkpoints offset positions by 2 and hold 2,050 rows); dropout is
0; BLOOM's fused query-key-value matrix is held as three matrices.

Weights arrive one layer at a time (``weights.layer(l)``), so at published
sizes only one layer's float32 copy is alive beside the program's own state.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: heads whose S x S scores are alive at once (bounds the reference's memory)
HEAD_GROUP = 8


def _ln(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _act(kind, x):
    if kind == "relu":
        return jnp.maximum(x, 0.0)
    if kind == "gelu_tanh":
        return 0.5 * x * (1.0 + jnp.tanh(
            math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))
    raise ValueError(f"unknown activation {kind!r}")


def alibi_slopes(n_head: int):
    """Press et al.: a geometric sequence starting at 2^(-8/H), for H a power
    of two (BLOOM-560m: 16 heads)."""
    if n_head & (n_head - 1):
        raise ValueError("the reference holds ALiBi for a power-of-two head count only")
    return jnp.asarray([2.0 ** (-8.0 * (h + 1) / n_head) for h in range(n_head)],
                       jnp.float32)


def embed(cfg, top, tokens):
    """tokens [B, S] int -> x_0 [B, S, D]."""
    x = top["wte"][tokens]
    if cfg["positions"] == "learned":
        x = x + top["wpe"][: tokens.shape[1]][None]
    if cfg.get("embed_layernorm"):
        x = _ln(x, top["emb_ln_g"], top["emb_ln_b"], cfg["eps"])
    return x


def layer(cfg, w, x):
    """One decoder layer on x [B, S, D] with that layer's weights ``w``."""
    B, S, D = x.shape
    H = cfg["n_head"]
    hd = D // H
    a = _ln(x, w["ln1_g"], w["ln1_b"], cfg["eps"])
    q = (a @ w["wq"] + w["bq"]).reshape(B, S, H, hd)
    k = (a @ w["wk"] + w["bk"]).reshape(B, S, H, hd)
    v = (a @ w["wv"] + w["bv"]).reshape(B, S, H, hd)
    i = jnp.arange(S)[:, None]
    j = jnp.arange(S)[None, :]
    slopes = alibi_slopes(H) if cfg["positions"] == "alibi" else None
    outs = []
    for h0 in range(0, H, HEAD_GROUP):
        hs = slice(h0, min(h0 + HEAD_GROUP, H))
        s = jnp.einsum("bihd,bjhd->bhij", q[:, :, hs], k[:, :, hs]) / math.sqrt(hd)
        if slopes is not None:
            s = s + slopes[hs][None, :, None, None] * (j - i)[None, None]
        s = jnp.where((j > i)[None, None], -jnp.inf, s)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("bhij,bjhd->bihd", p, v[:, :, hs]))
    o = jnp.concatenate(outs, axis=2).reshape(B, S, D)
    x = x + o @ w["wo"] + w["bo"]
    m = _ln(x, w["ln2_g"], w["ln2_b"], cfg["eps"])
    return x + _act(cfg["activation"], m @ w["w1"] + w["b1"]) @ w["w2"] + w["b2"]


def final_hidden(cfg, weights, tokens):
    """h = LN_f(x_L) for tokens [B, S]; ``weights`` gives ``top()`` and
    ``layer(l)`` dicts of float32 arrays."""
    with jax.default_matmul_precision("highest"):
        top = weights.top()
        x = jax.jit(lambda t, tk: embed(cfg, t, tk))(top, tokens)
        step = jax.jit(lambda w, x: layer(cfg, w, x))
        for l in range(cfg["n_layer"]):
            x = step(weights.layer(l), x)
        return jax.jit(lambda x, g, b: _ln(x, g, b, cfg["eps"]))(
            x, top["lnf_g"], top["lnf_b"])


def logits_rows(cfg, weights, h_rows):
    """h_rows [N, D] -> logits [N, V] through the tied head."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda h, e: h @ e.T)(h_rows, weights.top()["wte"])


def next_token_loss(cfg, weights, tokens, vocab_block: int = 16384):
    """Mean next-token cross-entropy of tokens [B, S], the log-sum-exp taken
    in blocks over the vocabulary so that [B*S, V] never exists."""
    h = final_hidden(cfg, weights, tokens)
    B, S, D = h.shape
    hr = h[:, :-1].reshape(-1, D)
    labels = tokens[:, 1:].reshape(-1)
    wte = weights.top()["wte"]
    V = wte.shape[0]

    @jax.jit
    def block(hr, e, lab, lo, m, z, picked):
        lg = hr @ e.T                                   # [N, vb]
        m2 = jnp.maximum(m, lg.max(axis=-1))
        z = z * jnp.exp(m - m2) + jnp.exp(lg - m2[:, None]).sum(axis=-1)
        idx = lab - lo
        inside = (idx >= 0) & (idx < e.shape[0])
        got = jnp.take_along_axis(lg, jnp.clip(idx, 0, e.shape[0] - 1)[:, None],
                                  axis=-1)[:, 0]
        return m2, z, jnp.where(inside, got, picked)

    with jax.default_matmul_precision("highest"):
        m = jnp.full((hr.shape[0],), -jnp.inf, jnp.float32)
        z = jnp.zeros_like(m)
        picked = jnp.zeros_like(m)
        for lo in range(0, V, vocab_block):
            m, z, picked = block(hr, wte[lo:lo + vocab_block], labels,
                                 jnp.int32(lo), m, z, picked)
        return float(jnp.mean(m + jnp.log(z) - picked))
