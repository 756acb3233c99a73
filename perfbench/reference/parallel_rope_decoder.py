"""The plain reference of the GPT-NeoX block: rotary positions, attention and
MLP in parallel on one residual, an untied head. Straight ``jax.numpy``.

float32 throughout under ``jax.default_matmul_precision("highest")``; no
kernels, no cache, no scan. Written from the published equations (GPT-NeoX-20B,
Black et al. 2022, section 2.1; rotary embeddings, RoFormer, Su et al. 2021,
section 3.4.2), not from ``models/transformer.py``. It answers the contract at
the top of ``correctness.py`` and is fed the program's weights through the
name map of its configuration.

For one sequence ``t[0..S)``::

    x_0   = E[t]
    a     = LN_1(x) ; q, k, v = a Wq + bq, a Wk + bk, a Wv + bv  (per head, hd)
    q, k  : position m turns the first R dims of each head, pair (i, i + R/2):
            (u_i, u_{i+R/2}) -> (u_i cos m th_i - u_{i+R/2} sin m th_i,
                                 u_{i+R/2} cos m th_i + u_i sin m th_i),
            th_i = theta^(-2 i / R), i = 0..R/2 ; dims R..hd pass through
    s_ij  = q_i . k_j / sqrt(hd) ; j > i masked ; p = softmax_j(s)
    x     = x + (p v) Wo + bo + act(LN_2(x) W1 + b1) W2 + b2
            (both branches read the same x: eq. of section 2.1.2)
    h     = LN_f(x_L) ; logits = h W_head (its own matrix, no bias)
    loss  = mean over i < S-1 of -log softmax(logits_i)[t_{i+1}]

The loss has no auxiliary term. ``cfg`` holds ``n_layer``, ``n_head``,
``d_model``, ``eps``, ``rope_theta``, ``activation`` (``gelu_tanh``, the 20B
model's ``gelu_fast``; ``gelu_exact``, Pythia's) and optionally ``rope_dim``
(R; the whole head where absent).

Departures from the published models, each noted because a reader comparing
with the papers would trip on it: RoFormer pairs neighbouring dims (2i,
2i+1); GPT-NeoX's code, and every checkpoint trained with it, pairs (i,
i + R/2), which is what is written above. The 20B model turns a quarter of
each head (``rotary_pct`` 0.25); ``models/presets.py gpt_neox`` turns the
whole head, so its maps give no ``rope_dim``. The fused query-key-value
matrix is held as three; dropout is 0.
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
    if kind == "gelu_tanh":
        return 0.5 * x * (1.0 + jnp.tanh(
            math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))
    if kind == "gelu_exact":
        return 0.5 * x * (1.0 + jax.scipy.special.erf(x / math.sqrt(2.0)))
    raise ValueError(f"unknown activation {kind!r}")


def rotate(u, theta: float, rope_dim: int):
    """u [B, S, H, hd] at positions 0..S: the first ``rope_dim`` dims of each
    head turned, the rest untouched."""
    S, half = u.shape[1], rope_dim // 2
    th = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / rope_dim)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * th[None, :]    # [S, half]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b, rest = u[..., :half], u[..., half:rope_dim], u[..., rope_dim:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], axis=-1)


def layer(cfg, w, x):
    """One layer on x [B, S, D] with that layer's weights ``w``."""
    B, S, D = x.shape
    H = cfg["n_head"]
    hd = D // H
    R = cfg.get("rope_dim") or hd
    a = _ln(x, w["ln1_g"], w["ln1_b"], cfg["eps"])
    q = rotate((a @ w["wq"] + w["bq"]).reshape(B, S, H, hd), cfg["rope_theta"], R)
    k = rotate((a @ w["wk"] + w["bk"]).reshape(B, S, H, hd), cfg["rope_theta"], R)
    v = (a @ w["wv"] + w["bv"]).reshape(B, S, H, hd)
    future = jnp.arange(S)[None, :] > jnp.arange(S)[:, None]
    outs = []
    for h0 in range(0, H, HEAD_GROUP):
        hs = slice(h0, min(h0 + HEAD_GROUP, H))
        s = jnp.einsum("bihd,bjhd->bhij", q[:, :, hs], k[:, :, hs]) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(future[None, None], -jnp.inf, s), axis=-1)
        outs.append(jnp.einsum("bhij,bjhd->bihd", p, v[:, :, hs]))
    attn = jnp.concatenate(outs, axis=2).reshape(B, S, D) @ w["wo"] + w["bo"]
    m = _ln(x, w["ln2_g"], w["ln2_b"], cfg["eps"])
    mlp = _act(cfg["activation"], m @ w["w1"] + w["b1"]) @ w["w2"] + w["b2"]
    return x + attn + mlp


def final_hidden(cfg, weights, tokens):
    """h = LN_f(x_L) for tokens [B, S]; ``weights`` gives ``top()`` and
    ``layer(l)`` dicts of float32 arrays."""
    with jax.default_matmul_precision("highest"):
        top = weights.top()
        x = top["wte"][tokens]
        step = jax.jit(lambda w, x: layer(cfg, w, x))
        for l in range(cfg["n_layer"]):
            x = step(weights.layer(l), x)
        return jax.jit(lambda x, g, b: _ln(x, g, b, cfg["eps"]))(
            x, top["lnf_g"], top["lnf_b"])


def logits_rows(cfg, weights, h_rows):
    """h_rows [N, D] -> logits [N, V] through the untied head [D, V]."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(jnp.matmul)(h_rows, weights.top()["head"])


def next_token_loss(cfg, weights, tokens, vocab_block: int = 16384):
    """Mean next-token cross-entropy of tokens [B, S], the log-sum-exp taken
    in blocks over the head's columns so that [B*S, V] never exists."""
    h = final_hidden(cfg, weights, tokens)
    hr = h[:, :-1].reshape(-1, h.shape[-1])
    labels = tokens[:, 1:].reshape(-1)
    head = weights.top()["head"]

    @jax.jit
    def block(hr, cols, lab, lo, m, z, picked):
        lg = hr @ cols                                  # [N, vb]
        m2 = jnp.maximum(m, lg.max(axis=-1))
        z = z * jnp.exp(m - m2) + jnp.exp(lg - m2[:, None]).sum(axis=-1)
        idx = lab - lo
        inside = (idx >= 0) & (idx < cols.shape[1])
        got = jnp.take_along_axis(
            lg, jnp.clip(idx, 0, cols.shape[1] - 1)[:, None], axis=-1)[:, 0]
        return m2, z, jnp.where(inside, got, picked)

    with jax.default_matmul_precision("highest"):
        m = jnp.full((hr.shape[0],), -jnp.inf, jnp.float32)
        z = jnp.zeros_like(m)
        picked = jnp.zeros_like(m)
        for lo in range(0, head.shape[1], vocab_block):
            m, z, picked = block(hr, head[:, lo:lo + vocab_block], labels,
                                 jnp.int32(lo), m, z, picked)
        return float(jnp.mean(m + jnp.log(z) - picked))
