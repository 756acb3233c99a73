"""The plain reference of the OLMoE block: pre-RMSNorm, query/key RMSNorm,
rotary positions, a mixture of gated-SiLU experts in every layer, an untied
head. Straight ``jax.numpy``.

float32 throughout under ``jax.default_matmul_precision("highest")``; no
kernels, no cache, no sort, no grouped matmul: every expert is computed for
every token, one expert at a time, and a mask picks. Written from the
published description (OLMoE, Muennighoff et al. 2024, section 2 and the
``olmoe`` model type's ``config.json`` keys; rotary embeddings, Su et al.
2021), not from ``models/moe_lm.py``. It answers the contract at the top of
``correctness.py`` and is fed the program's weights through the name map of
its configuration.

For one sequence ``t[0..S)``, with ``RMS(u; g) = u / sqrt(mean(u^2) + eps) * g``::

    x_0   = E[t]
    a     = RMS(x; g_1)
    q, k  = RMS(a Wq; g_q), RMS(a Wk; g_k)   the norm over the WHOLE projection
            (all heads together, H*hd wide), before the split into heads
    v     = a Wv
    q, k  : position m turns each head, pair (i, i + hd/2):
            (u_i, u_{i+hd/2}) -> (u_i cos m th_i - u_{i+hd/2} sin m th_i,
                                  u_{i+hd/2} cos m th_i + u_i sin m th_i),
            th_i = theta^(-2 i / hd), i = 0..hd/2
    s_ij  = q_i . k_j / sqrt(hd) ; j > i masked ; p = softmax_j(s)
    h     = x + (p v) Wo
    m     = RMS(h; g_2)
    r     = softmax(m Wr)                    over the E experts
    top   = the K experts of largest r ; c_e = r_e for e in top, else 0
            (the K values as they are; divided by their sum only under
            ``norm_topk_prob``, which OLMoE does not set)
    x     = h + sum_e c_e * ( silu(m Wgate_e) * (m Wup_e) ) Wdown_e
    out   = RMS(x_L; g_f) ; logits = out W_head (its own matrix, no bias)
    loss  = mean over i < S-1 of -log softmax(logits_i)[t_{i+1}]
            + aux_loss_coef * mean over layers of  E * sum_e f_e * P_e
            f_e = expert e's share of the N*K assignments of the batch's N
            tokens, P_e = mean over those tokens of r_e

No bias anywhere, no shared expert, no capacity: no token is dropped.
``cfg`` holds ``n_layer``, ``n_head``, ``d_model``, ``eps``, ``rope_theta``,
``n_experts``, ``experts_per_token``, ``d_expert``, ``aux_loss_coef`` and
optionally ``norm_topk_prob``.

Departures, each noted because a reader comparing with the sources would
trip on it. The load-balancing term is the one OLMoE was trained with
(megablocks: ``f_e`` a share of assignments, so the term is 1 when routing
is uniform); the ``transformers`` port computes it over all layers' tokens
at once and without the division by K. The published ``config.json`` carries
no coefficient: 0.01 is the paper's, given by the map as a constant. The
router z-loss of the paper (0.001) is not part of the program's loss and not
here. The experts' weights arrive in the type the program holds them in and
are cast to float32 one expert at a time (``Weights`` below): a whole layer
of them in float32 is 1.6 GB beside a serving engine that fills the chip.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: heads whose S x S scores are alive at once (bounds the reference's memory)
HEAD_GROUP = 8
#: the layer weights that are stacked over experts and stay as stored
EXPERT_STACKS = ("w_gate", "w_up", "w_down")


class Weights:
    """The program's parameter tree under the reference's names: float32, one
    layer at a time, but for the three expert stacks [E, ., .], which stay in
    the stored type until :func:`expert` casts one expert's matrices."""

    def __init__(self, params, name_map: dict, device=None):
        self.params, self.map = params, name_map
        self.device = device or jax.devices()[0]
        self._top = None

    def _get(self, path: str):
        node = self.params
        for part in path.split("/"):
            node = node[part]
        return node

    def top(self) -> dict:
        if self._top is None:
            self._top = {
                k: jax.device_put(self._get(p), self.device).astype(jnp.float32)
                for k, p in self.map["top"].items()}
        return self._top

    def layer(self, l: int) -> dict:
        out = {}
        for k, p in self.map["layer"].items():
            a = jax.device_put(self._get(p)[l], self.device)
            out[k] = a if k in EXPERT_STACKS else a.astype(jnp.float32)
        return out


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def rotate(u, theta: float):
    """u [B, S, H, hd] at positions 0..S, every head turned whole."""
    S, hd = u.shape[1], u.shape[3]
    half = hd // 2
    th = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * th[None, :]    # [S, half]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = u[..., :half], u[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(cfg, w, x):
    """x [B, S, D] -> x + Attn(RMS(x))."""
    B, S, D = x.shape
    H = cfg["n_head"]
    hd = D // H
    a = _rms(x, w["ln1_g"], cfg["eps"])
    q = _rms(a @ w["wq"], w["q_g"], cfg["eps"]).reshape(B, S, H, hd)
    k = _rms(a @ w["wk"], w["k_g"], cfg["eps"]).reshape(B, S, H, hd)
    v = (a @ w["wv"]).reshape(B, S, H, hd)
    q, k = rotate(q, cfg["rope_theta"]), rotate(k, cfg["rope_theta"])
    future = jnp.arange(S)[None, :] > jnp.arange(S)[:, None]
    outs = []
    for h0 in range(0, H, HEAD_GROUP):
        hs = slice(h0, min(h0 + HEAD_GROUP, H))
        s = jnp.einsum("bihd,bjhd->bhij", q[:, :, hs], k[:, :, hs]) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(future[None, None], -jnp.inf, s), axis=-1)
        outs.append(jnp.einsum("bhij,bjhd->bihd", p, v[:, :, hs]))
    return x + jnp.concatenate(outs, axis=2).reshape(B, S, D) @ w["wo"]


def route(cfg, w, h):
    """h [B, S, D] -> (m = RMS(h), r [B, S, E] the softmax over experts,
    c [B, S, E] each token's weight for the K experts it takes, else 0)."""
    m = _rms(h, w["ln2_g"], cfg["eps"])
    r = jax.nn.softmax(m @ w["router"], axis=-1)
    _, top = jax.lax.top_k(r, cfg["experts_per_token"])
    chosen = jnp.sum(jax.nn.one_hot(top, cfg["n_experts"], dtype=r.dtype), axis=-2)
    c = r * chosen
    if cfg.get("norm_topk_prob"):
        c = c / jnp.sum(c, axis=-1, keepdims=True)
    return m, r, c


def expert(m, c_e, w_gate, w_up, w_down):
    """One expert over every token, weighted by that token's c_e (0 for a
    token that did not choose it)."""
    w_gate, w_up, w_down = (a.astype(jnp.float32) for a in (w_gate, w_up, w_down))
    return c_e[..., None] * ((jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down)


_attention = jax.jit(attention, static_argnums=0)
_route = jax.jit(route, static_argnums=0)
_expert = jax.jit(expert)


class _Cfg(dict):
    """A configuration jit can take as a static argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def layer(cfg, w, x):
    """One layer on x [B, S, D]: (new x, r [B, S, E], c [B, S, E])."""
    cfg = _Cfg(cfg)
    attn_w = {k: v for k, v in w.items() if k not in EXPERT_STACKS}
    h = _attention(cfg, attn_w, x)
    m, r, c = _route(cfg, attn_w, h)
    out = h
    for e in range(cfg["n_experts"]):
        out = out + _expert(m, c[..., e], w["w_gate"][e], w["w_up"][e],
                            w["w_down"][e])
    return out, r, c


def _hidden(cfg, weights, tokens):
    """(RMS_f(x_L), the load-balancing term averaged over the layers)."""
    with jax.default_matmul_precision("highest"):
        top = weights.top()
        x = top["wte"][tokens]
        E, K = cfg["n_experts"], cfg["experts_per_token"]
        aux = 0.0
        for l in range(cfg["n_layer"]):
            x, r, c = layer(cfg, weights.layer(l), x)
            n = r.shape[0] * r.shape[1]
            f = jnp.sum(c > 0, axis=(0, 1)) / (n * K)
            aux = aux + E * jnp.sum(f * jnp.mean(r, axis=(0, 1)))
        return _rms(x, top["lnf_g"], cfg["eps"]), aux / cfg["n_layer"]


def final_hidden(cfg, weights, tokens):
    """RMS_f(x_L) for tokens [B, S]; ``weights`` gives ``top()`` and
    ``layer(l)`` dicts under the map's names."""
    return _hidden(cfg, weights, tokens)[0]


def logits_rows(cfg, weights, h_rows):
    """h_rows [N, D] -> logits [N, V] through the untied head [D, V]."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(jnp.matmul)(h_rows, weights.top()["head"])


def loss_value(cfg, weights, tokens, vocab_block: int = 16384):
    """:func:`next_token_loss` as an array, so that ``jax.grad`` can be taken
    of it over a ``Weights`` built from traced parameters."""
    h, aux = _hidden(cfg, weights, tokens)
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
        return jnp.mean(m + jnp.log(z) - picked) + cfg["aux_loss_coef"] * aux


def next_token_loss(cfg, weights, tokens):
    """Mean next-token cross-entropy of tokens [B, S] plus ``aux_loss_coef``
    times the load-balancing term (module docstring); the log-sum-exp taken
    in blocks over the head's columns so that [B*S, V] never exists."""
    return float(loss_value(cfg, weights, tokens))
