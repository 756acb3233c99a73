"""The plain reference of the Solar-Open2 block: pre-RMSNorm, no positions
anywhere, periods of one gated softmax GQA layer and three KDA layers (the
channel-wise gated delta rule), and in every layer a mixture of gated-SiLU
experts with a sigmoid router and a shared expert; an untied head. Straight
``jax.numpy``.

float32 throughout under ``jax.default_matmul_precision("highest")``; no
kernels, no cache, no chunks, no sort: the recurrence runs TOKEN BY TOKEN, and
every expert held here is computed for every token, one expert at a time, and
a mask picks. Written from the model's ``config.json`` keys and the published
descriptions of its parts (Kimi Linear, Kimi Team 2025, section 3: KDA; Gated
Delta Networks, Yang et al. 2024; DeepSeek-V3, section 2.1.2: sigmoid scores
with a selection bias; GLM-4-MoE / ``solar_open`` model code for the
normalisation), not from ``models/transformer.py``. It answers the contract
at the top of ``correctness.py`` and is fed the program's weights through the
name map of its configuration.

For one sequence ``t[0..S)``, with ``RMS(u; g) = u / sqrt(mean(u^2) + eps) * g``::

    x_0 = E[t];  every layer:  h = x + Mixer(RMS(x; g_1));  x = h + MoE(RMS(h; g_2))
    layer l is a GQA layer if l % (gqa_interval + 1) == 0, else a KDA layer

    GQA (H query heads, KV key/value heads of hd; head i reads kv head i // (H/KV)):
      q, k, v = a Wq, a Wk, a Wv ;  s_ij = q_i . k_j / sqrt(hd), j > i masked
      y = ((softmax_j(s) v) * sigmoid(a Wgate)) Wo

    KDA (Hl heads, dk = dv; per head, per token t; S_0 = 0 in R^{dk x dv}):
      q, k, v = silu(conv(a Wq)), silu(conv(a Wk)), silu(conv(a Wv))
          conv(u)_t = sum_{j<K} c_j * u_{t-(K-1)+j}  per channel, u_{<0} = 0
      qh = q / sqrt(|q|^2 + 1e-6) / sqrt(dk) ;  kh = k / sqrt(|k|^2 + 1e-6)
      g  = -exp(A_log) * softplus((a Wf1) Wf2 + dt_bias)    in R^dk
      beta = beta_scale * sigmoid(a Wb)                     a scalar a head
      S' = diag(exp(g)) S_{t-1} ;  S_t = S' + beta * kh (v - S'^T kh)^T
      o  = S_t^T qh ;  y = (RMS(o; g_o) * sigmoid((a Wg1) Wg2 + bg)) Wo

    MoE:  s = sigmoid(m Wr) over all n_experts ;  top = the K largest of s + b
      w_e = s_e / (sum_{top} s + 1e-20) * routed_scaling for e in top, else 0
      y = sum over e in top AND held here of w_e FFN_e(m)  +  FFN_shared(m)
      FFN(m) = (silu(m Wgate) * (m Wup)) Wdown
    out = RMS(x_L; g_f) ; logits = out W_head

THE SHARE. ``cfg["experts_held"]`` experts from ``cfg["expert_offset"]`` on are
held (one chip of an expert-parallel layer): the router scores all
``n_experts``, a token's weights are normalised over ALL its K choices, and
what the experts held elsewhere would add is left out; that partial sum (plus
the shared expert, which every chip computes alike) goes on to the next layer.
The head is the slice of the vocabulary the weights hold.

Departures, each noted because a reader comparing with the sources would trip
on it. The two 1e-6 under the square roots of qh and kh and the 1e-20 in the
weights' normalisation are the family's code's, not the papers'. ``beta_scale``
2 is ``kda_allow_neg_eigval``. The selection bias ``b`` takes part in the
choice only. No loss term for load balancing: the model is trained without
one (the bias does that), and this share cannot see the other chips' loads;
``next_token_loss`` is the plain cross-entropy over the held slice of the
vocabulary. The experts' weights arrive in the type the program holds them in
and are cast to float32 one expert at a time.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: the layer weights that are stacked over experts and stay as stored
EXPERT_STACKS = ("w_gate", "w_up", "w_down")


def is_gqa(cfg, l: int) -> bool:
    return l % (cfg["gqa_interval"] + 1) == 0


class Weights:
    """The program's parameter tree under the reference's names. The stack
    is not one leading axis: ``layers`` is one group a position of the
    period, each stacked over the periods, so layer ``l`` is row ``l //
    period`` of group ``l % period``, named through the map's ``gqa_layer``
    or ``kda_layer`` and its ``moe_layer``. float32, one layer at a time,
    but for the expert stacks [E, ., .], which stay in the stored type
    until :func:`expert` casts one expert's matrices."""

    def __init__(self, params, name_map: dict, device=None):
        self.params, self.map = params, name_map
        self.device = device or jax.devices()[0]
        self._top = None
        self.period = len(params[name_map["layers_root"]])

    @staticmethod
    def _get(node, path: str):
        for part in path.split("/"):
            node = node[part]
        return node

    def top(self) -> dict:
        if self._top is None:
            self._top = {
                k: jax.device_put(self._get(self.params, p), self.device)
                .astype(jnp.float32) for k, p in self.map["top"].items()}
        return self._top

    def layer(self, l: int) -> dict:
        group = self.params[self.map["layers_root"]][l % self.period]
        kind = "gqa_layer" if "attn" in group else "kda_layer"
        out = {}
        for k, p in {**self.map[kind], **self.map["moe_layer"]}.items():
            a = jax.device_put(self._get(group, p)[l // self.period], self.device)
            out[k] = a if k in EXPERT_STACKS else a.astype(jnp.float32)
        return out


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def gqa(cfg, w, x):
    """x [B, S, D] -> x + the gated grouped-query attention of RMS(x)."""
    B, S, D = x.shape
    H, KV, hd = cfg["n_head"], cfg["n_kv_head"], cfg["head_dim"]
    a = _rms(x, w["ln1_g"], cfg["eps"])
    q = (a @ w["wq"]).reshape(B, S, KV, H // KV, hd)
    k = (a @ w["wk"]).reshape(B, S, KV, hd)
    v = (a @ w["wv"]).reshape(B, S, KV, hd)
    future = jnp.arange(S)[None, :] > jnp.arange(S)[:, None]
    outs = []
    for c in range(KV):                       # one kv head's group at a time
        s = jnp.einsum("bigd,bjd->bgij", q[:, :, c], k[:, :, c]) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(future[None, None], -jnp.inf, s), axis=-1)
        outs.append(jnp.einsum("bgij,bjd->bigd", p, v[:, :, c]))
    o = jnp.stack(outs, axis=2).reshape(B, S, H * hd)
    return x + (o * jax.nn.sigmoid(a @ w["w_gate_attn"])) @ w["wo"]


def conv(u, c):
    """u [B, S, C], c [K, C]: the causal depthwise conv, tap K-1 on the
    current input, zeros before the sequence."""
    K, S = c.shape[0], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(padded[:, j:j + S] * c[j] for j in range(K))


def kda(cfg, w, x):
    """x [B, S, D] -> x + the KDA mixer of RMS(x), the state carried token
    by token from zero."""
    B, S, D = x.shape
    H, dk = cfg["lin_heads"], cfg["lin_head_dim"]
    C = H * dk
    a = _rms(x, w["ln1_g"], cfg["eps"])
    cq, ck, cv = w["conv"][:, :C], w["conv"][:, C:2 * C], w["conv"][:, 2 * C:]
    heads = lambda u: u.reshape(B, S, H, dk)               # noqa: E731
    q = heads(jax.nn.silu(conv(a @ w["wq"], cq)))
    k = heads(jax.nn.silu(conv(a @ w["wk"], ck)))
    v = heads(jax.nn.silu(conv(a @ w["wv"], cv)))
    qh = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) / math.sqrt(dk)
    kh = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    g = -jnp.exp(w["A_log"])[:, None] * jax.nn.softplus(
        heads((a @ w["wf1"]) @ w["wf2"] + w["dt_bias"]))
    beta = cfg["beta_scale"] * jax.nn.sigmoid(a @ w["wb"])     # [B, S, H]

    def token(state, xs):
        qt, kt, vt, gt, bt = xs                    # [B, H, dk] ... [B, H]
        s1 = jnp.exp(gt)[..., None] * state
        u = vt - jnp.einsum("bhkv,bhk->bhv", s1, kt)
        state = s1 + bt[..., None, None] * kt[..., None] * u[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt)

    seq = lambda u: jnp.moveaxis(u, 1, 0)                  # noqa: E731
    _, o = jax.lax.scan(token, jnp.zeros((B, H, dk, dk), jnp.float32),
                        (seq(qh), seq(kh), seq(v), seq(g), seq(beta)))
    o = _rms(jnp.moveaxis(o, 0, 1), w["o_g"], cfg["eps"]).reshape(B, S, C)
    gate = jax.nn.sigmoid((a @ w["wg1"]) @ w["wg2"] + w["bg"])
    return x + (o * gate) @ w["wo"]


def route(cfg, w, h):
    """h [B, S, D] -> (m = RMS(h), c [B, S, n_experts]: each token's weight
    for the K experts it takes, normalised over all K, else 0)."""
    m = _rms(h, w["ln2_g"], cfg["eps"])
    s = jax.nn.sigmoid(m @ w["router"])
    _, top = jax.lax.top_k(s + w["b_select"], cfg["experts_per_token"])
    chosen = jnp.sum(jax.nn.one_hot(top, cfg["n_experts"], dtype=s.dtype), axis=-2)
    c = s * chosen
    if cfg.get("norm_topk_prob", True):
        c = c / (jnp.sum(c, axis=-1, keepdims=True) + 1e-20)
    return m, c * cfg.get("routed_scaling", 1.0)


def expert(m, c_e, w_gate, w_up, w_down):
    """One expert over every token, weighted by that token's c_e (0 for a
    token that did not choose it; all ones for the shared expert)."""
    w_gate, w_up, w_down = (a.astype(jnp.float32) for a in (w_gate, w_up, w_down))
    return c_e[..., None] * ((jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down)


class _Cfg(dict):
    """A configuration jit can take as a static argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


_gqa = jax.jit(gqa, static_argnums=0)
_kda = jax.jit(kda, static_argnums=0)
_route = jax.jit(route, static_argnums=0)
_expert = jax.jit(expert)


def layer(cfg, w, x, l: int):
    """Layer ``l`` on x [B, S, D]."""
    cfg = _Cfg(cfg)
    small = {k: v for k, v in w.items() if k not in EXPERT_STACKS}
    h = (_gqa if is_gqa(cfg, l) else _kda)(cfg, small, x)
    m, c = _route(cfg, small, h)
    out = h + _expert(m, jnp.ones(m.shape[:-1], m.dtype), w["shared_gate"],
                      w["shared_up"], w["shared_down"])
    first = cfg["expert_offset"]
    for e in range(cfg["experts_held"]):
        out = out + _expert(m, c[..., first + e], w["w_gate"][e], w["w_up"][e],
                            w["w_down"][e])
    return out


def final_hidden(cfg, weights, tokens):
    """RMS_f(x_L) for tokens [B, S]; ``weights`` gives ``top()`` and
    ``layer(l)`` dicts under the map's names."""
    with jax.default_matmul_precision("highest"):
        top = weights.top()
        x = top["wte"][tokens]
        for l in range(cfg["n_layer"]):
            x = layer(cfg, weights.layer(l), x, l)
        return _rms(x, top["lnf_g"], cfg["eps"])


def logits_rows(cfg, weights, h_rows):
    """h_rows [N, D] -> logits [N, V] through the untied head [D, V]."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(jnp.matmul)(h_rows, weights.top()["head"])


def next_token_loss(cfg, weights, tokens):
    """Mean next-token cross-entropy of tokens [B, S] over the held slice of
    the vocabulary (no auxiliary term: module docstring)."""
    h = final_hidden(cfg, weights, tokens)
    with jax.default_matmul_precision("highest"):
        logits = h[:, :-1] @ weights.top()["head"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
        return float(-jnp.mean(picked))
