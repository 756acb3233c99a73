"""The plain reference of the LFM2-MoE block (``model_type`` ``lfm2_moe``:
LFM2-24B-A2B): pre-RMSNorm, a stack whose layers are each a GATED SHORT
CONVOLUTION or a softmax GQA mixer (``layer_types`` of ``config.json``), the
first ``num_dense_layers`` of them with a dense gated-SiLU MLP and the others
with a mixture of gated-SiLU experts behind a sigmoid router with a selection
bias; no bias anywhere else; a final RMSNorm (the family's ``embedding_norm``)
and a head tied to the embedding. Straight ``jax.numpy``.

float32 throughout under ``jax.default_matmul_precision("highest")``; no
kernels, no cache, no sort: the conv runs over the whole sequence with zeros
on its left, and every expert is computed for every token, one expert at a
time, and a mask picks. Written from the model's ``config.json`` keys and the
family's published modelling code (``Lfm2MoeShortConv``, ``Lfm2MoeAttention``,
``Lfm2MoeSparseMoeBlock``), not from ``models/transformer.py``. It answers the
contract at the top of ``correctness.py`` and is fed the program's weights
through the name map of its configuration.

For one sequence ``t[0..S)``, with ``RMS(u; g) = u / sqrt(mean(u^2) + eps) * g``::

    x_0 = E[t];  every layer:  h = x + Mixer(RMS(x; g_1));  x = h + FFN(RMS(h; g_2))

    conv (layer_types[l] == "conv"; K = conv_L_cache taps):
      [B | C | x~] = a W_in                 three parts of d_model each
      u = B * x~                            elementwise
      c_t = sum_{j<K} w_j * u_{t-(K-1)+j}   per channel, u_{<0} = 0; no bias
      y = (C * c) W_out                     (and no activation)

    GQA (H query heads, KV key/value heads of hd; head i reads kv head i // (H/KV)):
      q, k, v = a Wq, a Wk, a Wv
      q, k = RMS_head(q; g_q), RMS_head(k; g_k)      over hd, one scale of hd
      q, k = rope(q), rope(k)    theta, the whole head, halves rotated:
          rope(x)_p = x * cos(p f) + [-x_hi | x_lo] * sin(p f),
          f_i = theta^(-2 i / hd) for i < hd / 2, repeated over both halves
      s_ij = q_i . k_j / sqrt(hd), j > i masked ;  y = (softmax_j(s) v) Wo

    FFN, l < n_dense_layer:  (silu(m Wg) * (m Wu)) Wd         width d_ff
    FFN, otherwise:  s = sigmoid(m Wr) over n_experts ;  top = the K largest of s + b
      w_e = s_e / (sum_{top} s + topk_eps) * routed_scaling for e in top, else 0
      y = sum over e in top of w_e (silu(m Wg_e) * (m Wu_e)) Wd_e   width d_expert

    out = RMS(x_L; g_f) ; logits = out E^T

THE CUT. ``cfg["layer_types"]`` and ``cfg["n_dense_layer"]`` are those of
the configuration as it is run (a pipeline stage: published layers 1-9, one
leading dense layer); the weights' tree is a ``lead`` of single layers and
then periods (``Weights``).

Departures, each noted because a reader comparing with the sources would trip
on it. The selection bias ``b`` (``expert_bias``) takes part in the choice
only. The 1e-6 of the normalisation is the family's code's. The router is
float32 here as everything is (the published code computes its logits in the
activations' type and casts the scores). No loss term for load balancing: the
bias does that; ``next_token_loss`` is the plain cross-entropy. The experts'
weights arrive in the type the program holds them in and are cast to float32
one expert at a time.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: the layer weights that are stacked over experts and stay as stored
EXPERT_STACKS = ("e_gate", "e_up", "e_down")


class Weights:
    """The program's parameter tree under the reference's names. The stack
    is a LEAD and periods: ``lead`` is a tuple of single layers (leading dim
    1), ``layers`` one group a position of the period, each stacked over the
    periods, so layer ``l`` is ``lead[l]`` or row ``(l - n_lead) // period``
    of group ``(l - n_lead) % period``; named through the map's
    ``conv_layer`` or ``attn_layer`` and its ``dense_mlp`` or ``moe_mlp``,
    by what the group holds. float32, one layer at a time, but for the
    expert stacks [E, ., .], which stay in the stored type until
    :func:`expert` casts one expert's matrices."""

    def __init__(self, params, name_map: dict, device=None):
        self.params, self.map = params, name_map
        self.device = device or jax.devices()[0]
        self._top = None
        self.lead = params.get(name_map["lead_root"], ())
        self.groups = params[name_map["layers_root"]]

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
        n_lead, period = len(self.lead), len(self.groups)
        if l < n_lead:
            group, row = self.lead[l], 0
        else:
            group, row = self.groups[(l - n_lead) % period], \
                (l - n_lead) // period
        names = {**self.map["attn_layer" if "attn" in group else "conv_layer"],
                 **self.map["moe_mlp" if "gate_w" in group["mlp"]
                            else "dense_mlp"]}
        out = {}
        for k, p in names.items():
            a = jax.device_put(self._get(group, p)[row], self.device)
            out[k] = a if k in EXPERT_STACKS else a.astype(jnp.float32)
        return out


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def rope(x, theta: float):
    """x [B, S, heads, hd] at positions 0..S-1: the whole head, its two
    halves rotated against each other."""
    S, hd = x.shape[1], x.shape[-1]
    f = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * f[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[None, :, None, :]
    lo, hi = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-hi, lo], axis=-1) * sin


def gqa(cfg, w, a):
    """a [B, S, D] (normed) -> the grouped-query attention's output."""
    B, S, D = a.shape
    H, KV, hd = cfg["n_head"], cfg["n_kv_head"], cfg["head_dim"]
    theta = float(cfg["rope_parameters"]["rope_theta"])
    q = _rms((a @ w["wq"]).reshape(B, S, H, hd), w["q_g"], cfg["eps"])
    k = _rms((a @ w["wk"]).reshape(B, S, KV, hd), w["k_g"], cfg["eps"])
    q = rope(q, theta).reshape(B, S, KV, H // KV, hd)
    k = rope(k, theta)
    v = (a @ w["wv"]).reshape(B, S, KV, hd)
    future = jnp.arange(S)[None, :] > jnp.arange(S)[:, None]
    outs = []
    for c in range(KV):                       # one kv head's group at a time
        s = jnp.einsum("bigd,bjd->bgij", q[:, :, c], k[:, :, c]) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(future[None, None], -jnp.inf, s), axis=-1)
        outs.append(jnp.einsum("bgij,bjd->bigd", p, v[:, :, c]))
    return jnp.stack(outs, axis=2).reshape(B, S, H * hd) @ w["wo"]


def short_conv(cfg, w, a):
    """a [B, S, D] (normed) -> the gated short convolution's output: the
    conv over the whole sequence, zeros before it."""
    K, S = cfg["conv_kernel"], a.shape[1]
    b, c, xs = jnp.split(a @ w["w_in"], 3, axis=-1)
    u = jnp.pad(b * xs, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(u[:, j:j + S] * w["conv"][j] for j in range(K))
    return (c * conv) @ w["w_out"]


def route(cfg, w, m):
    """m [B, S, D] (normed) -> c [B, S, n_experts]: each token's weight for
    the K experts it takes, else 0."""
    s = jax.nn.sigmoid(m @ w["router"])
    _, top = jax.lax.top_k(s + w["expert_bias"], cfg["experts_per_token"])
    chosen = jnp.sum(jax.nn.one_hot(top, cfg["n_experts"], dtype=s.dtype), axis=-2)
    c = s * chosen
    if cfg.get("norm_topk_prob", True):
        c = c / (jnp.sum(c, axis=-1, keepdims=True) + cfg["topk_eps"])
    return c * cfg.get("routed_scaling", 1.0)


def expert(m, c_e, w_gate, w_up, w_down):
    """One gated-SiLU MLP over every token, weighted by that token's c_e (0
    for a token that did not choose it)."""
    w_gate, w_up, w_down = (a.astype(jnp.float32) for a in (w_gate, w_up, w_down))
    return c_e[..., None] * ((jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down)


class _Cfg(dict):
    """A configuration jit can take as a static argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def _mix(cfg, w, x, kind: str):
    """x [B, S, D] -> (h = x + the layer's mixer of RMS(x), m = RMS(h))."""
    mixer = short_conv if kind == "conv" else gqa
    h = x + mixer(cfg, w, _rms(x, w["ln1_g"], cfg["eps"]))
    return h, _rms(h, w["ln2_g"], cfg["eps"])


_mix = jax.jit(_mix, static_argnums=(0, 3))
_route = jax.jit(route, static_argnums=0)
_expert = jax.jit(expert)


def layer(cfg, w, x, l: int):
    """Layer ``l`` on x [B, S, D]."""
    cfg = _Cfg(cfg)
    kind = cfg["layer_types"][l]
    if ("w_in" in w) != (kind == "conv"):
        raise ValueError(f"layer {l} is {kind!r} in the configuration and "
                         "not in the weights")
    dense = l < cfg["n_dense_layer"]
    if dense != ("w_gate" in w):
        raise ValueError(f"layer {l}: the configuration's num_dense_layers "
                         "and the weights disagree on its MLP")
    small = {k: v for k, v in w.items() if k not in EXPERT_STACKS}
    h, m = _mix(cfg, small, x, kind)
    if dense:
        return h + _expert(m, jnp.ones(m.shape[:-1], m.dtype), w["w_gate"],
                           w["w_up"], w["w_down"])
    c = _route(cfg, small, m)
    for e in range(cfg["n_experts"]):
        h = h + _expert(m, c[..., e], w["e_gate"][e], w["e_up"][e],
                        w["e_down"][e])
    return h


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
    """h_rows [N, D] -> logits [N, V] through the tied head."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda h, e: h @ e.T)(h_rows, weights.top()["wte"])


def next_token_loss(cfg, weights, tokens):
    """Mean next-token cross-entropy of tokens [B, S] (no auxiliary term:
    module docstring)."""
    h = final_hidden(cfg, weights, tokens)
    logits = logits_rows(cfg, weights, h[:, :-1].reshape(-1, h.shape[-1]))
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:].reshape(-1, 1), axis=-1)
    return float(-jnp.mean(picked))
