"""The plain reference of the LongCat-Flash layer (``meituan-longcat/LongCat-
Flash-*``; here the language model of LongCat-Flash-Omni): two sub-blocks of
multi-head LATENT attention and a dense gated-SiLU MLP around a SHORTCUT
mixture of experts whose router also scores ZERO-COMPUTE experts. Straight
``jax.numpy``.

float32 throughout under ``jax.default_matmul_precision("highest")``; no
kernels, no cache, and the NON-absorbed form only: every position's latent is
expanded to its heads' keys and values and attention runs over those. Written
from the model's ``config.json`` keys and the equations of the family's
published code (``LongcatFlashMLA``, ``LongcatFlashTopkRouter``,
``LongcatFlashMoE``, ``LongcatFlashDecoderLayer``), not from
``models/latent_attention.py`` or ``models/moe_lm.py``. It is tied to the
published code by ``tests/unit/test_longcat_flash.py``, which holds it to
``transformers``' ``LongcatFlashForCausalLM`` on a small random model. It
answers the contract at the top of ``correctness.py`` and is fed the
program's weights through the name map of its configuration.

For one sequence ``t[0..S)``, ``N(u; g, e) = u / sqrt(mean(u^2) + e) * g``::

    x = E[t]
    a published layer:
      a0 = x  + MLA_0(N(x;  g1_0, eps));  h0 = N(a0; g2_0, eps)
      s  = MoE(h0)                                       the shortcut
      x1 = a0 + MLP_0(h0)
      a1 = x1 + MLA_1(N(x1; g1_1, eps));  h1 = N(a1; g2_1, eps)
      x  = a1 + MLP_1(h1) + s
    MLP(m) = (silu(m Wg) * (m Wu)) Wd

    MLA(h), H heads, ranks Q and R, head sizes dn (no position), dr (roped), dv:
      cq = N(h Wqa; gq, 1e-6);  q = (cq Wqb) * sqrt(D / Q)   -> H x (dn + dr)
      [ckv | kr] = h Wkva;  ckv = N(ckv; gkv, 1e-6) * sqrt(D / R);  kr ONE head
      rope(theta) on q's last dr and on kr, pairs (2i, 2i + 1) by angle
        pos * theta^(-2i / dr)
      [k_nope | v] = ckv Wkvb -> H x (dn + dv)
      s_ij = (q_nope_i . k_nope_j + q_rope_i . kr_j) / sqrt(dn + dr), j > i masked
      y = concat_heads(softmax_j(s) v) Wo

    MoE(m): p = softmax(m Wr) over n_experts + zero_experts outputs (float32)
      top = the K largest of p + b;  c_e = routed_scaling * p_e for e in top
      (NOT normalised), else 0
      out = sum over e < n_experts in top AND held here of c_e FFN_e(m)
          + (sum over e >= n_experts of c_e) * m        the zero-compute experts

    out = N(x_L; g_f, eps);  logits = out W_head        (untied)

THE SHARE. ``cfg["experts_held"]`` experts from ``cfg["expert_offset"]`` on
are held (one chip of an expert-parallel layer): the router scores all its
outputs and takes its K as published; what the experts held elsewhere would
add is left out. The zero-compute experts have no weights and are computed
where the row lives, so every share computes ALL of them (``moe(...,
zero=False)`` leaves them out, for the test that adds the shares up). The
head is the held slice of the vocabulary. Nothing stands in for the absent
chips.

Departures from the published code: the rope is written on interleaved pairs
in place (the published code first moves a pair's members ``dr / 2`` apart
and rotates halves: the same rotation of the same pairs on q and on kr, so
the same scores). ``norm_topk_prob`` does not exist in the family: the
weights are not normalised. ``next_token_loss`` is the plain cross-entropy
over the held slice (the published model has no auxiliary term at inference;
none is defined for a share).

Memory: a layer's matrices arrive in the program's own type and are cast as
they are used, one sub-block and one expert at a time, and attention runs a
few heads at a time (``HEAD_BLOCK``): at the cell's lengths the check runs
beside a full device.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: heads whose S x S scores live at once
HEAD_BLOCK = 8
LORA_EPS = 1e-6


class Weights:
    """The program's parameter tree under the reference's names. ``layers``
    is one group a SUB-BLOCK of the published layer, each stacked over the
    published layers, the shortcut MoE beside the first: ``layer(l)`` gives
    ``{"sub": [names of sub-block 0, of sub-block 1], "moe": names}`` of
    published layer ``l``. Vectors come in float32; matrices as the program
    holds them (cast where they are used)."""

    def __init__(self, params, name_map: dict, device=None):
        self.params, self.map = params, name_map
        self.device = device or jax.devices()[0]
        self._top = None

    @staticmethod
    def _get(node, path: str):
        for part in path.split("/"):
            node = node[part]
        return node

    def _put(self, a):
        a = jax.device_put(a, self.device)
        return a.astype(jnp.float32) if a.ndim < 2 else a

    def top(self) -> dict:
        if self._top is None:
            self._top = {k: self._put(self._get(self.params, p))
                         for k, p in self.map["top"].items()}
        return self._top

    def layer(self, l: int) -> dict:
        groups = self.params[self.map["layers_root"]]
        take = lambda g, names: {  # noqa: E731
            k: self._put(self._get(g, p)[l]) for k, p in names.items()}
        return {"sub": [take(g, self.map["sub_block"]) for g in groups],
                "moe": take(groups[0], self.map["moe"])}


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def rope(x, theta: float):
    """x [B, S, heads, d], position = index along S: the pairs (2i, 2i + 1)
    rotated by ``pos * theta^(-2i / d)``."""
    S, d = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def mla(cfg, w, h):
    """h [B, S, D] (normed) -> the latent attention's output, the latent
    EXPANDED to every head's keys and values."""
    B, S, D = h.shape
    H, Q, R = cfg["n_head"], cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    cq = _rms(h @ _f32(w["wq_a"]), w["q_g"], LORA_EPS)
    q = (cq @ _f32(w["wq_b"])).reshape(B, S, H, dn + dr) * (D / Q) ** 0.5
    kv = h @ _f32(w["wkv_a"])
    ckv = _rms(kv[..., :R], w["kv_g"], LORA_EPS) * (D / R) ** 0.5
    kr = rope(kv[..., None, R:], cfg["rope_theta"])[:, :, 0]       # [B, S, dr]
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:], cfg["rope_theta"])
    kvb = (ckv @ _f32(w["wkv_b"])).reshape(B, S, H, dn + dv)
    future = jnp.arange(S)[None, :] > jnp.arange(S)[:, None]
    outs = []
    for h0 in range(0, H, HEAD_BLOCK):         # a few heads' scores at a time
        hs = slice(h0, h0 + HEAD_BLOCK)
        s = (jnp.einsum("bihd,bjhd->bhij", q_nope[:, :, hs], kvb[:, :, hs, :dn])
             + jnp.einsum("bihd,bjd->bhij", q_rope[:, :, hs], kr)) \
            * (dn + dr) ** -0.5
        p = jax.nn.softmax(jnp.where(future[None, None], -jnp.inf, s), axis=-1)
        outs.append(jnp.einsum("bhij,bjhd->bihd", p, kvb[:, :, hs, dn:]))
    return jnp.concatenate(outs, axis=2).reshape(B, S, H * dv) @ _f32(w["wo"])


def mlp(w, m):
    return (jax.nn.silu(m @ _f32(w["w_gate"])) * (m @ _f32(w["w_up"]))) \
        @ _f32(w["w_down"])


def route(cfg, w, m):
    """m [B, S, D] -> c [B, S, n_experts + zero_experts]: each token's
    weight for the K outputs it takes (routed_scaling x its softmax score,
    not normalised), else 0."""
    p = jax.nn.softmax(m @ _f32(w["router"]), axis=-1)
    _, top = jax.lax.top_k(p + w["b_select"], cfg["experts_per_token"])
    chosen = jnp.sum(jax.nn.one_hot(top, p.shape[-1], dtype=p.dtype), axis=-2)
    return p * chosen * cfg["routed_scaling"]


def expert(m, c_e, w_gate, w_up, w_down):
    """One expert over every token, weighted by that token's c_e."""
    return c_e[..., None] * (
        (jax.nn.silu(m @ _f32(w_gate)) * (m @ _f32(w_up))) @ _f32(w_down))


class _Cfg(dict):
    """A configuration jit can take as a static argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


_mla = jax.jit(mla, static_argnums=0)
_mlp = jax.jit(mlp)
_route = jax.jit(route, static_argnums=0)
_expert = jax.jit(expert)
_norm = jax.jit(_rms)


def moe(cfg, w, m, zero: bool = True):
    """The shortcut MoE's output for m [B, S, D] as THIS SHARE computes it:
    its held experts' part and (``zero``) every zero-compute expert's."""
    c = _route(_Cfg(cfg), {k: w[k] for k in ("router", "b_select")}, m)
    n, first = cfg["n_experts"], cfg["expert_offset"]
    out = jnp.sum(c[..., n:], axis=-1, keepdims=True) * m if zero \
        else jnp.zeros_like(m)
    for e in range(cfg["experts_held"]):
        out = out + _expert(m, c[..., first + e], w["e_gate"][e],
                            w["e_up"][e], w["e_down"][e])
    return out


def layer(cfg, w, x):
    """One published layer on x [B, S, D]; ``w`` as ``Weights.layer``
    gives it."""
    eps, c = cfg["eps"], _Cfg(cfg)
    s0, s1 = w["sub"]
    a0 = x + _mla(c, s0, _norm(x, s0["ln1_g"], eps))
    h0 = _norm(a0, s0["ln2_g"], eps)
    s = moe(cfg, w["moe"], h0)
    x1 = a0 + _mlp(s0, h0)
    a1 = x1 + _mla(c, s1, _norm(x1, s1["ln1_g"], eps))
    return a1 + _mlp(s1, _norm(a1, s1["ln2_g"], eps)) + s


def final_hidden(cfg, weights, tokens):
    """N_f(x_L) for tokens [B, S]; ``weights`` gives ``top()`` and
    ``layer(l)`` under the map's names."""
    with jax.default_matmul_precision("highest"):
        top = weights.top()
        x = _f32(top["wte"][tokens])
        for l in range(cfg["n_layer"]):
            x = layer(cfg, weights.layer(l), x)
        return _norm(x, top["lnf_g"], cfg["eps"])


def logits_rows(cfg, weights, h_rows):
    """h_rows [N, D] -> logits [N, V] through the untied head [D, V]."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda h, w: h @ _f32(w))(h_rows, weights.top()["head"])


def next_token_loss(cfg, weights, tokens):
    """Mean next-token cross-entropy of tokens [B, S] over the held slice of
    the vocabulary (no auxiliary term: module docstring)."""
    h = final_hidden(cfg, weights, tokens)
    with jax.default_matmul_precision("highest"):
        logits = h[:, :-1] @ _f32(weights.top()["head"])
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
        return float(-jnp.mean(picked))
