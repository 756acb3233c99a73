"""The plain reference of the ``deepseek_v3`` block as Kanana-2-30B-A3B
publishes it (``kakaocorp/kanana-2-30b-a3b-instruct-2601``): pre-RMSNorm
layers whose mixer is multi-head LATENT attention with a DIRECT query
(``q_lora_rank`` null), the first ``first_k_dense_replace`` with a dense
gated-SiLU MLP and the others with a mixture of gated-SiLU experts behind a
sigmoid router with a selection bias and a group-limited top-k, beside the
shared experts; a final RMSNorm and an untied head. Straight ``jax.numpy``.

float32 throughout under ``jax.default_matmul_precision("highest")``; no
kernels, no cache, no table, no sort, and the NON-absorbed form only: every
position's latent is expanded to its heads' keys and values and attention
runs over those; every expert is computed for every token, a weight of zero
dropping it. Written from ISSUE 61's equations (the catalog row's ``config``
and the public ``deepseek_v3`` modelling code: ``DeepseekV3Attention``,
``apply_rotary_pos_emb_interleave``, ``DeepseekV3TopkRouter``,
``DeepseekV3MoE``), not from ``models/latent_attention.py`` or
``models/moe_lm.py``. It answers the contract at the top of
``correctness.py`` and is fed the program's weights through the name map of
its configuration.

For one sequence ``t[0..S)``, ``N(u; g) = u / sqrt(mean(u^2) + eps) * g``,
no bias anywhere::

    x = E[t]
    layer l:  h = x + MLA(N(x; g1));  m = N(h; g2);  x = h + F_l(m)

    MLA(u), H heads, latent rank R, head sizes dn (no position), dr (roped), dv:
      q = u Wq                               -> H x [q_nope dn | q_rope dr]
      [ckv R | kr dr] = u Wkva;  ckv = N(ckv; gkv);  kr ONE head for all H
      rope(theta) on q_rope and on kr THE PUBLISHED WAY (rope_interleave):
        the dr values are first de-interleaved, (0, 2, 4, ... | 1, 3, 5, ...),
        then the two halves turned against each other, member i of each by
        the angle pos * theta^(-2i / dr)
      [k_nope dn | v dv] a head = ckv Wkvb
      s_ij = (q_nope_i . k_nope_j + q_rope_i . kr_j) / sqrt(dn + dr), j > i masked
      y = concat_heads(softmax_j(s) v) Wo

    F_l(m), l < n_dense_layer:  (silu(m Wgate) * (m Wup)) Wdown      width d_ff
    F_l(m) otherwise:
      s = sigmoid(m Wr) over n_experts;  c = s + b        (b: the choice only)
      group-limited choice: the experts in n_group groups, a group scored by
        the sum of its 2 largest c, the topk_group best groups kept, every
        other expert's c set to 0; top = the K largest of what is left
      w_e = s_e for e in top;  norm_topk_prob: w / (sum_top w + 1e-20);
      w x routed_scaling
      sum_{e in top} w_e (silu(m Wgate_e) * (m Wup_e)) Wdown_e      width d_expert
      + Shared(m)          one gated MLP of n_shared_experts x d_expert

    out = N(x_L; g_f);  logits = out W_head (its own matrix)
    loss = mean over i < S-1 of -log softmax(logits_i)[t_{i+1}]

THE CUT (the configuration file's ``deployment``): a pipeline stage, every
expert and the whole vocabulary: no share of anything, so there is nothing
to leave out. ``n_dense_layer`` and the depth are those of the configuration
as it is run; the weights' tree is a ``lead`` of single layers and then
stacked layers (``Weights``).

Assumed (the configuration file lists each under ``assumed``): eps of
``kv_a_layernorm`` is its class's default 1e-6 (the published attention
builds it without one; the model's ``rms_norm_eps`` is the same number);
the config's
``head_dim`` 64 is the rope width the published class derives and sizes
nothing else; ``rope_scaling`` null: no mscale on the softmax scale.
Departures, each noted because a reader comparing with the sources would
trip on it: the router is float32 here as everything is; no loss term for
load balancing (``noaux_tc``: the bias is moved in training, not a loss);
attention is computed ``HEAD_BLOCK`` heads and ``Q_BLOCK`` queries at a
time against all keys, each head group's part of ``Wo`` added up (the same
sums); matrices arrive in the type the program holds them in and are cast
to float32 where they are used, a head group of attention, an MLP, one
expert or a slice of the head at a time (the check runs beside a serving
engine that leaves the chip ~3 GB at sequences of 12-21 k tokens: whole,
the scores of 32 heads at 12,299 tokens are 19 GB); an expert's index is an
operand of ONE program a sequence length (a loop inside it), not a constant
of 128 programs.

In a CONTROL only (no map sets it) ``cfg`` may hold ``round_to``: a type
narrower than the program's through which the matrices (but the router's,
whose product the configuration states in float32) and the activations are
rounded (:func:`_lossy`: the residual stream a layer reads, the norms'
outputs, q, the roped key, the heads' keys and values and their outputs, an
MLP's hidden vector), which is what this reference gives when it is computed
in that precision (``benchmarks/kanana_check_controls.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: heads whose keys and values are expanded at once
HEAD_BLOCK = 8
#: queries whose scores against every key are alive at once (at most 512)
Q_BLOCK = 256
#: vocabulary rows a slice of the head (the whole is 1 GB in float32)
HEAD_ROWS = 16384
#: the layer weights that are stacked over experts and stay where they are
EXPERT_STACKS = ("e_gate", "e_up", "e_down")
TOPK_EPS = 1e-20
#: ``kv_a_layernorm`` is built without an eps and takes the class default
LORA_EPS = 1e-6


class Weights:
    """The program's parameter tree under the reference's names. The stack
    is a LEAD and stacked layers: ``lead`` is a tuple of single layers
    (leading dim 1), ``layers`` one group a position of the period (here
    one), each stacked over the periods; named through the map's
    ``attn_layer`` and its ``dense_mlp`` or ``moe_mlp``, by what the group
    holds. Everything stays in the stored type (the functions below cast
    what they multiply), and a layer's expert stacks stay WHOLE where they
    lie, as ``(stack [layers, E, ...], row)``: ``moe`` reads one expert of
    one layer at a time out of them."""

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
            self._top = {k: jax.device_put(self._get(self.params, p),
                                           self.device)
                         for k, p in self.map["top"].items()}
        return self._top

    def layer(self, l: int) -> dict:
        n_lead, period = len(self.lead), len(self.groups)
        if l < n_lead:
            group, row = self.lead[l], 0
        else:
            group, row = self.groups[(l - n_lead) % period], \
                (l - n_lead) // period
        names = {**self.map["attn_layer"],
                 **self.map["moe_mlp" if "gate_w" in group["mlp"]
                            else "dense_mlp"]}
        out = {}
        for k, p in names.items():
            a = self._get(group, p)
            out[k] = (a, row) if k in EXPERT_STACKS \
                else jax.device_put(a[row], self.device)
        return out


def _f32(a):
    return a.astype(jnp.float32)


def _lossy(cfg, u):
    """A matrix or an activation as it is held: itself, or rounded through
    ``cfg["round_to"]`` and back where a control names such a type."""
    to = cfg.get("round_to")
    return u if to is None else u.astype(jnp.dtype(to)).astype(u.dtype)


def _mat(cfg, a):
    """A matrix as it enters a product."""
    return _lossy(cfg, _f32(a))


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) \
        * _f32(g)


def rope(x, theta: float):
    """x [S, heads, d], position = index along S, the published
    ``apply_rotary_pos_emb_interleave``: de-interleave (evens, then odds),
    then rotate halves, member i of each half by ``pos * theta^(-2i / d)``.
    The output keeps the de-interleaved order: q and kr take the same
    permutation, so their products are those of pairs turned in place."""
    S, d = x.shape[0], x.shape[-1]
    x = x.reshape(*x.shape[:-1], d // 2, 2).swapaxes(-1, -2).reshape(x.shape)
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    freqs = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * jnp.cos(emb) + jnp.concatenate([-x2, x1], -1) * jnp.sin(emb)


def mla(cfg, w, u):
    """u [S, D] (normed) -> the latent attention's output [S, D], the
    latent EXPANDED to every head's keys and values, ``HEAD_BLOCK`` heads
    and ``Q_BLOCK`` queries at a time."""
    S, D = u.shape
    H, R = cfg["n_head"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    hb = min(HEAD_BLOCK, H)
    if H % hb:
        raise ValueError(f"{H} heads are not whole blocks of {hb}")
    kv = u @ _mat(cfg, w["wkv_a"])
    ckv = _lossy(cfg, _rms(kv[:, :R], w["kv_g"], LORA_EPS))
    kr = _lossy(cfg, rope(kv[:, None, R:], cfg["rope_theta"])[:, 0])  # [S, dr]
    qb = min(Q_BLOCK, S)
    nb = -(-S // qb)
    j = jnp.arange(S)[None, :]
    scale = (dn + dr) ** -0.5
    # a head group's columns of Wq and Wkvb and rows of Wo, group first
    wq = w["wq"].reshape(D, H // hb, hb, dn + dr).transpose(1, 0, 2, 3)
    wkvb = w["wkv_b"].reshape(R, H // hb, hb, dn + dv).transpose(1, 0, 2, 3)
    wo = w["wo"].reshape(H // hb, hb * dv, D)

    def heads(ws):
        wq_g, wkvb_g, wo_g = ws
        q = jnp.einsum("sd,dhe->she", u, _mat(cfg, wq_g))      # [S, hb, dn+dr]
        q_nope = _lossy(cfg, q[..., :dn])
        q_rope = _lossy(cfg, rope(q[..., dn:], cfg["rope_theta"]))
        kvb = _lossy(cfg, jnp.einsum("sr,rhe->she", ckv,
                                     _mat(cfg, wkvb_g)))       # [S, hb, dn+dv]
        k_nope, v = kvb[..., :dn], kvb[..., dn:]
        pad = nb * qb - S
        qn = jnp.pad(q_nope, ((0, pad), (0, 0), (0, 0))).reshape(nb, qb, hb, dn)
        qr = jnp.pad(q_rope, ((0, pad), (0, 0), (0, 0))).reshape(nb, qb, hb, dr)

        def block(args):
            qn_i, qr_i, i0 = args
            i = (i0 + jnp.arange(qb))[:, None]
            s = (jnp.einsum("ihd,jhd->hij", qn_i, k_nope)
                 + jnp.einsum("ihd,jd->hij", qr_i, kr)) * scale
            p = jax.nn.softmax(jnp.where((j > i)[None], -jnp.inf, s), axis=-1)
            return jnp.einsum("hij,jhd->ihd", p, v)

        o = jax.lax.map(block, (qn, qr, jnp.arange(nb) * qb))
        return _lossy(cfg, o.reshape(nb * qb, hb * dv)[:S]) @ _mat(cfg, wo_g)

    out, _ = jax.lax.scan(lambda acc, ws: (acc + heads(ws), None),
                          jnp.zeros_like(u), (wq, wkvb, wo))
    return out


def mixer(cfg, w, x):
    """x [S, D] -> (h = x + MLA(N(x)), m = N(h))."""
    x = _lossy(cfg, x)
    h = x + mla(cfg, w, _lossy(cfg, _rms(x, w["ln1_g"], cfg["eps"])))
    return h, _lossy(cfg, _rms(h, w["ln2_g"], cfg["eps"]))


def choose(cfg, c):
    """c [..., n_experts], the scores the choice is made by (s + b) -> top
    [..., K]: the published group-limited top-k (one group: the plain K
    largest)."""
    E, G = cfg["n_experts"], cfg.get("n_group", 1)
    by_group = c.reshape(*c.shape[:-1], G, E // G)
    group_scores = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
    _, kept = jax.lax.top_k(group_scores, cfg.get("topk_group", 1))
    group_mask = jnp.sum(jax.nn.one_hot(kept, G, dtype=c.dtype), axis=-2)
    score_mask = jnp.repeat(group_mask, E // G, axis=-1) > 0
    _, top = jax.lax.top_k(jnp.where(score_mask, c, 0.0),
                           cfg["experts_per_token"])
    return top


def weigh(cfg, s, top):
    """The scores s and the experts ``top`` [..., K] each token takes ->
    w [..., n_experts]: the token's weight for those experts, else 0."""
    w = s * jnp.sum(jax.nn.one_hot(top, cfg["n_experts"], dtype=s.dtype),
                    axis=-2)
    if cfg.get("route_norm", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + TOPK_EPS)
    return w * cfg.get("route_scale", 1.0)


def scores(w, m):
    """m [..., D] (normed) -> (s, s + b) [..., n_experts]: the router's
    sigmoid scores, and what the choice is made by."""
    s = jax.nn.sigmoid(m @ _f32(w["router"]))
    return s, s + _f32(w["expert_bias"])


def route(cfg, w, m):
    """m [..., D] (normed) -> [..., n_experts]: each token's weight for the
    K experts it takes, else 0."""
    s, biased = scores(w, m)
    return weigh(cfg, s, choose(cfg, biased))


def gated_mlp(cfg, m, w_gate, w_up, w_down):
    """One gated-SiLU MLP over every token (the dense MLP, the shared
    experts, one routed expert)."""
    hidden = jax.nn.silu(m @ _mat(cfg, w_gate)) * (m @ _mat(cfg, w_up))
    return _lossy(cfg, hidden) @ _mat(cfg, w_down)


def experts(cfg, m, c, row, e_gate, e_up, e_down):
    """sum_e c[:, e] expert_e(m) over every expert of layer ``row`` of the
    stacks [layers, E, ...], one expert at a time where the stacks lie."""
    def add(e, f):
        return f + c[:, e, None] * gated_mlp(cfg, m, e_gate[row, e],
                                             e_up[row, e], e_down[row, e])
    return jax.lax.fori_loop(0, e_gate.shape[1], add, jnp.zeros_like(m))


class _Cfg(dict):
    """A configuration jit can take as a static argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


_mixer = jax.jit(mixer, static_argnums=0)
_route = jax.jit(route, static_argnums=0)
_gated_mlp = jax.jit(gated_mlp, static_argnums=0)
_experts = jax.jit(experts, static_argnums=0)
_norm = jax.jit(_rms, static_argnums=2)


def moe(cfg, w, m):
    """The MoE branch on m [S, D] (normed): the routed experts' weighted
    sum and the shared experts."""
    small = {k: v for k, v in w.items() if k not in EXPERT_STACKS}
    cfg = _Cfg(cfg)
    c = _route(cfg, small, m[None])[0]               # [1, S, D]: a batch of one
    (e_gate, row), (e_up, _), (e_down, _) = (w[k] for k in EXPERT_STACKS)
    return _experts(cfg, m, c, jnp.int32(row), e_gate, e_up, e_down) \
        + _gated_mlp(cfg, m, w["shared_gate"], w["shared_up"],
                     w["shared_down"])


def layer(cfg, w, x, l: int):
    """Layer ``l`` on x [S, D]."""
    dense = l < cfg["n_dense_layer"]
    if dense != ("w_gate" in w):
        raise ValueError(f"layer {l}: the configuration's "
                         "first_k_dense_replace and the weights disagree on "
                         "its MLP")
    small = {k: v for k, v in w.items() if k not in EXPERT_STACKS}
    h, m = _mixer(_Cfg(cfg), small, x)
    if dense:
        return h + _gated_mlp(_Cfg(cfg), m, w["w_gate"], w["w_up"],
                              w["w_down"])
    return h + moe(cfg, w, m)


def embed(cfg, wte, tokens):
    return _mat(cfg, wte[tokens])


def final_hidden(cfg, weights, tokens):
    """N_f(x_L) for tokens [B, S], a sequence at a time; ``weights`` gives
    ``top()`` and ``layer(l)`` dicts under the map's names."""
    with jax.default_matmul_precision("highest"):
        top = weights.top()
        xs = [_embed(_Cfg(cfg), top["wte"], t) for t in tokens]
        for l in range(cfg["n_layer"]):
            w = weights.layer(l)
            xs = [layer(cfg, w, x, l) for x in xs]
        return jnp.stack([_lossy(cfg, _norm(x, top["lnf_g"], cfg["eps"]))
                          for x in xs])


def head_slice(cfg, h_rows, head, v0):
    cols = jax.lax.dynamic_slice_in_dim(head, v0, min(HEAD_ROWS, head.shape[1]),
                                        axis=1)
    return h_rows @ _mat(cfg, cols)


_embed = jax.jit(embed, static_argnums=0)
_head = jax.jit(head_slice, static_argnums=0)


def logits_rows(cfg, weights, h_rows):
    """h_rows [N, D] -> logits [N, V] through the untied head [D, V], a
    slice of the vocabulary at a time."""
    with jax.default_matmul_precision("highest"):
        head = weights.top()["head"]
        V, n = head.shape[1], min(HEAD_ROWS, head.shape[1])
        # the last slice starts early enough to be whole; its overlap is cut
        starts = [min(v0, V - n) for v0 in range(0, V, n)]
        parts = [_head(_Cfg(cfg), h_rows, head,
                       jnp.int32(v0))[:, max(0, v1 - v0):]
                 for v0, v1 in zip(starts, range(0, V, n))]
        return jnp.concatenate(parts, axis=-1)


def next_token_loss(cfg, weights, tokens):
    """Mean next-token cross-entropy of tokens [B, S] (no auxiliary term:
    module docstring)."""
    h = final_hidden(cfg, weights, tokens)
    logits = logits_rows(cfg, weights, h[:, :-1].reshape(-1, h.shape[-1]))
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:].reshape(-1, 1), axis=-1)
    return float(-jnp.mean(picked))
