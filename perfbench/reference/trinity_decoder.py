"""The plain reference of the Trinity block (``model_type`` ``afmoe``:
Trinity-Large-Preview): SANDWICH-NORMED layers whose attention is a gated
GQA with per-head q/k RMSNorm, a rotary WINDOW in most layers and FULL
without positions in every fourth, the first ``num_dense_layers`` with a
dense gated-SiLU MLP and the others with a mixture of gated-SiLU experts
behind a sigmoid router with a selection bias beside one shared expert; an
embedding multiplied by sqrt(d); a final RMSNorm and an untied head.
Straight ``jax.numpy``.

float32 throughout under ``jax.default_matmul_precision("highest")``; no
kernels, no cache, no ring, no table, no sort: every layer sees the whole
sequence, the window is a mask over all of its keys, and every expert held
is computed for every token, one expert at a time, a weight of zero dropping
it. Written from ISSUE 56's equations (the catalog row's ``config`` and
``described_as`` for ``arcee-ai/Trinity-Large-Preview``), not from
``models/transformer.py`` or ``models/moe_lm.py``. It answers the contract
at the top of ``correctness.py`` and is fed the program's weights through
the name map of its configuration.

For one sequence ``t[0..S)``, with ``RMS(u; g) = u / sqrt(mean(u^2) + eps)
* g`` and no bias anywhere::

    x_0 = E[t] * sqrt(d)                               (mup_enabled)
    layer l, kind = layer_types[l]:
      a = RMS(x; g_in)
      q, k, v, g = a Wq, a Wk, a Wv, a Wg    H query heads, KV key/value
              heads of hd, the gate H * hd wide; query head h reads
              key/value head h // (H / KV)
      q, k = RMS_head(q; g_q), RMS_head(k; g_k)   over hd, one scale of hd
      sliding_attention only: position m turns each head of q and k whole,
              pair (i, i + hd/2) by the angle m theta^(-2 i / hd)
              (half-split pairing, no scaling); full_attention: NO positions
      s_ij = q_i . k_j / sqrt(hd) ; j > i masked ; sliding_attention also
              j <= i - W masked (a query sees W keys, its own among them)
      o = softmax_j(s) v ;  y = (o * sigmoid(g)) Wo
      h = x + RMS(y; g_post_attn)
      m = RMS(h; g_pre_mlp)
      l < n_dense_layer:  f = (silu(m Wgate) * (m Wup)) Wdown     width d_ff
      otherwise:  s = sigmoid(m Wr) over n_experts ; top = the K largest of
              s + b ; w_e = s_e / (sum_top s + 1e-20) * route_scale
              f = Shared(m) + sum over e in top AND HELD HERE of
                  w_e (silu(m Wgate_e) * (m Wup_e)) Wdown_e     width d_expert
      x = h + RMS(f; g_post_mlp)
    out = RMS(x_L; g_f) ; logits = out W_head (its own matrix)
    loss = mean over i < S-1 of -log softmax(logits_i)[t_{i+1}]

THE CUT (the configuration file's ``deployment``): one chip of the eight
that share each layer. The router scores all ``n_experts`` (256) and takes
the K largest; this chip HOLDS ``experts_held`` of them from
``expert_offset`` on, and an assignment to an expert held elsewhere is left
out of ``f``, which goes on through ``g_post_mlp`` as it is: nothing stands
in for the absent chips. The shared expert and attention are whole. The
vocabulary is the chip's slice. ``layer_types`` and ``n_dense_layer`` are
those of the configuration as it is run (one leading dense layer, then one
period); the weights' tree is a ``lead`` of single layers and then periods
(``Weights``).

Assumed (``config.json`` has no key for them; the configuration file lists
each under ``assumed``): the sigmoid output gate, the per-head q/k norm, no
positions in the full layers, the sandwich norm, sqrt(d) on the embedding,
the 1e-20. Departures, each noted because a reader comparing with the
sources would trip on it: the selection bias ``b`` takes part in the choice
only; the router is float32 here as everything is; no loss term for load
balancing (``load_balance_coeff`` drives the bias's update in training, not
a loss); attention is computed a block of ``Q_BLOCK`` queries at a time
against all keys (the same sum); matrices arrive in the type the program
holds them in and are cast to float32 where they are used, a layer's
attention, an MLP, one expert or the head at a time (the check runs beside
a serving engine that leaves the chip ~2 GB; the head's slice is 0.3 GB in
float32). A layer's window and whether it has positions are operands of ONE mixer
program (no window masks as a window longer than the sequence, no positions
is a turn by the angle 0), and an expert's index is an OPERAND of the program that adds it
(``add_expert``), not a constant of it: one program a sequence length, not
one an expert, which is most of what a cold check would compile.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

#: queries whose scores against every key are alive at once
Q_BLOCK = 256
#: the layer weights that are stacked over experts and stay where they are
EXPERT_STACKS = ("e_gate", "e_up", "e_down")
TOPK_EPS = 1e-20


@jax.jit
def _take(stack, row, e):
    # the indices as operands: one program a stack's shape, not one an index
    return stack[row, e]


class _Experts:
    """One layer's stack of an expert matrix, where it lies: ``[e]`` copies
    out that expert's matrix and no other."""

    def __init__(self, stack, row: int):
        self.stack, self.row = stack, row

    def __getitem__(self, e: int):
        return _take(self.stack, self.row, e)


class Weights:
    """The program's parameter tree under the reference's names. The stack
    is a LEAD and periods: ``lead`` is a tuple of single layers (leading
    dim 1), ``layers`` one group a position of the period, each stacked
    over the periods, so layer ``l`` is ``lead[l]`` or row ``(l - n_lead)
    // period`` of group ``(l - n_lead) % period``; named through the map's
    ``attn_layer`` and its ``dense_mlp`` or ``moe_mlp``, by what the group
    holds. Everything stays in the stored type: the functions below cast
    what they multiply."""

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
            out[k] = _Experts(a, row) if k in EXPERT_STACKS \
                else jax.device_put(a[row], self.device)
        return out


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) \
        * _f32(g)


def rotate(u, theta: float, turns=1.0):
    """u [B, S, heads, hd] at positions 0..S, every head turned whole, its
    two halves against each other; ``turns`` 0.0: every angle 0, nothing
    turned (a layer without positions)."""
    S, hd = u.shape[1], u.shape[3]
    half = hd // 2
    th = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / hd)
    ang = turns * jnp.arange(S, dtype=jnp.float32)[:, None] * th[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = u[..., :half], u[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def gated_attention(cfg, w, a, window, rope):
    """a [B, S, D] (normed) -> (o * sigmoid(g)) Wo. ``window`` 0: every key
    up to the query's own; W: the W keys up to the query's own. ``rope``:
    whether q and k are turned by their positions. Both are OPERANDS (a
    window of 0 masks as one of S + 1 does, no positions is a turn by the
    angle 0): one program a sequence length for both kinds of layer."""
    B, S, D = a.shape
    H, KV, hd = cfg["n_head"], cfg["n_kv_head"], cfg["head_dim"]
    q = _rms((a @ _f32(w["wq"])).reshape(B, S, H, hd), w["q_g"], cfg["eps"])
    k = _rms((a @ _f32(w["wk"])).reshape(B, S, KV, hd), w["k_g"], cfg["eps"])
    v = (a @ _f32(w["wv"])).reshape(B, S, KV, hd)
    turns = jnp.asarray(rope, jnp.float32)
    q, k = rotate(q, cfg["rope_theta"], turns), rotate(k, cfg["rope_theta"], turns)
    reach = jnp.where(jnp.asarray(window) > 0, window, S + 1)
    qb = min(Q_BLOCK, S)
    nb = -(-S // qb)
    # blocks of queries, each kv head's group of query heads together
    q = jnp.pad(q, ((0, 0), (0, nb * qb - S), (0, 0), (0, 0)))
    q = q.reshape(B, nb, qb, KV, H // KV, hd).transpose(1, 0, 2, 3, 4, 5)
    j = jnp.arange(S)[None, :]

    def block(args):
        qi, i0 = args                                   # [B, qb, KV, G, hd]
        i = (i0 + jnp.arange(qb))[:, None]
        s = jnp.einsum("bicgd,bjcd->bcgij", qi, k) / math.sqrt(hd)
        dead = (j > i) | (j <= i - reach)
        p = jax.nn.softmax(jnp.where(dead[None, None, None], -jnp.inf, s),
                           axis=-1)
        return jnp.einsum("bcgij,bjcd->bicgd", p, v)

    o = jax.lax.map(block, (q, jnp.arange(nb) * qb))    # [nb, B, qb, KV, G, hd]
    o = o.transpose(1, 0, 2, 3, 4, 5).reshape(B, nb * qb, H * hd)[:, :S]
    return (o * jax.nn.sigmoid(a @ _f32(w["w_gate_attn"]))) @ _f32(w["wo"])


def mixer(cfg, w, x, window, rope):
    """x [B, S, D] -> (h = x + RMS_post(Attn(RMS_in(x))), m = RMS_pre_mlp(h))."""
    y = gated_attention(cfg, w, _rms(x, w["ln1_g"], cfg["eps"]), window, rope)
    h = x + _rms(y, w["ln1_post_g"], cfg["eps"])
    return h, _rms(h, w["ln2_g"], cfg["eps"])


def scores(w, m):
    """m [B, S, D] (normed) -> (s, s + b) [B, S, n_experts]: the router's
    sigmoid scores, and what the choice is made by."""
    s = jax.nn.sigmoid(m @ _f32(w["router"]))
    return s, s + _f32(w["expert_bias"])


def weigh(cfg, s, top):
    """The scores s and the experts ``top`` [B, S, K] each token takes ->
    c [B, S, n_experts]: the token's weight for those experts, else 0."""
    c = s * jnp.sum(jax.nn.one_hot(top, cfg["n_experts"], dtype=s.dtype),
                    axis=-2)
    if cfg.get("route_norm", True):
        c = c / (jnp.sum(c, axis=-1, keepdims=True) + TOPK_EPS)
    return c * cfg.get("route_scale", 1.0)


def route(cfg, w, m):
    """m [B, S, D] (normed) -> c [B, S, n_experts]: each token's weight for
    the K experts it takes, else 0, over ALL the router's experts."""
    s, biased = scores(w, m)
    _, top = jax.lax.top_k(biased, cfg["experts_per_token"])
    return weigh(cfg, s, top)


def gated_mlp(m, w_gate, w_up, w_down):
    """One gated-SiLU MLP over every token (a dense MLP, the shared expert,
    one routed expert)."""
    return (jax.nn.silu(m @ _f32(w_gate)) * (m @ _f32(w_up))) @ _f32(w_down)


def add_expert(f, m, c, e, w_gate, w_up, w_down):
    """f + expert ``e`` (an index into the router's experts, an operand) over
    every token, weighted by that token's c[..., e] (0 for a token that did
    not choose it)."""
    c_e = jax.lax.dynamic_index_in_dim(c, e, axis=-1, keepdims=True)
    return f + c_e * gated_mlp(m, w_gate, w_up, w_down)


def join(cfg, h, f, g_post):
    """The MLP branch ``f`` through its norm on the way out."""
    return h + _rms(f, g_post, cfg["eps"])


class _Cfg(dict):
    """A configuration jit can take as a static argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


_mixer = jax.jit(mixer, static_argnums=0)
_route = jax.jit(route, static_argnums=0)
_gated_mlp = jax.jit(gated_mlp)
_add_expert = jax.jit(add_expert)
_join = jax.jit(join, static_argnums=0)


def moe(cfg, w, m):
    """The MoE branch of this chip's share on m [B, S, D] (normed): the
    shared expert whole, and of each token's K experts those held here
    (``experts_held`` from ``expert_offset`` on; the uncut layer: all of
    them from 0)."""
    cfg = _Cfg(cfg)
    small = {k: v for k, v in w.items() if k not in EXPERT_STACKS}
    c = _route(cfg, small, m)
    f = _gated_mlp(m, w["shared_gate"], w["shared_up"], w["shared_down"])
    lo = cfg.get("expert_offset", 0)
    for e in range(cfg.get("experts_held", cfg["n_experts"])):
        f = _add_expert(f, m, c, jnp.int32(lo + e), w["e_gate"][e],
                        w["e_up"][e], w["e_down"][e])
    return f


def layer(cfg, w, x, l: int):
    """Layer ``l`` on x [B, S, D]."""
    cfg = _Cfg(cfg)
    kind = cfg["layer_types"][l]
    if kind not in ("sliding_attention", "full_attention"):
        raise ValueError(f"layer {l}: unknown layer type {kind!r}")
    sliding = kind == "sliding_attention"
    dense = l < cfg["n_dense_layer"]
    if dense != ("w_gate" in w):
        raise ValueError(f"layer {l}: the configuration's num_dense_layers "
                         "and the weights disagree on its MLP")
    small = {k: v for k, v in w.items() if k not in EXPERT_STACKS}
    h, m = _mixer(cfg, small, x, int(cfg["window"]) if sliding else 0, sliding)
    if dense:
        f = _gated_mlp(m, w["w_gate"], w["w_up"], w["w_down"])
    else:
        f = moe(cfg, w, m)
    return _join(cfg, h, f, w["ln2_post_g"])


@functools.partial(jax.jit, static_argnums=2)
def _embed(wte, tokens, d_model: int):
    return _f32(wte[tokens]) * math.sqrt(d_model)


_final_norm = jax.jit(_rms, static_argnums=2)


def final_hidden(cfg, weights, tokens):
    """RMS_f(x_L) for tokens [B, S]; ``weights`` gives ``top()`` and
    ``layer(l)`` dicts under the map's names."""
    with jax.default_matmul_precision("highest"):
        top = weights.top()
        x = _embed(top["wte"], tokens, cfg["d_model"])
        for l in range(cfg["n_layer"]):
            x = layer(cfg, weights.layer(l), x, l)
        return _final_norm(x, top["lnf_g"], cfg["eps"])


@jax.jit
def _head(h_rows, head):
    return h_rows @ _f32(head)


def logits_rows(cfg, weights, h_rows):
    """h_rows [N, D] -> logits [N, V] through the untied head [D, V]."""
    with jax.default_matmul_precision("highest"):
        return _head(h_rows, weights.top()["head"])


def next_token_loss(cfg, weights, tokens):
    """Mean next-token cross-entropy of tokens [B, S] (no auxiliary term:
    module docstring)."""
    h = final_hidden(cfg, weights, tokens)
    logits = logits_rows(cfg, weights, h[:, :-1].reshape(-1, h.shape[-1]))
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:].reshape(-1, 1), axis=-1)
    return float(-jnp.mean(picked))
