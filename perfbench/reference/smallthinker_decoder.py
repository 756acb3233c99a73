"""The plain reference of the SmallThinker block: pre-RMSNorm layers whose
attention is FULL and without positions in some layers and a rotary WINDOW
in the others, a mixture of ReLU-gated experts in every layer whose router
reads the ATTENTION'S INPUT, an untied head. Straight ``jax.numpy``.

float32 throughout under ``jax.default_matmul_precision("highest")``; no
kernels, no cache, no ring, no sort, no grouped matmul: every layer sees the
whole sequence, the window is a mask over all of its keys, every expert is
computed for every token, one expert at a time, and a weight of zero drops
it. Written from the published ``config.json`` of
``PowerInfer/SmallThinker-21BA3B-Instruct`` and the description of the
family (SmallThinker, Song et al. 2025: "sparse ReGLU", "router placed
before attention", NoPE global layers between sliding-window rope layers;
rotary embeddings, Su et al. 2021), not from ``models/transformer.py`` or
``models/moe_lm.py``. It answers the contract at the top of
``correctness.py`` and is fed the program's weights through the name map of
its configuration.

For one sequence ``t[0..S)``, layer ``l``, with ``RMS(u; g) = u /
sqrt(mean(u^2) + eps) * g``::

    x_0   = E[t]
    a     = RMS(x; g_1)
    q, k, v = a Wq, a Wk, a Wv     H query heads, KV key/value heads of hd;
            query head h reads key/value head h // (H / KV); no bias, no
            norm on q or k
    where rope_layout[l] = 1, position m turns each head of q and k, pair
            (i, i + hd/2): (u_i, u_{i+hd/2}) -> (u_i cos m th_i - u_{i+hd/2}
            sin m th_i, u_{i+hd/2} cos m th_i + u_i sin m th_i),
            th_i = theta^(-2 i / hd), i = 0..hd/2, no scaling;
            where it is 0 the layer has NO positions at all
    s_ij  = q_i . k_j / sqrt(hd) ; j > i masked ; where
            sliding_window_layout[l] = 1 also j <= i - W masked: a query sees
            W keys, its own among them ; p = softmax_j(s)
    h     = x + (p v) Wo
    z     = a Wr                   over the E experts: the router reads a,
            what the attention read, NOT RMS(h; g_2)
    top   = the K experts of largest z ; c = softmax over those K logits,
            0 elsewhere (the softmax over all E, its K largest, divided by
            their sum: moe_primary_router_apply_softmax and norm_topk_prob)
    m     = RMS(h; g_2)
    x     = h + sum_e c_e * ( relu(m Wgate_e) * (m Wup_e) ) Wdown_e
    out   = RMS(x_L; g_f) ; logits = out W_head (its own matrix, no bias)
    loss  = mean over i < S-1 of -log softmax(logits_i)[t_{i+1}]

No bias anywhere, no shared expert, no capacity: no token is dropped; no
auxiliary term in the loss (``config.json`` names none and the program's
preset sets its coefficient to 0). ``cfg`` holds ``n_layer``, ``n_head``,
``n_kv_head``, ``head_dim``, ``d_model``, ``eps``, ``rope_theta``,
``window``, ``window_layout``, ``rope_layout`` (a flag a layer of the
PUBLISHED depth; a cut reads its first ``n_layer``), ``n_experts``,
``experts_per_token``, ``d_expert``; and, in a CONTROL only (no map sets
it), ``round_to``: a type narrower than the program's through which the
matrices (but the router's, whose product the configuration states in
float32) and the activations are rounded (:func:`_lossy`: the residual
stream a layer reads, both norms' outputs, q, k, v, the heads' outputs, an
expert's hidden vector), which is what this reference gives when it is
computed in that precision
(``benchmarks/smallthinker_check_controls.py``).

Assumed (``config.json`` carries no key for them; the configuration file
lists each under ``assumed`` with its reason):

* the experts' gate is ReLU (the family's "sparse ReGLU");
* the router reads the NORMED attention input ``a`` (the family's "router
  placed before attention"), not the raw residual stream;
* no attention bias, no q/k norm;
* bf16 weights and KV in the program (the reference holds none: float32);
* no secondary experts: the description names "primary+secondary experts",
  ``config.json`` has keys for the primary ones only; ``config.json`` is
  trusted.

Departures, each noted because a reader comparing with the sources would
trip on it. Attention is computed a block of ``Q_BLOCK`` queries at a time
against all keys (a 10,000-token sequence's scores are 11 GB a layer
otherwise); the result is the same sum. The experts' weights, the embedding
and the head stay in the type the program holds them in (``Weights`` below)
and are cast to float32 where they are used, an expert, the sequence's rows
or a block of the head's columns at a time: a layer's experts in float32
are 1.5 GB and the two vocabulary matrices 3.1 GB, beside a serving engine
that leaves the chip 2 GB.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

#: queries whose scores against every key are alive at once
Q_BLOCK = 256
#: the layer weights that are stacked over experts and stay as stored
EXPERT_STACKS = ("w_gate", "w_up", "w_down")
#: the two [vocabulary, d] matrices, which stay as stored too
VOCAB_MATRICES = ("wte", "head")
#: columns of the head cast to float32 at a time
VOCAB_BLOCK = 16384


@jax.jit
def _take(stack, row, e):
    # the indices as operands: one program a stack's shape, not one an index
    return stack[row, e]


class _Experts:
    """One layer's stack of an expert matrix, where it lies: ``[e]`` copies
    out that expert's matrix and no other."""

    def __init__(self, stack, row: int, device):
        self.stack, self.row, self.device = stack, row, device

    def __getitem__(self, e: int):
        return jax.device_put(_take(self.stack, self.row, e), self.device)


class Weights:
    """The program's parameter tree under the reference's names. The stack
    is not one leading axis: ``layers`` is one group a position of the
    period, each stacked over the periods, so layer ``l`` is row ``l //
    period`` of group ``l % period`` (full and window layers carry the same
    names). float32, one layer at a time, but for the expert stacks [E, .,
    .], which stay where they are in the stored type until :func:`expert`
    casts one expert's matrices, and the embedding and the head (module
    docstring)."""

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
            self._top = {}
            for k, p in self.map["top"].items():
                a = jax.device_put(self._get(self.params, p), self.device)
                self._top[k] = a if k in VOCAB_MATRICES \
                    else a.astype(jnp.float32)
        return self._top

    def layer(self, l: int) -> dict:
        group = self.params[self.map["layers_root"]][l % self.period]
        out = {}
        for k, p in self.map["layer"].items():
            a, row = self._get(group, p), l // self.period
            out[k] = _Experts(a, row, self.device) if k in EXPERT_STACKS \
                else jax.device_put(a[row], self.device).astype(jnp.float32)
        return out


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def _lossy(cfg, u):
    """A matrix or an activation as it is held: itself, or rounded through
    ``cfg["round_to"]`` and back where a control names such a type."""
    to = cfg.get("round_to")
    return u if to is None else u.astype(jnp.dtype(to)).astype(u.dtype)


def rotate(u, theta: float):
    """u [B, S, H, hd] at positions 0..S, every head turned whole."""
    S, hd = u.shape[1], u.shape[3]
    half = hd // 2
    th = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * th[None, :]    # [S, half]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = u[..., :half], u[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(cfg, w, x, window: int, rope: bool):
    """x [B, S, D] -> (x + Attn(a), a = RMS(x)). ``window`` 0: every key up
    to the query's own; W: the W keys up to the query's own. ``rope``:
    whether q and k are turned by their positions."""
    B, S, D = x.shape
    H, KV, hd = cfg["n_head"], cfg["n_kv_head"], cfg["head_dim"]
    x = _lossy(cfg, x)
    a = _lossy(cfg, _rms(x, w["ln1_g"], cfg["eps"]))
    q = (a @ _lossy(cfg, w["wq"])).reshape(B, S, H, hd)
    k = (a @ _lossy(cfg, w["wk"])).reshape(B, S, KV, hd)
    v = _lossy(cfg, (a @ _lossy(cfg, w["wv"])).reshape(B, S, KV, hd))
    if rope:
        q, k = rotate(q, cfg["rope_theta"]), rotate(k, cfg["rope_theta"])
    q, k = _lossy(cfg, q), _lossy(cfg, k)
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
        dead = j > i
        if window:
            dead = dead | (j <= i - window)
        p = jax.nn.softmax(jnp.where(dead[None, None, None], -jnp.inf, s),
                           axis=-1)
        return jnp.einsum("bcgij,bjcd->bicgd", p, v)

    o = jax.lax.map(block, (q, jnp.arange(nb) * qb))    # [nb, B, qb, KV, G, hd]
    o = o.transpose(1, 0, 2, 3, 4, 5).reshape(B, nb * qb, H * hd)[:, :S]
    return x + _lossy(cfg, o) @ _lossy(cfg, w["wo"]), a


def route(cfg, w, a, h):
    """a [B, S, D] the attention's input, h the residual stream after the
    attention -> (m = RMS(h; g_2) what the experts read, c [B, S, E] each
    token's weight for the K experts it takes, else 0)."""
    z = a @ w["router"]
    _, top = jax.lax.top_k(z, cfg["experts_per_token"])
    chosen = jnp.sum(jax.nn.one_hot(top, cfg["n_experts"], dtype=z.dtype),
                     axis=-2) > 0
    c = jax.nn.softmax(jnp.where(chosen, z, -jnp.inf), axis=-1)
    return _lossy(cfg, _rms(h, w["ln2_g"], cfg["eps"])), c


def expert(cfg, m, c_e, w_gate, w_up, w_down):
    """One expert over every token, weighted by that token's c_e (0 for a
    token that did not choose it)."""
    w_gate, w_up, w_down = (_lossy(cfg, a.astype(jnp.float32))
                            for a in (w_gate, w_up, w_down))
    hidden = _lossy(cfg, jax.nn.relu(m @ w_gate) * (m @ w_up))
    return c_e[..., None] * (hidden @ w_down)


_attention = jax.jit(attention, static_argnums=(0, 3, 4))
_route = jax.jit(route, static_argnums=0)
_expert = jax.jit(expert, static_argnums=0)


class _Cfg(dict):
    """A configuration jit can take as a static argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def layer(cfg, w, x, l: int):
    """Layer ``l`` on x [B, S, D]."""
    cfg = _Cfg(cfg)
    attn_w = {k: v for k, v in w.items() if k not in EXPERT_STACKS}
    window = cfg["window"] if cfg["window_layout"][l] else 0
    h, a = _attention(cfg, attn_w, x, int(window), bool(cfg["rope_layout"][l]))
    m, c = _route(cfg, attn_w, a, h)
    out = h
    for e in range(cfg["n_experts"]):
        out = out + _expert(cfg, m, c[..., e], w["w_gate"][e], w["w_up"][e],
                            w["w_down"][e])
    return out


def final_hidden(cfg, weights, tokens):
    """RMS_f(x_L) for tokens [B, S]; ``weights`` gives ``top()`` and
    ``layer(l)`` dicts under the map's names."""
    with jax.default_matmul_precision("highest"):
        top = weights.top()
        x = top["wte"][tokens].astype(jnp.float32)
        for l in range(cfg["n_layer"]):
            x = layer(cfg, weights.layer(l), x, l)
        return _rms(x, top["lnf_g"], cfg["eps"])


@functools.partial(jax.jit, static_argnums=0)
def _head_block(cfg, h_rows, cols):
    return h_rows @ _lossy(cfg, cols.astype(jnp.float32))


def logits_rows(cfg, weights, h_rows):
    """h_rows [N, D] -> logits [N, V] through the untied head [D, V], a
    block of its columns at a time."""
    head = weights.top()["head"]
    with jax.default_matmul_precision("highest"):
        return jnp.concatenate(
            [_head_block(_Cfg(cfg), h_rows, head[:, lo:lo + VOCAB_BLOCK])
             for lo in range(0, head.shape[1], VOCAB_BLOCK)], axis=1)


def loss_value(cfg, weights, tokens, vocab_block: int = VOCAB_BLOCK):
    """:func:`next_token_loss` as an array."""
    h = final_hidden(cfg, weights, tokens)
    hr = h[:, :-1].reshape(-1, h.shape[-1])
    labels = tokens[:, 1:].reshape(-1)
    head = weights.top()["head"]

    @jax.jit
    def block(hr, cols, lab, lo, m, z, picked):
        lg = hr @ cols.astype(jnp.float32)              # [N, vb]
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
        return jnp.mean(m + jnp.log(z) - picked)


def next_token_loss(cfg, weights, tokens):
    """Mean next-token cross-entropy of tokens [B, S] (no auxiliary term:
    module docstring); the log-sum-exp taken in blocks over the head's
    columns so that [B*S, V] never exists."""
    return float(loss_value(cfg, weights, tokens))
