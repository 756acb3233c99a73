"""The plain reference of the Granite 4.0-H block (``granitemoehybrid`` with no
experts): pre-RMSNorm, no positions anywhere, a stack whose layers are each a
Mamba-2 mixer or a softmax GQA mixer (``layer_types`` of ``config.json``) and
then a dense gated-SiLU MLP, Granite's four multipliers, a tied head.
Straight ``jax.numpy``.

float32 throughout under ``jax.default_matmul_precision("highest")``; no
kernels, no cache, no chunks: the state-space recurrence runs TOKEN BY TOKEN
from a zero state (``lax.scan`` over positions). Written from the model's
``config.json`` keys and the equations of its parts (Dao & Gu 2024,
"Transformers are SSMs", section 7: the Mamba-2 block; the Granite 3 / 4
model cards for the multipliers), not from ``models/transformer.py``. It is
tied to the published code by ``tests/unit/test_granite_hybrid.py``, which
holds it to ``transformers``' ``GraniteMoeHybridForCausalLM`` on a small
random model. It answers the contract at the top of ``correctness.py`` and is
fed the program's weights through the name map of its configuration.

For one sequence ``t[0..S)``, with ``RMS(u; g) = u / sqrt(mean(u^2) + eps) * g``,
``em``, ``rm``, ``am``, ``ls`` the embedding, residual and attention
multipliers and the logits scaling::

    x_0 = em * E[t]
    every layer:  h = x + rm * Mixer(RMS(x; g_1));  x = h + rm * MLP(RMS(h; g_2))
    MLP(m) = (silu(m Wg) * (m Wu)) Wd      ([Wg | Wu] is the published W_in)

    GQA (H query heads, KV key/value heads of hd; head i reads kv head i // (H/KV)):
      q, k, v = a Wq, a Wk, a Wv ;  s_ij = am * q_i . k_j , j > i masked
      y = (softmax_j(s) v) Wo                           (no rope, no bias)

    Mamba-2 (Hs heads of P channels, state N, G groups of B and C, K taps):
      [z | xBC | dt] = a W_in              widths Hs P, Hs P + 2 G N, Hs
      xBC_t = silu(sum_{j<K} c_j * xBC_{t-(K-1)+j} + b)   per channel, xBC_{<0} = 0
      [x | B | C] = xBC                    x [Hs, P], B and C [G, N]
      dt = softplus(dt + dt_bias) ;  A = -exp(A_log)    per head
      S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        S [P, N] a head, S_{-1} = 0
      y_t = S_t C_t + D x_t                             head h reads group h // (Hs/G)
      out = (RMS(y * silu(z); g_n)) W_out               the norm over all Hs P channels

    out = RMS(x_L; g_f) ; logits = out E^T / ls

Departures: none from the published arithmetic but the type of the state,
which is float32 here as everything is (the published code keeps it in the
cache's type). The published clamp of ``dt`` to ``(0, inf)`` does nothing
after a softplus and is left out. ``next_token_loss`` is the plain
cross-entropy (the model has no experts, so no auxiliary term).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


class Weights:
    """The program's parameter tree under the reference's names. The stack
    is not one leading axis: ``layers`` is one group a position of the
    period, each stacked over the periods, so layer ``l`` is row ``l //
    period`` of group ``l % period``, named through the map's
    ``attn_layer`` or ``mamba_layer`` and its ``mlp_layer``. float32, one
    layer at a time."""

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

    def _f32(self, a):
        return jax.device_put(a, self.device).astype(jnp.float32)

    def top(self) -> dict:
        if self._top is None:
            self._top = {k: self._f32(self._get(self.params, p))
                         for k, p in self.map["top"].items()}
        return self._top

    def layer(self, l: int) -> dict:
        group = self.params[self.map["layers_root"]][l % self.period]
        kind = "attn_layer" if "attn" in group else "mamba_layer"
        return {k: self._f32(self._get(group, p)[l // self.period])
                for k, p in {**self.map[kind], **self.map["mlp_layer"]}.items()}


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def gqa(cfg, w, a):
    """a [B, S, D] (normed) -> the grouped-query attention's output."""
    B, S, D = a.shape
    H, KV, hd = cfg["n_head"], cfg["n_kv_head"], cfg["head_dim"]
    q = (a @ w["wq"]).reshape(B, S, KV, H // KV, hd)
    k = (a @ w["wk"]).reshape(B, S, KV, hd)
    v = (a @ w["wv"]).reshape(B, S, KV, hd)
    future = jnp.arange(S)[None, :] > jnp.arange(S)[:, None]
    outs = []
    for c in range(KV):                 # one kv head's group of queries at a
        s = jnp.einsum("bigd,bjd->bgij", q[:, :, c], k[:, :, c]) \
            * cfg["attention_multiplier"]     # time: [G, S, S] scores live
        p = jax.nn.softmax(jnp.where(future[None, None], -jnp.inf, s), axis=-1)
        outs.append(jnp.einsum("bgij,bjd->bigd", p, v[:, :, c]))
    return jnp.stack(outs, axis=2).reshape(B, S, H * hd) @ w["wo"]


def conv(u, c, b):
    """u [B, S, C], c [K, C], b [C]: the causal depthwise conv, tap K-1 on
    the current input, zeros before the sequence."""
    K, S = c.shape[0], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(padded[:, j:j + S] * c[j] for j in range(K)) + b


def mamba2(cfg, w, a):
    """a [B, S, D] (normed) -> the Mamba-2 mixer's output, the state carried
    token by token from zero."""
    B, S, D = a.shape
    H, P, N = cfg["ssm_heads"], cfg["ssm_head_dim"], cfg["ssm_state"]
    G = cfg.get("ssm_groups", 1)
    inner = H * P
    u = a @ w["w_in"]
    z, xbc, dt = jnp.split(u, [inner, 2 * inner + 2 * G * N], axis=-1)
    xbc = jax.nn.silu(conv(xbc, w["conv"], w["conv_b"]))
    x, Bm, Cm = jnp.split(xbc, [inner, inner + G * N], axis=-1)
    x = x.reshape(B, S, H, P)
    # head h reads group h // (H / G)
    Bm = jnp.repeat(Bm.reshape(B, S, G, N), H // G, axis=2)
    Cm = jnp.repeat(Cm.reshape(B, S, G, N), H // G, axis=2)
    dt = jax.nn.softplus(dt + w["dt_bias"])                  # [B, S, H]
    A = -jnp.exp(w["A_log"])                                 # [H]

    def token(state, xs):
        xt, bt, ct, dtt = xs            # [B, H, P] [B, H, N] [B, H, N] [B, H]
        state = jnp.exp(dtt * A)[..., None, None] * state \
            + (dtt[..., None] * xt)[..., None] * bt[..., None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, ct)

    seq = lambda t: jnp.moveaxis(t, 1, 0)                  # noqa: E731
    _, y = jax.lax.scan(token, jnp.zeros((B, H, P, N), jnp.float32),
                        (seq(x), seq(Bm), seq(Cm), seq(dt)))
    y = jnp.moveaxis(y, 0, 1) + w["D"][:, None] * x
    y = y.reshape(B, S, inner) * jax.nn.silu(z)
    return _rms(y, w["norm_g"], cfg["eps"]) @ w["w_out"]


def mlp(w, m):
    return (jax.nn.silu(m @ w["w_gate"]) * (m @ w["w_up"])) @ w["w_down"]


class _Cfg(dict):
    """A configuration jit can take as a static argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


@jax.jit
def _embed(wte, tokens, em):
    return em * wte[tokens]


def layer(cfg, w, x):
    """One layer on x [B, S, D]: its mixer is the one whose weights ``w``
    holds."""
    rm = cfg["residual_multiplier"]
    mixer = mamba2 if "w_in" in w else gqa
    h = x + rm * mixer(cfg, w, _rms(x, w["ln1_g"], cfg["eps"]))
    return h + rm * mlp(w, _rms(h, w["ln2_g"], cfg["eps"]))


_layer = jax.jit(layer, static_argnums=0)


def final_hidden(cfg, weights, tokens):
    """RMS_f(x_L) for tokens [B, S]; ``weights`` gives ``top()`` and
    ``layer(l)`` dicts under the map's names."""
    cfg = _Cfg(cfg)
    with jax.default_matmul_precision("highest"):
        top = weights.top()
        x = _embed(top["wte"], tokens, cfg["embedding_multiplier"])
        for l in range(cfg["n_layer"]):
            w = weights.layer(l)
            want = "w_in" if cfg["layer_types"][l] == "mamba" else "wq"
            if want not in w:
                raise ValueError(f"layer {l} is {cfg['layer_types'][l]!r} in "
                                 "the configuration and not in the weights")
            x = _layer(cfg, w, x)
        return _rms(x, top["lnf_g"], cfg["eps"])


def logits_rows(cfg, weights, h_rows):
    """h_rows [N, D] -> logits [N, V] through the tied head, over
    ``logits_scaling``."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda h, e: h @ e.T)(h_rows, weights.top()["wte"]) \
            / cfg["logits_scaling"]


def next_token_loss(cfg, weights, tokens):
    """Mean next-token cross-entropy of tokens [B, S] (no auxiliary term:
    module docstring)."""
    h = final_hidden(cfg, weights, tokens)
    logits = logits_rows(cfg, weights, h[:, :-1].reshape(-1, h.shape[-1]))
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:].reshape(-1, 1), axis=-1)
    return float(-jnp.mean(picked))
