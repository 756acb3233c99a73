"""``reference/smallthinker_decoder.py`` against a hand-written case: the
same equations position by position, head by head and expert by expert in
float64 NumPy loops (a full layer without positions, rope layers with a
window, GQA, a router that reads the attention's input and takes a softmax
over its chosen logits, ReLU-gated experts, an untied head); the blocked
attention against a sequence longer than a block of queries; and that each
thing the family does differently is seen by the case."""

import math

import jax.numpy as jnp
import numpy as np
import pytest

from reference import smallthinker_decoder as ref

D, H, KV, HD, V, L = 12, 4, 2, 6, 13, 4
E, K, F, W = 6, 2, 7, 5
CFG = {"n_layer": L, "n_head": H, "n_kv_head": KV, "head_dim": HD, "d_model": D,
       "eps": 1e-6, "rope_theta": 100.0, "window": W,
       "window_layout": [0, 1, 1, 1], "rope_layout": [0, 1, 1, 1],
       "n_experts": E, "experts_per_token": K, "d_expert": F,
       "norm_topk_prob": True}


class Weights:
    def __init__(self, rng):
        g = lambda *shape: rng.standard_normal(shape) * 0.5  # noqa: E731
        self._top = {"wte": g(V, D), "head": g(D, V), "lnf_g": 1 + 0.1 * g(D)}
        self._layers = [{
            "ln1_g": 1 + 0.1 * g(D), "wq": g(D, H * HD), "wk": g(D, KV * HD),
            "wv": g(D, KV * HD), "wo": g(H * HD, D),
            "ln2_g": 1 + 0.1 * g(D), "router": g(D, E) * 2,
            "w_gate": g(E, D, F), "w_up": g(E, D, F),
            "w_down": g(E, F, D)} for _ in range(L)]

    def top(self):
        return {k: jnp.asarray(v, jnp.float32) for k, v in self._top.items()}

    def layer(self, l):
        return {k: jnp.asarray(v, jnp.float32) for k, v in self._layers[l].items()}


def rms(x, g, eps=1e-6):
    return x / math.sqrt((x ** 2).mean() + eps) * g


def turn(u, m, theta=100.0):
    half = u.size // 2
    out = u.copy()
    for i in range(half):
        th = theta ** (-2.0 * i / u.size)
        c, s = math.cos(m * th), math.sin(m * th)
        out[i] = u[i] * c - u[i + half] * s
        out[i + half] = u[i + half] * c + u[i] * s
    return out


def by_hand(w: Weights, tokens, window=W, rope_full=False, route_from="a",
            gate="relu"):
    """The whole forward of ``tokens``, float64 loops. The keywords plant
    one departure each (the controls)."""
    S = len(tokens)
    x = np.stack([w._top["wte"][t] for t in tokens]).astype(np.float64)
    for l, lw in enumerate(w._layers):
        full = l % 4 == 0
        a = np.stack([rms(x[i], lw["ln1_g"]) for i in range(S)])
        q = np.zeros((S, H, HD))
        k = np.zeros((S, KV, HD))
        v = np.zeros((S, KV, HD))
        for i in range(S):
            qi, ki = a[i] @ lw["wq"], a[i] @ lw["wk"]
            v[i] = (a[i] @ lw["wv"]).reshape(KV, HD)
            for h in range(H):
                u = qi[h * HD:(h + 1) * HD]
                q[i, h] = u if full and not rope_full else turn(u, i)
            for c in range(KV):
                u = ki[c * HD:(c + 1) * HD]
                k[i, c] = u if full and not rope_full else turn(u, i)
        h_ = x.copy()
        for i in range(S):
            first = 0 if full or not window else max(0, i - window + 1)
            o = np.zeros(H * HD)
            for h in range(H):
                c = h // (H // KV)
                s = np.array([q[i, h] @ k[j, c] / math.sqrt(HD)
                              for j in range(first, i + 1)])
                p = np.exp(s - s.max())
                p /= p.sum()
                o[h * HD:(h + 1) * HD] = sum(
                    p[j - first] * v[j, c] for j in range(first, i + 1))
            h_[i] = x[i] + o @ lw["wo"]
        out = h_.copy()
        for i in range(S):
            m = rms(h_[i], lw["ln2_g"])
            z = (a[i] if route_from == "a" else m) @ lw["router"]
            top = np.argsort(-z)[:K]
            cw = np.exp(z[top] - z[top].max())
            cw /= cw.sum()
            for c_e, e in zip(cw, top):
                gt = m @ lw["w_gate"][e]
                gt = np.maximum(gt, 0) if gate == "relu" else gt / (1 + np.exp(-gt))
                out[i] += c_e * ((gt * (m @ lw["w_up"][e])) @ lw["w_down"][e])
        x = out
    return np.stack([rms(x[i], w._top["lnf_g"]) for i in range(S)])


@pytest.fixture(scope="module")
def case():
    rng = np.random.default_rng(37)
    return Weights(rng), rng.integers(0, V, size=11)


def test_final_hidden_is_the_hand_written_forward(case):
    w, tokens = case
    got = np.asarray(ref.final_hidden(CFG, w, jnp.asarray(tokens[None])))[0]
    np.testing.assert_allclose(got, by_hand(w, tokens), rtol=2e-4, atol=2e-5)


def test_queries_in_blocks_give_the_same_sum(case, monkeypatch):
    """A sequence of 11 in blocks of 4 queries (the last one padded)."""
    w, tokens = case
    whole = np.asarray(ref.final_hidden(CFG, w, jnp.asarray(tokens[None])))
    monkeypatch.setattr(ref, "Q_BLOCK", 4)
    monkeypatch.setattr(ref, "_attention", ref.attention)
    blocked = np.asarray(ref.final_hidden(CFG, w, jnp.asarray(tokens[None])))
    np.testing.assert_allclose(blocked, whole, rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("departure", [
    {"window": 0}, {"rope_full": True}, {"route_from": "m"}, {"gate": "silu"}])
def test_the_case_sees_each_thing_the_family_does_differently(case, departure):
    """The window left out, rope on the full layer, a router fed the
    experts' input, silu for relu: each moves the hand-written forward far
    beyond the tolerance the reference is held to."""
    w, tokens = case
    got = np.asarray(ref.final_hidden(CFG, w, jnp.asarray(tokens[None])))[0]
    other = by_hand(w, tokens, **departure)
    assert np.abs(got - other).max() > 1e-2


def test_logits_and_loss_by_hand(case):
    w, tokens = case
    h = by_hand(w, tokens)
    logits = h @ w._top["head"]
    got = np.asarray(ref.logits_rows(CFG, w, jnp.asarray(h, jnp.float32)))
    np.testing.assert_allclose(got, logits, rtol=2e-4, atol=2e-5)
    lse = np.log(np.exp(logits[:-1]).sum(axis=-1))
    want = float(np.mean(lse - logits[np.arange(len(tokens) - 1), tokens[1:]]))
    assert abs(ref.next_token_loss(CFG, w, jnp.asarray(tokens[None])) - want) < 2e-4
