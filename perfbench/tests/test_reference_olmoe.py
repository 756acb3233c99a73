"""``reference/olmoe_decoder.py`` against a hand-written two-layer case: the
same equations token by token, head by head, pair by pair and expert by expert
in float64 NumPy (query/key RMSNorm over the whole projection, top-K of a
softmax over E experts with the weights as they are, gated SiLU experts, an
untied head, cross-entropy + the load-balancing term)."""

import math

import jax.numpy as jnp
import numpy as np
import pytest

from reference import olmoe_decoder as ref

D, H, F, V, S, L, E, K = 16, 2, 12, 11, 6, 2, 5, 2
HD = D // H
CFG = {"n_layer": L, "n_head": H, "d_model": D, "eps": 1e-5,
       "rope_theta": 10000.0, "n_experts": E, "experts_per_token": K,
       "d_expert": F, "aux_loss_coef": 0.01}


class Weights:
    def __init__(self, rng):
        g = lambda *shape: rng.standard_normal(shape) * 0.5
        self._top = {"wte": g(V, D), "head": g(D, V), "lnf_g": 1 + 0.1 * g(D)}
        self._layers = [{
            "ln1_g": 1 + 0.1 * g(D), "wq": g(D, D), "wk": g(D, D),
            "wv": g(D, D), "wo": g(D, D), "q_g": 1 + 0.1 * g(D),
            "k_g": 1 + 0.1 * g(D), "ln2_g": 1 + 0.1 * g(D),
            "router": g(D, E) * 2, "w_gate": g(E, D, F), "w_up": g(E, D, F),
            "w_down": g(E, F, D)} for _ in range(L)]

    def top(self):
        return {k: jnp.asarray(v, jnp.float32) for k, v in self._top.items()}

    def layer(self, l):
        return {k: jnp.asarray(v, jnp.float32) for k, v in self._layers[l].items()}


def rms(x, g, eps):
    return x / math.sqrt((x ** 2).mean() + eps) * g


def turn(u, m, theta):
    out = u.copy()
    half = len(u) // 2
    for i in range(half):
        ang = m * theta ** (-2.0 * i / len(u))
        a, b = u[i], u[i + half]
        out[i] = a * math.cos(ang) - b * math.sin(ang)
        out[i + half] = b * math.cos(ang) + a * math.sin(ang)
    return out


def by_hand(cfg, w, tokens):
    """(logits [S, V], per layer (counts [E], summed probabilities [E])) for
    one sequence, loops only."""
    top, eps, theta = w._top, cfg["eps"], cfg["rope_theta"]
    xs = [top["wte"][t].copy() for t in tokens]
    routed = []
    for lw in w._layers:
        a = [rms(x, lw["ln1_g"], eps) for x in xs]
        # the norm over the whole projection, then the split into heads
        q = [rms(v @ lw["wq"], lw["q_g"], eps) for v in a]
        k = [rms(v @ lw["wk"], lw["k_g"], eps) for v in a]
        val = [v @ lw["wv"] for v in a]
        counts, prob_sum, nxt = np.zeros(E), np.zeros(E), []
        for i, x in enumerate(xs):
            heads = []
            for h in range(H):
                sl = slice(h * HD, (h + 1) * HD)
                qi = turn(q[i][sl], i, theta)
                scores = np.array([qi @ turn(k[j][sl], j, theta) / math.sqrt(HD)
                                   for j in range(i + 1)])
                p = np.exp(scores - scores.max())
                p /= p.sum()
                heads.append(sum(p[j] * val[j][sl] for j in range(i + 1)))
            hid = x + np.concatenate(heads) @ lw["wo"]
            m = rms(hid, lw["ln2_g"], eps)
            logits = m @ lw["router"]
            r = np.exp(logits - logits.max())
            r /= r.sum()
            prob_sum += r
            out = hid.copy()
            for e in np.argsort(-r)[:K]:
                counts[e] += 1
                gate = m @ lw["w_gate"][e]
                silu = gate / (1 + np.exp(-gate))
                # the weight as it is: not divided by the K weights' sum
                out += r[e] * ((silu * (m @ lw["w_up"][e])) @ lw["w_down"][e])
            nxt.append(out)
        xs = nxt
        routed.append((counts, prob_sum))
    return np.stack([rms(x, top["lnf_g"], eps) @ top["head"] for x in xs]), routed


def test_reference_against_the_hand_written_case():
    rng = np.random.default_rng(0)
    w = Weights(rng)
    tokens = rng.integers(0, V, size=(2, S))
    h = ref.final_hidden(CFG, w, jnp.asarray(tokens))
    got = np.asarray(ref.logits_rows(CFG, w, h.reshape(-1, D))).reshape(2, S, V)
    hand = [by_hand(CFG, w, t) for t in tokens]
    want = np.stack([lg for lg, _ in hand])
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    # the loss: cross-entropy in blocks over the head's columns (4 does not
    # divide 11) + 0.01 x the load-balancing term over BOTH sequences' tokens
    lse = np.log(np.exp(want[:, :-1]).sum(-1))
    picked = np.take_along_axis(want[:, :-1], tokens[:, 1:, None], -1)[..., 0]
    n = tokens.size
    aux = np.mean([
        E * np.sum(sum(hand[b][1][l][0] for b in range(2)) / (n * K)
                   * sum(hand[b][1][l][1] for b in range(2)) / n)
        for l in range(L)])
    assert 0.9 < aux < E
    assert float(ref.loss_value(CFG, w, jnp.asarray(tokens), vocab_block=4)) == \
        pytest.approx(float((lse - picked).mean()) + 0.01 * aux, abs=2e-4)


def test_renormalised_weights_are_a_flag():
    rng = np.random.default_rng(1)
    w = Weights(rng)
    x = jnp.asarray(rng.standard_normal((1, S, D)), jnp.float32)
    lw = w.layer(0)
    _, r, c = ref.route(CFG, lw, x)
    assert np.all((np.asarray(c) > 0).sum(-1) == K)
    assert float(np.asarray(c).sum(-1).max()) < 1.0
    np.testing.assert_allclose(np.asarray(c)[np.asarray(c) > 0],
                               np.asarray(r)[np.asarray(c) > 0])
    _, _, cn = ref.route({**CFG, "norm_topk_prob": True}, lw, x)
    np.testing.assert_allclose(np.asarray(cn).sum(-1), 1.0, rtol=1e-6)
