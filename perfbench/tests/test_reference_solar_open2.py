"""``reference/solar_open2_decoder.py`` against a hand-written four-layer
case: the same equations token by token, head by head, channel by channel and
expert by expert in float64 NumPy loops (a gated GQA layer without positions,
three KDA layers with their conv and their state, a sigmoid router with a
selection bias over E experts of which a share is held, a shared expert, an
untied head), and ``costs_linear_attn`` by hand."""

import math

import jax.numpy as jnp
import numpy as np

import costs_linear_attn
from reference import solar_open2_decoder as ref

D, H, KV, HD, HL, DK, KC, V, S, L = 12, 4, 2, 5, 2, 4, 4, 11, 9, 4
E, HELD, FIRST, K, F, R = 6, 3, 2, 2, 7, 3
CFG = {"n_layer": L, "n_head": H, "n_kv_head": KV, "head_dim": HD, "d_model": D,
       "eps": 1e-5, "gqa_interval": 3, "lin_heads": HL, "lin_head_dim": DK,
       "beta_scale": 2.0, "n_experts": E, "experts_held": HELD,
       "expert_offset": FIRST, "experts_per_token": K, "d_expert": F,
       "norm_topk_prob": True, "routed_scaling": 1.0}


class Weights:
    def __init__(self, rng):
        g = lambda *shape: rng.standard_normal(shape) * 0.5  # noqa: E731
        self._top = {"wte": g(V, D), "head": g(D, V), "lnf_g": 1 + 0.1 * g(D)}
        moe = lambda: {  # noqa: E731
            "ln2_g": 1 + 0.1 * g(D), "router": g(D, E) * 2, "b_select": g(E),
            "w_gate": g(HELD, D, F), "w_up": g(HELD, D, F), "w_down": g(HELD, F, D),
            "shared_gate": g(D, F), "shared_up": g(D, F), "shared_down": g(F, D)}
        C = HL * DK
        self._layers = [{
            "ln1_g": 1 + 0.1 * g(D), "wq": g(D, H * HD), "wk": g(D, KV * HD),
            "wv": g(D, KV * HD), "wo": g(H * HD, D), "w_gate_attn": g(D, H * HD),
            **moe()}] + [{
                "ln1_g": 1 + 0.1 * g(D), "wq": g(D, C), "wk": g(D, C), "wv": g(D, C),
                "wo": g(C, D), "conv": g(KC, 3 * C), "wf1": g(D, R), "wf2": g(R, C),
                "A_log": np.log(1 + 3 * rng.random(HL)), "dt_bias": g(C) - 1,
                "wb": g(D, HL), "wg1": g(D, R), "wg2": g(R, C), "bg": g(C),
                "o_g": 1 + 0.1 * g(DK), **moe()} for _ in range(L - 1)]

    def top(self):
        return {k: jnp.asarray(v, jnp.float32) for k, v in self._top.items()}

    def layer(self, l):
        return {k: jnp.asarray(v, jnp.float32) for k, v in self._layers[l].items()}


def rms(x, g, eps=1e-5):
    return x / math.sqrt((x ** 2).mean() + eps) * g


def sigmoid(x):
    return 1 / (1 + np.exp(-x))


def silu(x):
    return x * sigmoid(x)


def gqa_by_hand(w, xs):
    a = [rms(x, w["ln1_g"]) for x in xs]
    q = [(v @ w["wq"]).reshape(H, HD) for v in a]
    k = [(v @ w["wk"]).reshape(KV, HD) for v in a]
    vv = [(v @ w["wv"]).reshape(KV, HD) for v in a]
    out = []
    for i in range(len(xs)):
        heads = np.zeros((H, HD))
        for h in range(H):
            c = h // (H // KV)
            s = np.array([q[i][h] @ k[j][c] / math.sqrt(HD) for j in range(i + 1)])
            p = np.exp(s - s.max())
            p /= p.sum()
            heads[h] = sum(p[j] * vv[j][c] for j in range(i + 1))
        gate = sigmoid(a[i] @ w["w_gate_attn"])
        out.append(xs[i] + (heads.reshape(-1) * gate) @ w["wo"])
    return out


def kda_by_hand(w, xs):
    C = HL * DK
    a = [rms(x, w["ln1_g"]) for x in xs]
    pre = {n: [v @ w[n] for v in a] for n in ("wq", "wk", "wv")}
    taps = {"wq": w["conv"][:, :C], "wk": w["conv"][:, C:2 * C], "wv": w["conv"][:, 2 * C:]}

    def conv(n, t):
        acc = np.zeros(C)
        for j in range(KC):
            src = t - (KC - 1) + j
            if src >= 0:
                acc += taps[n][j] * pre[n][src]
        return silu(acc).reshape(HL, DK)

    state = np.zeros((HL, DK, DK))
    out = []
    for t in range(len(xs)):
        q, k, v = conv("wq", t), conv("wk", t), conv("wv", t)
        g = -np.exp(w["A_log"])[:, None] * np.log1p(np.exp(
            ((a[t] @ w["wf1"]) @ w["wf2"] + w["dt_bias"]).reshape(HL, DK)))
        beta = 2.0 * sigmoid(a[t] @ w["wb"])
        o = np.zeros((HL, DK))
        for h in range(HL):
            qh = q[h] / math.sqrt(q[h] @ q[h] + 1e-6) / math.sqrt(DK)
            kh = k[h] / math.sqrt(k[h] @ k[h] + 1e-6)
            s1 = np.exp(g[h])[:, None] * state[h]
            state[h] = s1 + beta[h] * np.outer(kh, v[h] - s1.T @ kh)
            o[h] = rms(state[h].T @ qh, w["o_g"])
        gate = sigmoid((a[t] @ w["wg1"]) @ w["wg2"] + w["bg"])
        out.append(xs[t] + (o.reshape(-1) * gate) @ w["wo"])
    return out


def moe_by_hand(w, hs):
    ffn = lambda m, g, u, d: (silu(m @ g) * (m @ u)) @ d  # noqa: E731
    out, routed = [], []
    for h in hs:
        m = rms(h, w["ln2_g"])
        s = sigmoid(m @ w["router"])
        top = np.argsort(-(s + w["b_select"]))[:K]
        total = s[top].sum() + 1e-20
        y = h + ffn(m, w["shared_gate"], w["shared_up"], w["shared_down"])
        for e in top:
            if FIRST <= e < FIRST + HELD:      # the others live on other chips
                j = e - FIRST
                y = y + s[e] / total * ffn(m, w["w_gate"][j], w["w_up"][j], w["w_down"][j])
        out.append(y)
        routed.append(sorted(top))
    return out, routed


def by_hand(w, tokens):
    xs = [w._top["wte"][t].copy() for t in tokens]
    routed = []
    for l in range(L):
        lw = w._layers[l]
        hs = gqa_by_hand(lw, xs) if l % 4 == 0 else kda_by_hand(lw, xs)
        xs, r = moe_by_hand(lw, hs)
        routed.append(r)
    return np.stack([rms(x, w._top["lnf_g"]) @ w._top["head"] for x in xs]), routed


def test_the_reference_against_the_hand_written_case():
    rng = np.random.default_rng(31)
    w = Weights(rng)
    tokens = rng.integers(0, V, size=(2, S))
    h = ref.final_hidden(CFG, w, jnp.asarray(tokens))
    got = np.asarray(ref.logits_rows(CFG, w, h.reshape(-1, D))).reshape(2, S, V)
    some_left_out = False
    for b in range(2):
        want, routed = by_hand(w, tokens[b])
        # float32 against float64: 1e-4 of logits of magnitude ~3
        np.testing.assert_allclose(got[b], want, atol=2e-4, rtol=2e-4)
        some_left_out |= any(e < FIRST or e >= FIRST + HELD
                             for layer in routed for tok in layer for e in tok)
    assert some_left_out           # the share left something to the other chips
    loss = ref.next_token_loss(CFG, w, jnp.asarray(tokens))
    want = np.mean([-(lg[i] - np.log(np.exp(lg[i]).sum()))[t[i + 1]]
                    for lg, t in ((by_hand(w, tokens[b])[0], tokens[b]) for b in range(2))
                    for i in range(S - 1)])
    assert abs(loss - want) < 2e-4


def test_the_state_carries_what_the_conv_and_the_decay_say():
    """The KDA layer is causal and its first token sees an empty state: a
    sequence's prefix gives the prefix of its outputs."""
    rng = np.random.default_rng(7)
    w = Weights(rng)
    tokens = rng.integers(0, V, size=(1, S))
    full = np.asarray(ref.final_hidden(CFG, w, jnp.asarray(tokens)))
    part = np.asarray(ref.final_hidden(CFG, w, jnp.asarray(tokens[:, :5])))
    np.testing.assert_allclose(full[:, :5], part, atol=1e-5)


def test_costs_by_hand():
    # 128 rows x 64 heads x 128 x 128 float32, read and written
    flops, nbytes = costs_linear_attn.kda_decode_update(
        {"state_rows": 128, "lin_heads": 64, "lin_head_dim": 128})
    assert nbytes == 128 * 64 * 128 * 128 * 4 * 2 == 1_073_741_824
    assert flops == 7 * 128 * 64 * 128 * 128
    # memory-bound by far on a v5e: 1.31 ms of bytes against 0.005 ms of flops
    assert nbytes / 819e9 > 100 * flops / 197e12
