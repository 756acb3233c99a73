"""``reference/sdar_decoder.py`` against a hand-written two-block case: the
same equations position by position, head by head and expert by expert in
float64 NumPy loops (GQA with a per-head q/k RMSNorm and rope, the block
mask, a softmax router with normalised top-k weights over E experts of
which a share is held, an untied head); the blockwise form of
``final_hidden`` against the naive whole-sequence forward of every row, for
every prompt remainder; and the generation loop's three rules by hand."""

import math

import jax.numpy as jnp
import numpy as np
import pytest

from reference import sdar_decoder as ref

D, H, KV, HD, V, L, B = 12, 4, 2, 6, 13, 2, 4
E, HELD, FIRST, K, F = 6, 3, 2, 2, 7
MASK = V - 1
CFG = {"n_layer": L, "n_head": H, "n_kv_head": KV, "head_dim": HD, "d_model": D,
       "eps": 1e-6, "rope_theta": 100.0, "n_experts": E, "experts_held": HELD,
       "expert_offset": FIRST, "experts_per_token": K, "d_expert": F,
       "norm_topk_prob": True, "block": B, "steps": 4, "rule": "sequential",
       "threshold": 0.9, "mask_id": MASK}


class Weights:
    def __init__(self, rng):
        g = lambda *shape: rng.standard_normal(shape) * 0.5  # noqa: E731
        self._top = {"wte": g(V, D), "head": g(D, V), "lnf_g": 1 + 0.1 * g(D)}
        self._layers = [{
            "ln1_g": 1 + 0.1 * g(D), "wq": g(D, H * HD), "wk": g(D, KV * HD),
            "wv": g(D, KV * HD), "wo": g(H * HD, D),
            "q_g": 1 + 0.1 * g(HD), "k_g": 1 + 0.1 * g(HD),
            "ln2_g": 1 + 0.1 * g(D), "router": g(D, E) * 2,
            "w_gate": g(HELD, D, F), "w_up": g(HELD, D, F),
            "w_down": g(HELD, F, D)} for _ in range(L)]

    def top(self):
        return {k: jnp.asarray(v, jnp.float32) for k, v in self._top.items()}

    def layer(self, l):
        return {k: jnp.asarray(v, jnp.float32) for k, v in self._layers[l].items()}


def rms(x, g, eps=1e-6):
    return x / math.sqrt((x ** 2).mean() + eps) * g


def silu(x):
    return x / (1 + np.exp(-x))


def turn(u, m, theta=100.0):
    half = u.size // 2
    out = u.copy()
    for i in range(half):
        th = theta ** (-2.0 * i / u.size)
        c, s = math.cos(m * th), math.sin(m * th)
        out[i] = u[i] * c - u[i + half] * s
        out[i + half] = u[i + half] * c + u[i] * s
    return out


def by_hand(w: Weights, tokens):
    """The whole forward of ``tokens`` under the block mask, float64 loops."""
    S = len(tokens)
    x = np.stack([w._top["wte"][t] for t in tokens]).astype(np.float64)
    for lw in w._layers:
        a = np.stack([rms(x[i], lw["ln1_g"]) for i in range(S)])
        q = np.zeros((S, H, HD))
        k = np.zeros((S, KV, HD))
        v = np.zeros((S, KV, HD))
        for i in range(S):
            qi, ki = a[i] @ lw["wq"], a[i] @ lw["wk"]
            v[i] = (a[i] @ lw["wv"]).reshape(KV, HD)
            for h in range(H):
                q[i, h] = turn(rms(qi[h * HD:(h + 1) * HD], lw["q_g"]), i)
            for c in range(KV):
                k[i, c] = turn(rms(ki[c * HD:(c + 1) * HD], lw["k_g"]), i)
        out = np.zeros((S, H * HD))
        for i in range(S):
            sees = [j for j in range(S) if j // B <= i // B]
            for h in range(H):
                c = h // (H // KV)
                s = np.array([q[i, h] @ k[j, c] for j in sees]) / math.sqrt(HD)
                p = np.exp(s - s.max())
                p /= p.sum()
                out[i, h * HD:(h + 1) * HD] = sum(
                    pj * v[j, c] for pj, j in zip(p, sees))
        hdn = x + out @ lw["wo"]
        x = hdn.copy()
        for i in range(S):
            m = rms(hdn[i], lw["ln2_g"])
            z = m @ lw["router"]
            r = np.exp(z - z.max())
            r /= r.sum()
            top = np.argsort(-r, kind="stable")[:K]
            for e in top:
                if FIRST <= e < FIRST + HELD:
                    j = e - FIRST
                    x[i] += r[e] / r[top].sum() * (
                        (silu(m @ lw["w_gate"][j]) * (m @ lw["w_up"][j]))
                        @ lw["w_down"][j])
    return np.stack([rms(x[i], w._top["lnf_g"]) for i in range(S)])


@pytest.fixture(scope="module")
def w():
    return Weights(np.random.default_rng(33))


def test_the_whole_forward_is_the_hand_written_one(w):
    """Two whole blocks: position 3 sees position 0..3 and nothing of block
    1; position 4 sees all eight."""
    tokens = [3, 7, MASK, 0, 5, 5, 9, 1]
    got = np.asarray(ref.forward_full(CFG, w, np.asarray(tokens)))
    np.testing.assert_allclose(got, by_hand(w, tokens), atol=2e-5)
    # the mask, seen from outside: a change in block 1 leaves block 0 alone,
    # a change at position 3 moves position 0
    other = np.asarray(ref.forward_full(CFG, w, np.asarray(tokens[:4] + [2, 2, 2, 2])))
    np.testing.assert_allclose(other[:4], got[:4], atol=1e-6)
    moved = np.asarray(ref.forward_full(CFG, w, np.asarray(tokens[:3] + [8] + tokens[4:])))
    assert np.abs(moved[0] - got[0]).max() > 1e-3


@pytest.mark.parametrize("n_prompt", [4, 5, 6, 7])
def test_row_r_is_the_pass_that_decided_token_r_plus_1(w, n_prompt):
    """For every prompt remainder: ``final_hidden``'s blockwise form equals,
    row by row, the naive whole-sequence forward of the deciding pass (the
    final tokens before position r + 1, ``[MASK]`` from it to the end of
    its block), which is the hand-written forward of that sequence. Where
    the prompt ended inside a block does not enter."""
    rng = np.random.default_rng(n_prompt)
    tokens = rng.integers(0, V, n_prompt + 5)
    rows = np.asarray(ref.final_hidden(CFG, w, tokens[None]))[0]
    assert rows.shape == (tokens.size, D)
    for r in range(tokens.size):
        naive = np.asarray(ref.naive_row(CFG, w, tokens, r))
        np.testing.assert_allclose(rows[r], naive, atol=2e-5)
    for r in (n_prompt - 1, tokens.size - 1):
        seq = ref.deciding_pass(CFG, tokens, r)
        assert list(seq[:r + 1]) == list(tokens[:r + 1])
        assert (seq[r + 1:] == MASK).all() and seq.size % B == 0
        np.testing.assert_allclose(rows[r], by_hand(w, list(seq))[r + 1], atol=2e-5)


def _by_hand_generate(w, prompt, max_new, rule, steps, threshold):
    seq = list(prompt)
    start = len(seq) // B * B
    while len(seq) < len(prompt) + max_new:
        tok = [MASK] * B
        dec = [False] * B
        for i, t in enumerate(seq[start:start + B]):
            tok[i], dec[i] = t, True
        i = 0
        while not all(dec):
            fed = seq[:start] + [t if d else MASK for t, d in zip(tok, dec)]
            logits = by_hand(w, fed)[start:] @ w._top["head"]
            conf = [float(np.exp(l - l.max()).max() / np.exp(l - l.max()).sum())
                    for l in logits]
            masked = [j for j in range(B) if not dec[j]]
            n = min(B // steps + (i < B % steps), len(masked))
            if rule == "sequential":
                take = masked[:n]
            else:
                take = sorted(masked, key=lambda j: (-conf[j], j))[:n]
                passing = [j for j in masked if conf[j] > threshold]
                if rule == "low_confidence_dynamic" and len(passing) >= n:
                    take = passing
            for j in take:
                tok[j], dec[j] = int(np.argmax(logits[j])), True
            i += 1
        seq = seq[:start] + tok
        start += B
    return seq[len(prompt):len(prompt) + max_new]


@pytest.mark.parametrize("rule,steps,threshold", [
    ("sequential", 4, 0.9), ("sequential", 3, 0.9),
    ("low_confidence_static", 2, 0.9), ("low_confidence_dynamic", 4, 0.2)])
def test_generate_is_the_loop_by_hand(w, rule, steps, threshold):
    cfg = {**CFG, "rule": rule, "steps": steps, "threshold": threshold}
    prompt = [3, 7, 1, 0, 5, MASK]          # a remainder of 2, a mask id in it
    want = _by_hand_generate(w, prompt, 7, rule, steps, threshold)
    rec = []
    assert ref.generate(cfg, w, prompt, 7, record=rec) == want
    # passes a block: 2 left in the first block, 4 in the second
    if rule != "low_confidence_dynamic":
        per = lambda left: -(-left // (B // steps)) if B % steps == 0 else None  # noqa: E731
        if B % steps == 0:
            assert len(rec) == per(2) + per(4) + per(4)


def test_logits_and_loss(w):
    tokens = np.asarray([[3, 7, 1, 0, 5, 2, 9]])
    h = ref.final_hidden(CFG, w, tokens)
    logits = np.asarray(ref.logits_rows(CFG, w, h[0]))
    np.testing.assert_allclose(logits, np.asarray(h[0]) @ w._top["head"], atol=2e-5)
    logp = logits[:-1] - np.log(np.exp(logits[:-1]).sum(-1, keepdims=True))
    want = -np.mean(logp[np.arange(6), tokens[0, 1:]])
    assert abs(ref.next_token_loss(CFG, w, tokens) - want) < 1e-5
