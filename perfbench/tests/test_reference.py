"""The plain reference against a hand-written two-layer case: the same
equations written out position by position and head by head in float64
NumPy, for both of the benchmark's decoder styles."""

import math

import jax.numpy as jnp
import numpy as np
import pytest

from reference import dense_decoder as ref

D, H, F, V, S, L = 8, 2, 16, 11, 5, 2


class Weights:
    def __init__(self, rng, positions):
        g = lambda *shape: rng.standard_normal(shape) * 0.5
        self._top = {"wte": g(V, D), "lnf_g": 1 + 0.1 * g(D), "lnf_b": 0.1 * g(D),
                     "emb_ln_g": 1 + 0.1 * g(D), "emb_ln_b": 0.1 * g(D)}
        if positions == "learned":
            self._top["wpe"] = g(S + 3, D)
        self._layers = [{
            "ln1_g": 1 + 0.1 * g(D), "ln1_b": 0.1 * g(D),
            "wq": g(D, D), "bq": 0.1 * g(D), "wk": g(D, D), "bk": 0.1 * g(D),
            "wv": g(D, D), "bv": 0.1 * g(D), "wo": g(D, D), "bo": 0.1 * g(D),
            "ln2_g": 1 + 0.1 * g(D), "ln2_b": 0.1 * g(D),
            "w1": g(D, F), "b1": 0.1 * g(F), "w2": g(F, D), "b2": 0.1 * g(D)}
            for _ in range(L)]

    def top(self):
        return {k: jnp.asarray(v, jnp.float32) for k, v in self._top.items()}

    def layer(self, l):
        return {k: jnp.asarray(v, jnp.float32) for k, v in self._layers[l].items()}


def ln(x, g, b, eps):
    mu = x.mean()
    return (x - mu) / math.sqrt(((x - mu) ** 2).mean() + eps) * g + b


def by_hand(cfg, w, tokens):
    """logits [S, V] for one sequence, loops only."""
    top, eps, hd = w._top, cfg["eps"], D // H
    xs = []
    for i, t in enumerate(tokens):
        x = top["wte"][t].copy()
        if cfg["positions"] == "learned":
            x = x + top["wpe"][i]
        if cfg.get("embed_layernorm"):
            x = ln(x, top["emb_ln_g"], top["emb_ln_b"], eps)
        xs.append(x)
    for lw in w._layers:
        a = [ln(x, lw["ln1_g"], lw["ln1_b"], eps) for x in xs]
        q = [v @ lw["wq"] + lw["bq"] for v in a]
        k = [v @ lw["wk"] + lw["bk"] for v in a]
        val = [v @ lw["wv"] + lw["bv"] for v in a]
        out = []
        for i in range(len(xs)):
            heads = []
            for h in range(H):
                sl = slice(h * hd, (h + 1) * hd)
                scores = []
                for j in range(i + 1):
                    s = q[i][sl] @ k[j][sl] / math.sqrt(hd)
                    if cfg["positions"] == "alibi":
                        s += 2.0 ** (-8.0 * (h + 1) / H) * (j - i)
                    scores.append(s)
                p = np.exp(np.array(scores) - max(scores))
                p /= p.sum()
                heads.append(sum(p[j] * val[j][sl] for j in range(i + 1)))
            out.append(np.concatenate(heads) @ lw["wo"] + lw["bo"])
        xs = [x + o for x, o in zip(xs, out)]
        nxt = []
        for x in xs:
            m = ln(x, lw["ln2_g"], lw["ln2_b"], eps) @ lw["w1"] + lw["b1"]
            if cfg["activation"] == "relu":
                m = np.maximum(m, 0)
            else:
                m = 0.5 * m * (1 + np.tanh(math.sqrt(2 / math.pi) * (m + 0.044715 * m ** 3)))
            nxt.append(x + m @ lw["w2"] + lw["b2"])
        xs = nxt
    return np.stack([ln(x, top["lnf_g"], top["lnf_b"], eps) @ top["wte"].T for x in xs])


@pytest.mark.parametrize("style", [
    {"positions": "alibi", "activation": "gelu_tanh", "embed_layernorm": True},
    {"positions": "learned", "activation": "relu", "embed_layernorm": False}],
    ids=["bloom-style", "opt-style"])
def test_reference_against_the_hand_written_case(style):
    cfg = {"n_layer": L, "n_head": H, "d_model": D, "d_ff": F, "eps": 1e-5, **style}
    rng = np.random.default_rng(0)
    w = Weights(rng, style["positions"])
    tokens = rng.integers(0, V, size=(2, S))
    h = ref.final_hidden(cfg, w, jnp.asarray(tokens))
    got = np.asarray(ref.logits_rows(cfg, w, h.reshape(-1, D))).reshape(2, S, V)
    want = np.stack([by_hand(cfg, w, t) for t in tokens])
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    # the loss in vocabulary blocks (a block of 4 does not divide 11)
    lse = np.log(np.exp(want[:, :-1]).sum(-1))
    picked = np.take_along_axis(want[:, :-1], tokens[:, 1:, None], -1)[..., 0]
    assert ref.next_token_loss(cfg, w, jnp.asarray(tokens), vocab_block=4) == \
        pytest.approx(float((lse - picked).mean()), abs=2e-4)


def test_alibi_slopes_for_sixteen_heads():
    s = np.asarray(ref.alibi_slopes(16))
    assert s[0] == pytest.approx(2 ** -0.5) and s[-1] == pytest.approx(2 ** -8)
    with pytest.raises(ValueError):
        ref.alibi_slopes(12)


def test_the_map_names_the_reference_and_its_view_of_the_weights(monkeypatch):
    import correctness
    from reference import parallel_rope_decoder

    assert correctness.load_reference({}) is ref
    named = {"reference": "parallel_rope_decoder"}
    assert correctness.load_reference(named) is parallel_rope_decoder
    plain = correctness.Weights({}, {}, device="cpu:0")
    assert correctness._reference_of(plain) == (ref, plain)

    class Own(correctness.Weights):
        """a stack whose layers are not one leading axis brings its own"""

    monkeypatch.setattr(parallel_rope_decoder, "Weights", Own, raising=False)
    params = {"layers": {}}
    module, view = correctness._reference_of(
        correctness.Weights(params, named, device="cpu:0"))
    assert module is parallel_rope_decoder and type(view) is Own
    assert (view.params, view.map, view.device) == (params, named, "cpu:0")
