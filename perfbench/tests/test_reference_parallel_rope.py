"""``reference/parallel_rope_decoder.py`` against a hand-written two-layer
case (the same equations position by position, head by head and pair by pair
in float64 NumPy), and against the program's ``gpt_neox`` ``tiny`` preset on
seeded weights."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import correctness
from reference import parallel_rope_decoder as ref

D, H, F, V, S, L = 16, 2, 24, 11, 5, 2
HD = D // H


class Weights:
    def __init__(self, rng):
        g = lambda *shape: rng.standard_normal(shape) * 0.5
        self._top = {"wte": g(V, D), "head": g(D, V),
                     "lnf_g": 1 + 0.1 * g(D), "lnf_b": 0.1 * g(D)}
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


def turn(u, m, theta, R):
    """One head's vector at position m, pair by pair."""
    out = u.copy()
    for i in range(R // 2):
        ang = m * theta ** (-2.0 * i / R)
        a, b = u[i], u[i + R // 2]
        out[i] = a * math.cos(ang) - b * math.sin(ang)
        out[i + R // 2] = b * math.cos(ang) + a * math.sin(ang)
    return out


def by_hand(cfg, w, tokens):
    """logits [S, V] for one sequence, loops only."""
    top, eps = w._top, cfg["eps"]
    R = cfg.get("rope_dim") or HD
    xs = [top["wte"][t].copy() for t in tokens]
    for lw in w._layers:
        a = [ln(x, lw["ln1_g"], lw["ln1_b"], eps) for x in xs]
        q = [v @ lw["wq"] + lw["bq"] for v in a]
        k = [v @ lw["wk"] + lw["bk"] for v in a]
        val = [v @ lw["wv"] + lw["bv"] for v in a]
        nxt = []
        for i, x in enumerate(xs):
            heads = []
            for h in range(H):
                sl = slice(h * HD, (h + 1) * HD)
                qi = turn(q[i][sl], i, cfg["rope_theta"], R)
                scores = np.array([
                    qi @ turn(k[j][sl], j, cfg["rope_theta"], R) / math.sqrt(HD)
                    for j in range(i + 1)])
                p = np.exp(scores - scores.max())
                p /= p.sum()
                heads.append(sum(p[j] * val[j][sl] for j in range(i + 1)))
            attn = np.concatenate(heads) @ lw["wo"] + lw["bo"]
            m = ln(x, lw["ln2_g"], lw["ln2_b"], eps) @ lw["w1"] + lw["b1"]
            if cfg["activation"] == "gelu_tanh":
                m = 0.5 * m * (1 + np.tanh(math.sqrt(2 / math.pi) * (m + 0.044715 * m ** 3)))
            else:
                m = 0.5 * m * (1 + np.vectorize(math.erf)(m / math.sqrt(2)))
            # both branches read the layer's input
            nxt.append(x + attn + m @ lw["w2"] + lw["b2"])
        xs = nxt
    return np.stack([ln(x, top["lnf_g"], top["lnf_b"], eps) @ top["head"] for x in xs])


@pytest.mark.parametrize("style", [
    {"activation": "gelu_tanh"},
    {"activation": "gelu_exact", "rope_dim": 4}],
    ids=["neox-preset-style", "pythia-style"])
def test_reference_against_the_hand_written_case(style):
    cfg = {"n_layer": L, "n_head": H, "d_model": D, "eps": 1e-5,
           "rope_theta": 10000.0, **style}
    rng = np.random.default_rng(0)
    w = Weights(rng)
    tokens = rng.integers(0, V, size=(2, S))
    h = ref.final_hidden(cfg, w, jnp.asarray(tokens))
    got = np.asarray(ref.logits_rows(cfg, w, h.reshape(-1, D))).reshape(2, S, V)
    want = np.stack([by_hand(cfg, w, t) for t in tokens])
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    # the loss in blocks over the head's columns (4 does not divide 11)
    lse = np.log(np.exp(want[:, :-1]).sum(-1))
    picked = np.take_along_axis(want[:, :-1], tokens[:, 1:, None], -1)[..., 0]
    assert ref.next_token_loss(cfg, w, jnp.asarray(tokens), vocab_block=4) == \
        pytest.approx(float((lse - picked).mean()), abs=2e-4)


def test_the_first_position_is_not_turned_and_the_tail_passes():
    u = jnp.asarray(np.random.default_rng(1).standard_normal((1, 3, 2, 8)),
                    jnp.float32)
    out = np.asarray(ref.rotate(u, 10000.0, 4))
    np.testing.assert_allclose(out[:, 0], np.asarray(u)[:, 0], atol=1e-6)
    np.testing.assert_array_equal(out[..., 4:], np.asarray(u)[..., 4:])
    # a turn keeps each pair's length
    np.testing.assert_allclose((out[..., :4] ** 2).sum(-1),
                               (np.asarray(u)[..., :4] ** 2).sum(-1), rtol=1e-5)


def test_against_the_programs_gpt_neox_preset_on_seeded_weights():
    """The program's float32 forward (XLA paths on the CPU) on the toy
    configuration's own files: map, sizes and seeded weights as a run makes
    them."""
    from build_model import build_model
    from weights import make_params

    bench = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(bench, "configs", "rehearsal-neox-tiny.json")) as f:
        config = json.load(f)
    name_map = correctness.load_map("rehearsal-neox-tiny")
    assert correctness.load_reference(name_map) is ref
    cfg = correctness.reference_config(config, name_map)
    model = build_model(config["preset"])
    params = make_params(model, 7, jnp.float32, jax.devices()[:1])
    tokens = np.random.default_rng(7).integers(0, config["vocab_size"], size=(2, 48))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(model.forward(params, jnp.asarray(tokens)))
    w = correctness.Weights(params, name_map)
    h = ref.final_hidden(cfg, w, jnp.asarray(tokens))
    got = np.asarray(ref.logits_rows(cfg, w, h.reshape(-1, h.shape[-1])))
    np.testing.assert_allclose(got.reshape(want.shape), want, atol=2e-4, rtol=2e-4)
    assert ref.next_token_loss(cfg, w, jnp.asarray(tokens)) == pytest.approx(
        float(model.loss(params, {"input_ids": jnp.asarray(tokens)})), abs=1e-4)
