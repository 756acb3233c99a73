"""The plain reference of the Granite 4.0-H block on cases small enough to
work by hand: two positions of one Mamba-2 head (a conv of two taps, the
decay, the rank-one update, ``D x``, the gate before the norm), and one
attention layer of two positions under the four multipliers. What holds it to
the published code is ``tests/unit/test_module_inject.py`` (against
``transformers``); this file holds each formula to arithmetic a reader can
follow."""

import math

import jax.numpy as jnp
import numpy as np

from reference import granite_hybrid_decoder as ref


def silu(v):
    return v / (1.0 + math.exp(-v))


def test_two_positions_of_one_mamba_head_by_hand():
    cfg = {"ssm_heads": 1, "ssm_head_dim": 1, "ssm_state": 1, "ssm_groups": 1,
           "eps": 0.0}
    a0, a1 = 1.0, 2.0
    w = {  # z | x B C | dt: z = a, x = a, B = 2a, C = -a, dt = a / 2
        "w_in": jnp.asarray([[1.0, 1.0, 2.0, -1.0, 0.5]]),
        # two taps, tap 1 on the current input; one bias a channel
        "conv": jnp.asarray([[0.5, 0.5, 0.5], [1.0, 1.0, 1.0]]),
        "conv_b": jnp.asarray([0.1, 0.0, 0.0]),
        "A_log": jnp.asarray([math.log(2.0)]), "dt_bias": jnp.asarray([0.25]),
        "D": jnp.asarray([0.05]), "norm_g": jnp.asarray([1.5]),
        "w_out": jnp.asarray([[2.0]])}
    got = np.asarray(ref.mamba2(cfg, w, jnp.asarray([[[a0], [a1]]])))[0, :, 0]

    # the conv: nothing before position 0
    x0, b0, c0 = silu(a0 + 0.1), silu(2 * a0), silu(-a0)
    x1 = silu(0.5 * a0 + a1 + 0.1)
    b1, c1 = silu(0.5 * 2 * a0 + 2 * a1), silu(0.5 * -a0 - a1)
    dt0 = math.log1p(math.exp(0.5 * a0 + 0.25))
    dt1 = math.log1p(math.exp(0.5 * a1 + 0.25))
    A = -2.0
    s0 = dt0 * x0 * b0                                   # from a zero state
    y0 = s0 * c0 + 0.05 * x0
    s1 = math.exp(dt1 * A) * s0 + dt1 * x1 * b1
    y1 = s1 * c1 + 0.05 * x1
    # the gate BEFORE the norm; over one channel RMS(v) = sign(v)
    want = [math.copysign(1.0, y * silu(a)) * 1.5 * 2.0
            for y, a in ((y0, a0), (y1, a1))]
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # and the state term decides: D x alone would give the other sign
    assert y0 < 0 < x0 and y1 < 0 < x1


def test_the_state_and_d_terms_by_hand_through_two_channels():
    """Two channels of one head (P 2), so that the norm does not reduce the
    output to a sign: y = S C + D x per channel, normed together."""
    cfg = {"ssm_heads": 1, "ssm_head_dim": 2, "ssm_state": 1, "ssm_groups": 1,
           "eps": 1e-5}
    w = {"w_in": jnp.asarray([[1.0, 0.5, 1.0, -1.0, 1.0, 1.0, 0.0]]),
         "conv": jnp.ones((1, 4)), "conv_b": jnp.zeros((4,)),
         "A_log": jnp.asarray([0.0]), "dt_bias": jnp.asarray([0.0]),
         "D": jnp.asarray([0.5]), "norm_g": jnp.asarray([1.0, 2.0]),
         "w_out": jnp.asarray([[1.0], [1.0]])}
    got = float(ref.mamba2(cfg, w, jnp.asarray([[[1.0]]]))[0, 0, 0])
    x = [silu(1.0), silu(-1.0)]
    b = c = silu(1.0)
    dt = math.log(2.0)                                   # softplus(0)
    y = [dt * xi * b * c + 0.5 * xi for xi in x]
    g = [y[0] * silu(1.0), y[1] * silu(0.5)]             # z = (1, 0.5)
    rms = math.sqrt((g[0] ** 2 + g[1] ** 2) / 2 + 1e-5)
    np.testing.assert_allclose(got, g[0] / rms * 1.0 + g[1] / rms * 2.0, rtol=1e-6)


class _OneAttentionLayer:
    """Two tokens of width 2, one head of 2, identity projections, an MLP
    that gives zero."""
    E = np.array([[1.0, 0.0], [0.6, 0.8], [-0.8, 0.6]])

    def top(self):
        return {"wte": jnp.asarray(self.E), "lnf_g": jnp.ones((2,))}

    def layer(self, l):
        eye = jnp.eye(2)
        return {"ln1_g": jnp.ones((2,)), "ln2_g": jnp.ones((2,)),
                "wq": eye, "wk": eye, "wv": eye, "wo": eye,
                "w_gate": eye, "w_up": eye, "w_down": jnp.zeros((2, 2))}


def _logits(**mult):
    cfg = {"n_layer": 1, "n_head": 1, "n_kv_head": 1, "head_dim": 2,
           "d_model": 2, "eps": 0.0, "layer_types": ["attention"],
           "embedding_multiplier": 12, "attention_multiplier": 0.5,
           "residual_multiplier": 0.22, "logits_scaling": 8, **mult}
    w = _OneAttentionLayer()
    h = ref.final_hidden(cfg, w, jnp.asarray([[0, 1]]))
    return np.asarray(ref.logits_rows(cfg, w, h[0])), cfg


def _by_hand(cfg):
    E = _OneAttentionLayer.E
    rms = lambda v: v / np.sqrt(np.mean(v * v))            # noqa: E731
    x = cfg["embedding_multiplier"] * E[[0, 1]]
    a = np.stack([rms(x[0]), rms(x[1])])
    s = cfg["attention_multiplier"] * np.array([a[1] @ a[0], a[1] @ a[1]])
    p = np.exp(s - s.max()) / np.exp(s - s.max()).sum()
    h0 = x[0] + cfg["residual_multiplier"] * a[0]          # sees itself alone
    h1 = x[1] + cfg["residual_multiplier"] * (p[0] * a[0] + p[1] * a[1])
    return np.stack([rms(h0), rms(h1)]) @ E.T / cfg["logits_scaling"]


def test_the_four_multipliers_by_hand():
    got, cfg = _logits()
    np.testing.assert_allclose(got, _by_hand(cfg), rtol=1e-5, atol=1e-6)
    # each is where the formula has it: one at a time at 1
    for key in ("embedding_multiplier", "attention_multiplier",
                "residual_multiplier", "logits_scaling"):
        other, cfg1 = _logits(**{key: 1})
        np.testing.assert_allclose(other, _by_hand(cfg1), rtol=1e-5, atol=1e-6)
        assert np.abs(other - got).max() > 1e-4, key
    # the logits are the head's product OVER the scaling
    np.testing.assert_allclose(_logits(logits_scaling=1)[0] / 8, got, rtol=1e-6)
