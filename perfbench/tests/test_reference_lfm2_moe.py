"""The plain reference of the LFM2-MoE block on cases small enough to work by
hand: three positions of a gated short conv of three taps, the sigmoid router
with its selection bias and its 1e-6, the rotate-half rope on one pair, and
the weights' view of a lead and periods. What holds the program to it is
``tests/unit/test_lfm2_moe.py``; this file holds each formula to arithmetic a
reader can follow."""

import math

import jax.numpy as jnp
import numpy as np

from reference import lfm2_moe_decoder as ref


def test_three_positions_of_a_gated_short_conv_by_hand():
    """One channel: B = a, C = 2a, x~ = -a, so u = -a^2; taps (0.5, -1, 2),
    tap 2 on the current input and zeros before the sequence; out = 3 C c."""
    cfg = {"conv_kernel": 3}
    w = {"w_in": jnp.asarray([[1.0, 2.0, -1.0]]),
         "conv": jnp.asarray([[0.5], [-1.0], [2.0]]),
         "w_out": jnp.asarray([[3.0]])}
    a = [1.0, 2.0, -3.0]
    got = np.asarray(ref.short_conv(cfg, w, jnp.asarray(a)[None, :, None]))[0, :, 0]
    u = [-x * x for x in a]
    c = [2.0 * u[0], -1.0 * u[0] + 2.0 * u[1],
         0.5 * u[0] - 1.0 * u[1] + 2.0 * u[2]]
    np.testing.assert_allclose(got, [3.0 * 2.0 * x * ci for x, ci in zip(a, c)],
                               rtol=1e-6)
    # no activation anywhere: the mixer is cubic in its input
    twice = np.asarray(ref.short_conv(cfg, w, 2 * jnp.asarray(a)[None, :, None]))
    np.testing.assert_allclose(twice[0, :, 0], 8 * got, rtol=1e-6)


def test_the_router_by_hand():
    """Four experts, two a token: the bias moves the CHOICE (expert 3 over
    expert 1) and never the weight; the two scores are divided by their sum +
    1e-6, which shows where both are tiny."""
    cfg = {"n_experts": 4, "experts_per_token": 2, "norm_topk_prob": True,
           "topk_eps": 1e-6, "routed_scaling": 1.0}
    w = {"router": jnp.eye(4), "expert_bias": jnp.asarray([0.0, 0.0, 0.0, 0.3])}
    sig = lambda v: 1.0 / (1.0 + math.exp(-v))  # noqa: E731
    m = jnp.asarray([[[2.0, 1.0, -1.0, 0.5],          # scores .88 .73 .27 .62
                      [-14.0, -15.0, -30.0, -30.0]]])  # both picks ~1e-6
    c = np.asarray(ref.route(cfg, w, m))[0]
    s0, s3 = sig(2.0), sig(0.5)
    assert sig(1.0) > s3 and s3 + 0.3 > sig(1.0)        # the bias decides
    np.testing.assert_allclose(
        c[0], [s0 / (s0 + s3 + 1e-6), 0.0, 0.0, s3 / (s0 + s3 + 1e-6)], rtol=1e-6)
    # the second token's scores are ~1e-6 and less: the bias picks expert 3
    # there too, beside expert 0, and the 1e-6 halves what they weigh
    t0, t3 = sig(-14.0), sig(-30.0)
    np.testing.assert_allclose(c[1, [0, 3]], [t0 / (t0 + t3 + 1e-6),
                                              t3 / (t0 + t3 + 1e-6)],
                               rtol=1e-5, atol=1e-12)
    assert c[1, 1] == c[1, 2] == 0.0 and 0.4 < c[1].sum() < 0.6
    free = np.asarray(ref.route({**cfg, "norm_topk_prob": False}, w, m))[0]
    np.testing.assert_allclose(free[0], [s0, 0.0, 0.0, s3], rtol=1e-6)


def test_rope_rotates_the_two_halves_of_a_head():
    """A head of four at position 1, theta 100: frequencies 1 and 0.1, each
    pairing element i with element i + 2."""
    x = jnp.asarray([1.0, 2.0, 3.0, 4.0]).reshape(1, 1, 1, 4)
    two = jnp.concatenate([x, x], axis=1)               # positions 0 and 1
    got = np.asarray(ref.rope(two, 100.0))[0, :, 0]
    np.testing.assert_allclose(got[0], [1.0, 2.0, 3.0, 4.0], rtol=1e-6)
    c1, s1, c2, s2 = math.cos(1.0), math.sin(1.0), math.cos(0.1), math.sin(0.1)
    np.testing.assert_allclose(
        got[1], [1 * c1 - 3 * s1, 2 * c2 - 4 * s2, 3 * c1 + 1 * s1,
                 4 * c2 + 2 * s2], rtol=1e-6)


def test_the_weights_view_of_a_lead_and_periods():
    """Layer 0 is the lead's group (a dense MLP), layers 1.. are rows of the
    period's groups in order, and the kind is read off what a group holds."""
    import correctness
    name_map = correctness.load_map("rehearsal-lfm2-moe-tiny")
    z = lambda *s: np.zeros(s, np.float32)  # noqa: E731
    conv = lambda n, tag: {  # noqa: E731
        "ln_attn": {"scale": z(n, 2) + tag}, "ln_mlp": {"scale": z(n, 2)},
        "conv": {"w_in": z(n, 2, 6), "conv_w": z(n, 3, 2), "w_out": z(n, 2, 2)}}
    moe = {"gate_w": z(2, 2, 4), "b_select": z(2, 4), "w_gate": z(2, 4, 2, 3),
           "w_up": z(2, 4, 2, 3), "w_down": z(2, 4, 3, 2)}
    dense = {"w_gate": z(1, 2, 5), "w_up": z(1, 2, 5), "w_down": z(1, 5, 2)}
    attn = {"ln_attn": {"scale": z(2, 2) + 10}, "ln_mlp": {"scale": z(2, 2)},
            "attn": {"wq": z(2, 2, 4), "wk": z(2, 2, 2), "wv": z(2, 2, 2),
                     "wo": z(2, 4, 2), "q_norm": {"scale": z(2, 2)},
                     "k_norm": {"scale": z(2, 2)}}, "mlp": moe}
    params = {"embed": {"tokens": z(7, 2)}, "ln_f": {"scale": z(2)},
              "lead": ({**conv(1, 1), "mlp": dense},),
              "layers": (attn, {**conv(2, 20), "mlp": moe})}
    params["layers"][1]["ln_attn"]["scale"][1] += 1
    w = ref.Weights(params, name_map)
    lead, first, second, third, fourth = (w.layer(l) for l in range(5))
    assert "w_in" in lead and "w_gate" in lead and "router" not in lead
    assert lead["w_gate"].shape == (2, 5) and float(lead["ln1_g"][0]) == 1
    assert "wq" in first and first["e_up"].shape == (4, 2, 3) \
        and float(first["ln1_g"][0]) == 10
    assert "w_in" in second and "expert_bias" in second \
        and float(second["ln1_g"][0]) == 20
    assert "wq" in third
    assert float(fourth["ln1_g"][0]) == 21          # row 1 of the conv group
    assert set(w.top()) == {"wte", "lnf_g"}
