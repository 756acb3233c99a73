"""The records of ``models/state_mixers.py``: every recurrent layer kind is
counted, pooled and imported through its record alone. (That the move of the
mixers changed no serving program is ``test_sdar.py``'s digests.)"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.models import state_mixers as SM
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.presets import get_model

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
#: the preset whose ``tiny`` size has layers of the kind
PRESET = {T.LINEAR_ATTENTION: "solar_open2", T.MAMBA2: "granite_hybrid",
          T.SHORT_CONV: "lfm2_moe"}


def test_every_kind_has_a_name_a_record_and_a_preset():
    assert tuple(SM.STATE_MIXERS) == T.STATE_KINDS
    assert set(PRESET) == set(SM.STATE_MIXERS)


@pytest.mark.parametrize("kind", sorted(PRESET))
def test_num_parameters_is_the_leaf_count(kind):
    """Fails on the commit before PR 47 for the KDA preset: its MoE model
    counted a KDA layer as an attention layer and left out the output gate,
    the selection bias and the shared expert."""
    model = get_model(PRESET[kind], "tiny")
    assert kind in model.config.period
    shapes = jax.eval_shape(model.init_params, jax.random.key(0))
    assert model.num_parameters == sum(a.size for a in jax.tree.leaves(shapes))


@pytest.mark.parametrize("kind", sorted(PRESET))
def test_the_pools_have_the_records_shapes(kind):
    """One array a recurrent position, a leading layer's first ([1, slots,
    ...]); a kind that keeps a conv state alone (its state shape None) has
    None where the others have their state's array."""
    model = get_model(PRESET[kind], "tiny")
    cfg = model.config
    pools = jax.eval_shape(
        lambda: model.init_paged_cache(4, 16, dtype=jnp.bfloat16, state_slots=3))
    kinds = [(1, k) for k in cfg.lead_kinds if k in SM.STATE_MIXERS] \
        + [(cfg.n_periods, k) for k in cfg.period if k in SM.STATE_MIXERS]
    assert kind in [k for _, k in kinds]
    assert len(pools["state"]) == len(pools["conv"]) == len(kinds)
    assert cfg.cache_spec["state"] == sum(n for n, _ in kinds)
    for (n, k), st, cv in zip(kinds, pools["state"], pools["conv"]):
        state, conv = SM.STATE_MIXERS[k].shapes(cfg)
        assert cfg.state_shapes(k) == (state, conv)
        if state is None:
            assert st is None and k == T.SHORT_CONV
        else:
            assert (st.shape, st.dtype) == ((n, 3, *state), jnp.float32)
        assert (cv.shape, cv.dtype) == ((n, 3, *conv), jnp.bfloat16)


def test_a_stack_without_a_lead_builds_the_tree_it_always_did():
    """No ``lead`` key, no array ahead of the periods' in a pool, ``n_layer``
    whole periods: what every configuration before the lead came in gets
    (that their programs are unchanged is ``test_sdar.py``'s digests)."""
    for family in ("granite_hybrid", "solar_open2", "olmoe"):
        model = get_model(family, "tiny")
        cfg = model.config
        assert cfg.lead_kinds == () and cfg.lead_d_ff is None
        assert cfg.n_periods * len(cfg.period) == cfg.n_layer
        shapes = jax.eval_shape(model.init_params, jax.random.key(0))
        assert set(shapes) <= {"embed", "layers", "ln_f", "lm_head"}
        pools = jax.eval_shape(lambda: model.init_paged_cache(
            4, 16, dtype=jnp.bfloat16, state_slots=3))
        assert len(pools.get("conv", ())) == sum(
            k in SM.STATE_MIXERS for k in cfg.period)
        assert pools["k"].shape[0] == cfg.n_periods * cfg.period.count("attention")
    # and with one, the tree gains the group and the pools their first entries
    lfm2 = get_model("lfm2_moe", "tiny")
    shapes = jax.eval_shape(lfm2.init_params, jax.random.key(0))
    assert set(shapes) == {"embed", "layers", "ln_f", "lead"}
    assert lfm2.config.n_periods * len(lfm2.config.period) + 1 == lfm2.config.n_layer


@pytest.mark.parametrize("kind", sorted(PRESET))
def test_a_kind_without_its_sizes_is_refused(kind):
    sizes = {T.LINEAR_ATTENTION: "lin_heads", T.MAMBA2: "ssm_heads",
             T.SHORT_CONV: "conv_kernel"}
    with pytest.raises(ValueError, match=f"a {kind} layer needs {sizes[kind]}"):
        get_model(PRESET[kind], "tiny", **{sizes[kind]: 0}).init_params(
            jax.random.key(0))


@pytest.mark.parametrize("module", ["state_mixers", "transformer"])
def test_each_module_imports_alone(module):
    """No cycle in either order: ``transformer`` reaches the records inside
    functions only, and builds and checks a config without them."""
    code = (f"import sys, deepspeed_tpu.models.{module} as m\n"
            "from deepspeed_tpu.models.transformer import TransformerConfig, "
            "_check_pattern\n"
            "_check_pattern(TransformerConfig(n_layer=4, layer_kinds=("
            "'attention', 'window_attention'), attn_window=8))\n"
            "print('deepspeed_tpu.models.state_mixers' in sys.modules)\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, cwd=ROOT,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split()[-1] == str(module == "state_mixers")
