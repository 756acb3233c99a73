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
PRESET = {T.LINEAR_ATTENTION: "solar_open2", T.MAMBA2: "granite_hybrid"}


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
    model = get_model(PRESET[kind], "tiny")
    cfg = model.config
    pools = jax.eval_shape(
        lambda: model.init_paged_cache(4, 16, dtype=jnp.bfloat16, state_slots=3))
    kinds = [k for k in cfg.period if k in SM.STATE_MIXERS]
    assert len(pools["state"]) == len(pools["conv"]) == len(kinds)
    for k, st, cv in zip(kinds, pools["state"], pools["conv"]):
        state, conv = SM.STATE_MIXERS[k].shapes(cfg)
        assert cfg.state_shapes(k) == (state, conv)
        assert (st.shape, st.dtype) == ((cfg.n_periods, 3, *state), jnp.float32)
        assert (cv.shape, cv.dtype) == ((cfg.n_periods, 3, *conv), jnp.bfloat16)


@pytest.mark.parametrize("kind", sorted(PRESET))
def test_a_kind_without_its_sizes_is_refused(kind):
    sizes = {T.LINEAR_ATTENTION: "lin_heads", T.MAMBA2: "ssm_heads"}
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
