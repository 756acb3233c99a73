"""Tests for the memory/speed policies: per-layer remat policies, chunked
cross-entropy, and ZeRO optimizer-state sharding by tree path.

Reference analogues: activation checkpointing
(``deepspeed/runtime/activation_checkpointing/checkpointing.py``), fused
softmax-xent kernels (``csrc/transformer/softmax_kernels.cu``), ZeRO
round-robin state partitioning (``deepspeed/runtime/zero/stage_1_and_2.py``).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.models import CausalLM
from deepspeed_tpu.models.transformer import TransformerConfig

import deepspeed_tpu.comm as dist


def tiny(remat, loss_chunk=0, **over):
    kw = dict(vocab_size=256, n_layer=2, n_head=4, d_model=64, max_seq=64)
    kw.update(over)
    cfg = TransformerConfig(remat=remat, loss_chunk=loss_chunk, **kw)
    return CausalLM(cfg)


def batch(B=2, S=64, vocab=256, seed=0):
    rng = np.random.default_rng(seed)
    return {"input_ids": jnp.asarray(rng.integers(0, vocab, size=(B, S)).astype(np.int32))}


def peak(remat, b, **over):
    """Temporaries of the compiled gradient program of ``tiny(remat)``."""
    m = tiny(remat, **over)
    p = m.init_params(jax.random.key(0))
    c = jax.jit(jax.grad(lambda p: m.loss(p, b))).lower(p).compile()
    return c.memory_analysis().temp_size_in_bytes


class TestRematPolicies:
    """Every remat policy must produce the same loss and grads as full remat."""

    @pytest.fixture(autouse=True)
    def no_mesh(self):
        dist.set_mesh(None)
        yield

    def reference(self):
        m = tiny(remat=True)
        p = m.init_params(jax.random.key(0))
        b = batch()
        loss, grads = jax.value_and_grad(lambda p: m.loss(p, b))(p)
        return p, b, loss, grads

    @pytest.mark.parametrize("remat", [
        pytest.param(False, marks=pytest.mark.nightly),
        pytest.param("dots", marks=pytest.mark.slow), "selective",
        pytest.param("offload_dots", marks=pytest.mark.nightly)])
    def test_loss_and_grad_parity(self, remat):
        p, b, ref_loss, ref_grads = self.reference()
        if remat == "offload_dots" and jax.default_backend() == "cpu":
            pytest.skip("host offload not supported on the CPU backend")
        m = tiny(remat=remat)
        loss, grads = jax.value_and_grad(lambda p: m.loss(p, b))(p)
        assert np.allclose(float(loss), float(ref_loss), rtol=1e-5)
        jax.tree.map(lambda a, r: np.testing.assert_allclose(
            np.asarray(a), np.asarray(r), rtol=2e-4, atol=2e-5), grads, ref_grads)

    @pytest.mark.slow
    def test_selective_saves_less_than_none(self):
        """Compiled-memory assertion: 'selective' must keep fewer live
        activation bytes than remat=False (save everything)."""
        b = batch(B=4, S=64)
        assert peak("selective", b) < peak(False, b)

    @pytest.mark.slow
    def test_full_remat_saves_least(self):
        b = batch(B=4, S=64)
        assert peak(True, b) <= peak("selective", b)


def _grad_jaxpr(m, B=4, S=64):
    """The jaxpr of the loss's gradient, traced from shapes alone."""
    p = jax.eval_shape(m.init_params, jax.random.key(0))
    b = {"input_ids": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    return jax.make_jaxpr(jax.grad(lambda p, b: m.loss(p, b)))(p, b)


def _pallas_calls(jaxpr, scans=0, out=None):
    """``(kernel name, number of scans around it)`` of every ``pallas_call``
    in ``jaxpr``, sub-jaxprs (scan bodies, custom-vjp and shard_map calls)
    included."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append((eqn.params["name"], scans))
        inner = scans + (eqn.primitive.name == "scan")
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _pallas_calls(sub, inner, out)
    return out


def _bare_dots(monkeypatch):
    """``remat="dots"`` as it was before it named the flash kernel's
    residuals: the policy that goes by primitive alone."""
    from deepspeed_tpu.models import transformer
    monkeypatch.setattr(
        transformer, "_remat_policy",
        lambda remat: jax.checkpoint_policies.dots_with_no_batch_dims_saveable)


# d_model 256 = 4 heads of 64, the head size of BLOOM-560m and OPT-1.3b
FLASH = dict(d_model=256, attention_backend="flash")
# the three flash forms the train cells and their neighbours take
FLASH_FORMS = {
    "general_alibi": dict(over=dict(pos_embedding="alibi"), mesh=None,
                          prefix="flash"),
    "packed": dict(over={}, mesh=None, prefix="flash_packed"),
    "packed_shard_map": dict(over={}, mesh=("fsdp", 4),
                             prefix="flash_packed"),
}


class TestDotsKeepsFlashResiduals:
    """``remat="dots"`` keeps the flash kernel's (o, lse): the backward of a
    layer runs dq and dkv on them and no second forward kernel."""

    @pytest.fixture
    def form(self, request):
        spec = FLASH_FORMS[request.param]
        if spec["mesh"] is None:
            dist.set_mesh(None)
        else:
            axis, n = spec["mesh"]
            dist.set_mesh(Mesh(np.array(jax.devices()[:n]), (axis,)))
        yield spec
        dist.set_mesh(None)

    @staticmethod
    def kernels(spec, remat):
        m = tiny(remat, **FLASH, **spec["over"])
        assert m.config.scan_layers
        calls = _pallas_calls(_grad_jaxpr(m).jaxpr)
        # every kernel sits in a layer scan's body (forward or backward)
        assert calls and all(scans == 1 for _, scans in calls), calls
        return sorted(name for name, _ in calls)

    @pytest.mark.parametrize("form", FLASH_FORMS, indirect=True)
    def test_one_forward_kernel_a_layer(self, form):
        pre = form["prefix"]
        assert self.kernels(form, "dots") == [
            f"{pre}_dkv", f"{pre}_dq", f"{pre}_fwd"]

    @pytest.mark.parametrize("form", FLASH_FORMS, indirect=True)
    def test_two_without_the_names(self, form, monkeypatch):
        """The control: the count sees the re-run forward kernel under the
        bare dots policy, which cannot look inside a pallas_call."""
        _bare_dots(monkeypatch)
        pre = form["prefix"]
        assert self.kernels(form, "dots") == [
            f"{pre}_dkv", f"{pre}_dq", f"{pre}_fwd", f"{pre}_fwd"]

    @pytest.mark.parametrize("form", FLASH_FORMS, indirect=True)
    def test_loss_and_grads_equal_no_remat(self, form):
        over = dict(FLASH, **form["over"])
        ref_m, m = tiny(False, **over), tiny("dots", **over)
        p = ref_m.init_params(jax.random.key(0))
        b = batch(B=4)
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(
            lambda p: ref_m.loss(p, b)))(p)
        loss, grads = jax.jit(jax.value_and_grad(lambda p: m.loss(p, b)))(p)
        assert np.allclose(float(loss), float(ref_loss), rtol=1e-5)
        jax.tree.map(lambda a, r: np.testing.assert_allclose(
            np.asarray(a), np.asarray(r), rtol=2e-4, atol=2e-5), grads, ref_grads)

    @pytest.mark.parametrize("scan_layers", [True, False])
    def test_einsum_attention_traces_as_under_bare_dots(self, scan_layers,
                                                        monkeypatch):
        """A block with no flash kernel has no such names: the joined policy
        traces to the jaxpr the bare dots policy traces to."""
        dist.set_mesh(None)

        def text():
            m = tiny("dots", attention_backend="xla", scan_layers=scan_layers)
            # the policy function's own repr (its address) is no part of
            # what was traced
            return re.sub(r"policy=<function .*? at 0x[0-9a-f]+>", "policy=_",
                          str(_grad_jaxpr(m)))

        joined = text()
        _bare_dots(monkeypatch)
        assert joined == text()

    def test_full_remat_keeps_no_more_than_selective(self):
        dist.set_mesh(None)
        b = batch(B=4)
        assert peak(True, b, **FLASH) <= peak("selective", b, **FLASH)

    def test_what_keeping_the_residuals_costs(self, monkeypatch):
        """Compiled temporaries: no more than layers x (o + lse) bytes over
        the bare dots policy."""
        dist.set_mesh(None)
        B, S, D, H, L = 4, 64, FLASH["d_model"], 4, 2
        b = batch(B=B, S=S)
        kept = peak("dots", b, **FLASH)
        _bare_dots(monkeypatch)
        bare = peak("dots", b, **FLASH)
        o_and_lse = B * S * D * 4 + B * H * S * 4   # float32 params: o is f32
        assert kept <= bare + L * o_and_lse, (bare, kept)


class TestLossChunk:
    @pytest.fixture(autouse=True)
    def no_mesh(self):
        dist.set_mesh(None)
        yield

    @pytest.mark.parametrize("chunk", [
        32, pytest.param(64, marks=pytest.mark.nightly)])
    def test_chunked_ce_matches_unchunked(self, chunk):
        b = batch()
        m0 = tiny(remat=False, loss_chunk=0)
        p = m0.init_params(jax.random.key(0))
        ref = jax.value_and_grad(lambda p: m0.loss(p, b))(p)
        mc = tiny(remat=False, loss_chunk=chunk)
        got = jax.value_and_grad(lambda p: mc.loss(p, b))(p)
        assert np.allclose(float(got[0]), float(ref[0]), rtol=1e-5)
        jax.tree.map(lambda a, r: np.testing.assert_allclose(
            np.asarray(a), np.asarray(r), rtol=2e-4, atol=2e-5), got[1], ref[1])

    def test_chunked_ce_respects_ignore_index(self):
        b = batch()
        labels = np.array(b["input_ids"])
        labels[:, ::3] = -100
        b = dict(b, labels=jnp.asarray(labels))
        m0 = tiny(remat=False, loss_chunk=0)
        mc = tiny(remat=False, loss_chunk=32)
        p = m0.init_params(jax.random.key(0))
        assert np.allclose(float(m0.loss(p, b)), float(mc.loss(p, b)), rtol=1e-5)

    @pytest.mark.slow
    def test_chunked_ce_caps_logits_buffer(self):
        """The whole point of loss_chunk: the [B, S, vocab] logits must never
        be materialised. Compare compiled temp memory against unchunked."""
        # large-ish vocab so the logits dominate temps
        m0 = tiny(remat=False, loss_chunk=0, vocab_size=8192)
        mc = tiny(remat=False, loss_chunk=32, vocab_size=8192)
        b = batch(B=4, S=64, vocab=8192)
        p = m0.init_params(jax.random.key(0))

        def temp(m):
            c = jax.jit(jax.grad(lambda p: m.loss(p, b))).lower(p).compile()
            return c.memory_analysis().temp_size_in_bytes

        full_logits_bytes = 4 * 64 * 8192 * 4  # B*S*vocab f32
        assert temp(mc) < temp(m0)
        assert temp(mc) < temp(m0) - full_logits_bytes // 2


class TestOptStateShardingsByPath:
    """Two same-shape params with DIFFERENT TP specs must keep their own
    specs in the optimizer-state shardings (regression: shape-keyed map
    silently shared the last-inserted spec)."""

    def test_same_shape_different_tp_specs(self):
        from deepspeed_tpu.runtime.zero.partition import ZeroShardingRules
        from deepspeed_tpu.runtime.zero.config import ZeroConfig

        devs = np.array(jax.devices()[:4]).reshape(2, 2)
        mesh = Mesh(devs, ("dp", "tp"))
        rules = ZeroShardingRules(mesh, ZeroConfig(stage=1))

        params = {"a": jnp.zeros((8, 8)), "b": jnp.zeros((8, 8))}
        tp_specs = {"a": P(None, "tp"), "b": P("tp", None)}
        opt_state = optax.adam(1e-3).init(params)
        sh = rules.opt_state_shardings(opt_state, params, tp_specs)

        mu = sh[0].mu
        assert mu["a"].spec != mu["b"].spec
        assert "tp" in (mu["a"].spec[1] if not isinstance(mu["a"].spec[1], tuple)
                        else mu["a"].spec[1])
        # count scalar replicates
        assert sh[0].count.spec == P()

    def test_scalar_params_fallback(self):
        from deepspeed_tpu.runtime.zero.partition import ZeroShardingRules
        from deepspeed_tpu.runtime.zero.config import ZeroConfig

        devs = np.array(jax.devices()[:2]).reshape(2)
        mesh = Mesh(devs, ("dp",))
        rules = ZeroShardingRules(mesh, ZeroConfig(stage=1))
        params = jnp.zeros((16,))  # bare-array param tree
        opt_state = optax.adam(1e-3).init(params)
        sh = rules.opt_state_shardings(opt_state, params, None)
        assert sh[0].mu.spec == P("dp")
        assert sh[0].count.spec == P()
