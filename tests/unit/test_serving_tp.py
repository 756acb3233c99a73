"""Tensor-parallel paged serving: head-sharded KV pools and mesh-parallel
fused decode. Covers the ``auto_tp`` heuristics (column/row/embed/bias
spec emission, divisibility guards), THE acceptance pin — ``generate_batch``
under ``serving.tp=2`` and ``tp=4`` is greedy-token-identical to the tp=1
paged engine in every covered scenario (eviction pressure, prefix cache
on/off + re-hit, chunked prefill, speculation) on the forced 8-CPU-device
mesh — the shard_map'd Pallas paged-kernel path (interpret mode) against a
replicated einsum reference AND its dispatch from the sharded engine, the
``serving_sharded_steady`` compile-budget contract, and the ``serving/tp``
telemetry annotation."""

import importlib
import os
import sys

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import deepspeed_tpu
import deepspeed_tpu.comm as dist
from deepspeed_tpu.inference.auto_tp import (auto_tp_specs,
                                             validate_tp_specs)
from deepspeed_tpu.models import CausalLM
from deepspeed_tpu.models.transformer import TransformerConfig

_TOOLS = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                      "..", "..", "tools"))
if _TOOLS not in sys.path:
    sys.path.insert(0, _TOOLS)


@pytest.fixture(autouse=True)
def clean_mesh():
    dist.set_mesh(None)
    yield
    dist.set_mesh(None)


def tiny_model(**over):
    base = dict(vocab_size=64, n_layer=2, n_head=4, d_model=32, d_ff=64,
                max_seq=64, remat=False)
    base.update(over)
    return CausalLM(TransformerConfig(**base))


def make_engine(model=None, tp=0, **srv):
    """A paged serving engine on a FRESH mesh (every engine pins its own
    mesh per serve via ``_mesh_scope``, so mixed-tp engines coexist)."""
    dist.set_mesh(None)
    serving = {"block_size": 8, "max_running": 2}
    serving.update(srv)
    if tp:
        serving["tp"] = tp
    return deepspeed_tpu.init_inference(model or tiny_model(), dtype="fp32",
                                        serving=serving)


def _prompts(lens=(5, 11, 3, 8), vocab=64, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).astype(np.int32) for n in lens]


def _assert_same(outs_a, outs_b):
    assert len(outs_a) == len(outs_b)
    for a, b in zip(outs_a, outs_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------- #
# auto_tp: spec emission + divisibility guards


class TestAutoTP:

    def _gpt2_tree(self):
        """GPT-2-shaped param pytree: fused-qkv-free naming, c_fc/c_proj
        MLP, wte embedding — the AutoTP reference shapes."""
        z = np.zeros
        return {
            "wte": z((64, 16)),
            "h": {
                "attn": {"q_proj": {"w": z((16, 16)), "b": z((16,))},
                         "k_proj": {"w": z((16, 16)), "b": z((16,))},
                         "v_proj": {"w": z((16, 16)), "b": z((16,))},
                         "out_proj": {"w": z((16, 16)), "b": z((16,))}},
                "mlp": {"c_fc": {"w": z((16, 64)), "b": z((64,))},
                        "c_proj": {"w": z((64, 16)), "b": z((16,))}},
                "ln_1": {"scale": z((16,)), "bias": z((16,))},
            },
        }

    def test_column_row_embed_bias_emission(self):
        specs = auto_tp_specs(self._gpt2_tree())
        # column: qkv + c_fc shard the OUTPUT (last) dim; their biases too
        assert specs["h"]["attn"]["q_proj"]["w"] == P(None, "tp")
        assert specs["h"]["attn"]["q_proj"]["b"] == P("tp")
        assert specs["h"]["mlp"]["c_fc"]["w"] == P(None, "tp")
        assert specs["h"]["mlp"]["c_fc"]["b"] == P("tp")
        # row: out_proj + c_proj shard the INPUT dim; biases replicate
        # (added once, after the all-reduce)
        assert specs["h"]["attn"]["out_proj"]["w"] == P("tp", None)
        assert specs["h"]["attn"]["out_proj"]["b"] == P(None)
        assert specs["h"]["mlp"]["c_proj"]["w"] == P("tp", None)
        assert specs["h"]["mlp"]["c_proj"]["b"] == P(None)
        # embeddings vocab-shard dim 0; norms replicate
        assert specs["wte"] == P("tp", None)
        assert specs["h"]["ln_1"]["scale"] == P(None)

    def test_divisibility_guard_replicates_not_crashes(self):
        # 16-wide projections over tp=3: every pattern rule must fall back
        # to replication (with a warning), never emit a spec that crashes
        specs = auto_tp_specs(self._gpt2_tree(), tp=3)
        flat = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        assert all(all(s is None for s in sp) for sp in flat), (
            "non-divisible dims must replicate under tp=3")
        # tp=2 divides everything: the full layout comes back
        specs2 = auto_tp_specs(self._gpt2_tree(), tp=2)
        assert specs2["h"]["attn"]["q_proj"]["w"] == P(None, "tp")

    def test_divisibility_guard_is_per_tensor(self):
        tree = {"q_proj": np.zeros((16, 12)), "w_down": np.zeros((10, 16))}
        specs = auto_tp_specs(tree, tp=4)
        assert specs["q_proj"] == P(None, "tp")       # 12 % 4 == 0
        assert specs["w_down"] == P(None, None)       # 10 % 4 != 0

    def test_validate_tp_specs_drops_nondividing(self, devices):
        from jax.sharding import Mesh
        mesh = Mesh(np.array(devices[:8]).reshape(2, 4), ("dp", "tp"))
        params = {"wq": np.zeros((8, 12)), "wo": np.zeros((10, 8))}
        specs = {"wq": P(None, "tp"), "wo": P("tp", None)}
        got = validate_tp_specs(params, specs, mesh)
        assert got["wq"] == P(None, "tp")     # 12 % 4 == 0: kept
        assert got["wo"] == P(None, None)     # 10 % 4 != 0: replicated


# --------------------------------------------------------------------- #
# config plumbing


class TestServingTPConfig:

    def test_serving_tp_builds_tp_mesh_and_shards(self):
        e = make_engine(tp=2)
        assert e.mesh.shape.get("tp") == 2
        wq = e.params["layers"]["attn"]["wq"]
        assert "tp" in [s for s in wq.sharding.spec if s is not None]
        pools, _ = e._paged_pools(9, 8)
        assert "tp" in [s for s in pools["k"].sharding.spec
                        if s is not None]

    def test_serving_tp_conflict_with_tensor_parallel_raises(self):
        dist.set_mesh(None)
        with pytest.raises(ValueError, match="serving.tp"):
            deepspeed_tpu.init_inference(
                tiny_model(), dtype="fp32",
                tensor_parallel={"tp_size": 4}, serving={"tp": 2})

    def test_tensor_parallel_alone_still_shards_serving(self):
        dist.set_mesh(None)
        e = deepspeed_tpu.init_inference(
            tiny_model(), dtype="fp32", tensor_parallel={"tp_size": 2},
            serving={"block_size": 8, "max_running": 2})
        assert e.mesh.shape.get("tp") == 2
        pools, _ = e._paged_pools(9, 8)
        assert "tp" in [s for s in pools["k"].sharding.spec
                        if s is not None]

    def test_serving_tp_honored_under_foreign_mesh(self):
        """Review regression: an engine configured serving.tp=2 while a
        FOREIGN global mesh (no tp axis — e.g. a training run's) is live
        must not silently adopt it and serve unsharded — it builds a
        private tp mesh, really shards, leaves the global mesh alone, and
        produces the tp=1 tokens."""
        prompts = _prompts((5, 9))
        want = make_engine().generate_batch(prompts, max_new_tokens=6)
        dist.init_mesh({"dp": -1})          # a training run's mesh, no tp
        foreign = dist.get_mesh()
        e = deepspeed_tpu.init_inference(
            tiny_model(), dtype="fp32",
            serving={"block_size": 8, "max_running": 2, "tp": 2})
        assert e.mesh.shape.get("tp") == 2, (
            "engine adopted the foreign mesh and dropped serving.tp")
        assert dist.get_mesh() is foreign, (
            "engine clobbered the global mesh")
        wq = e.params["layers"]["attn"]["wq"]
        assert "tp" in [s for s in wq.sharding.spec if s is not None]
        _assert_same(want, e.generate_batch(prompts, max_new_tokens=6))
        assert dist.get_mesh() is foreign   # _mesh_scope restored it

    def test_kv_heads_not_dividing_tp_replicates_pools(self):
        # kv_heads=3 over tp=2: params still shard where dims divide, but
        # the KV pools replicate (warning, never a crash) — and the engine
        # still serves (greedy determinism pinned; full tp-vs-tp1 identity
        # for the replicated-pool layout rides the tp2/tp4 pins above,
        # where the SAME einsum core runs on a replicated-KV operand)
        model_kw = dict(vocab_size=64, n_layer=2, n_head=6, n_kv_head=3,
                        d_model=48, d_ff=64, max_seq=64, remat=False)
        e = make_engine(model=CausalLM(TransformerConfig(**model_kw)), tp=2)
        pools, _ = e._paged_pools(9, 8)
        assert all(s is None for s in pools["k"].sharding.spec), (
            "kv_heads % tp != 0 must replicate the pools")
        wq = e.params["layers"]["attn"]["wq"]
        assert "tp" in [s for s in wq.sharding.spec if s is not None], (
            "params must still shard where their dims divide")
        out = e.generate_batch(_prompts((5,)), max_new_tokens=4)
        assert out[0].shape == (9,)
        _assert_same(out, e.generate_batch(_prompts((5,)), max_new_tokens=4))


# --------------------------------------------------------------------- #
# THE acceptance pin: sharded-vs-single-chip token identity


class TestShardedIdentity:

    def test_identity_tp2_and_tp4(self):
        prompts = _prompts()
        ref = make_engine().generate_batch(prompts, max_new_tokens=8)
        _assert_same(ref, make_engine(tp=2).generate_batch(
            prompts, max_new_tokens=8))
        _assert_same(ref, make_engine(tp=4).generate_batch(
            prompts, max_new_tokens=8))

    def test_identity_under_eviction_pressure(self):
        # 5 blocks of 8 for two ~20-token streams: preemption + recompute
        # under tp=2 must schedule AND decode exactly as at tp=1 (the
        # allocator is replicated host state — eviction is shard-invariant)
        prompts = _prompts((5, 11))
        ref = make_engine(max_num_blocks=5).generate_batch(
            prompts, max_new_tokens=10)
        got = make_engine(tp=2, max_num_blocks=5).generate_batch(
            prompts, max_new_tokens=10)
        _assert_same(ref, got)

    def test_identity_prefix_cache_rehit_across_serves(self):
        # shared system prefix + a SECOND serve of the same prompts: the
        # tp engine's content-addressed cache (replicated block ids over
        # head-sharded pool shards) must reproduce the tp=1 tokens on both
        # the cold and the fully-cached serve
        rng = np.random.default_rng(3)
        sysp = rng.integers(0, 64, size=24).astype(np.int32)
        prompts = [np.concatenate(
            [sysp, rng.integers(0, 64, size=k).astype(np.int32)])
            for k in (3, 6)]
        ref_e = make_engine(prefill_chunk_tokens=8)
        tp_e = make_engine(tp=2, prefill_chunk_tokens=8)
        for serve in range(2):
            ref = ref_e.generate_batch(prompts, max_new_tokens=6)
            got = tp_e.generate_batch(prompts, max_new_tokens=6)
            _assert_same(ref, got)
        # the second serve really re-hit the persisted allocator
        assert tp_e._paged_alloc is not None

    def test_identity_prefix_cache_off(self):
        prompts = _prompts((5, 9))
        ref = make_engine(prefix_caching="off").generate_batch(
            prompts, max_new_tokens=8)
        got = make_engine(tp=2, prefix_caching="off").generate_batch(
            prompts, max_new_tokens=8)
        _assert_same(ref, got)

    def test_identity_chunked_prefill(self):
        prompts = _prompts((26, 37), seed=5)
        ref = make_engine(prefill_chunk_tokens=8).generate_batch(
            prompts, max_new_tokens=6)
        got = make_engine(tp=2, prefill_chunk_tokens=8).generate_batch(
            prompts, max_new_tokens=6)
        _assert_same(ref, got)

    def test_identity_speculative(self):
        # repetitive prompts so the proposer fires: the fused verify step
        # under tp=2 (same sharded attention impl as decode) must accept
        # exactly the candidates the tp=1 verify accepts
        rng = np.random.default_rng(4)
        motif = rng.integers(0, 64, size=12).astype(np.int32)
        prompts = [np.tile(motif, 4)]
        spec = {"speculative": {"mode": "ngram", "k": 4}}
        ref = make_engine(**spec).generate_batch(prompts, max_new_tokens=12)
        tp_e = make_engine(tp=2, **spec)
        got = tp_e.generate_batch(prompts, max_new_tokens=12)
        _assert_same(ref, got)
        st = tp_e._last_serve_stats
        assert st["spec_accepted"] > 0, (
            f"scenario never speculated under tp: {st}")


# --------------------------------------------------------------------- #
# the shard_map'd Pallas kernel path (interpret mode on CPU)


def _einsum_reference(q, kp, vp, bt, pos, scale):
    """Replicated numpy softmax-attention reference through the block
    tables — independent of both the kernel and the jax einsum core."""
    B, H, Hd = q.shape
    KV = kp.shape[2] // Hd          # pools are [blocks, bs, KV*Hd]
    G = H // KV
    out = np.zeros_like(q, dtype=np.float32)
    for b in range(B):
        k = kp[bt[b]].reshape(-1, KV, Hd).astype(np.float32)
        v = vp[bt[b]].reshape(-1, KV, Hd).astype(np.float32)
        S = k.shape[0]
        valid = np.arange(S) <= pos[b]
        for h in range(H):
            g = h // G
            s = (q[b, h].astype(np.float32) @ k[:, g].T) * scale
            s = np.where(valid, s, -1e30)
            p = np.exp(s - s.max())
            p = p / p.sum()
            out[b, h] = p @ v[:, g]
    return out


class TestShardedKernelPath:

    def test_shard_map_kernel_matches_einsum_reference(self, devices):
        """The shard_map'd paged kernel (interpret mode, heads split over
        tp=2) against the replicated einsum reference on randomized block
        tables."""
        from jax.sharding import Mesh

        from deepspeed_tpu.models.transformer import _paged_decode_sharded

        mesh = Mesh(np.array(devices[:8]).reshape(4, 2), ("dp", "tp"))
        rng = np.random.default_rng(0)
        # per shard: 2 kv heads of 64 = one whole 128-lane pool row
        B, H, KV, Hd, bs, NB, nmax = 3, 8, 4, 64, 128, 7, 3
        q = rng.standard_normal((B, H, Hd)).astype(np.float32)
        kp = rng.standard_normal((NB, bs, KV * Hd)).astype(np.float32)
        vp = rng.standard_normal((NB, bs, KV * Hd)).astype(np.float32)
        bt = np.stack([rng.permutation(np.arange(1, NB))[:nmax]
                       for _ in range(B)]).astype(np.int32)
        pos = np.asarray([37, 200, 129], np.int32)
        scale = Hd ** -0.5

        dist.set_mesh(mesh)
        got = _paged_decode_sharded(q, kp, vp, bt, pos, None, None, mesh,
                                    scale=scale)
        assert got is not None, "sharded kernel path refused a legal shape"
        want = _einsum_reference(q, kp, vp, bt, pos, scale)
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-5,
                                   rtol=1e-5)

    def test_shard_ok_rejects_off_envelope(self, devices):
        from jax.sharding import Mesh

        from deepspeed_tpu.models.transformer import _paged_shard_ok

        mesh = Mesh(np.array(devices[:8]).reshape(4, 2), ("dp", "tp"))
        assert _paged_shard_ok(mesh, 8, 4, 64, 128)
        assert _paged_shard_ok(mesh, 4, 2, 128, 128)
        assert not _paged_shard_ok(mesh, 4, 2, 64, 128)   # shard row 64 lanes
        assert not _paged_shard_ok(mesh, 4, 3, 64, 128)   # KV % tp
        assert not _paged_shard_ok(mesh, 5, 2, 64, 128)   # H % tp
        assert not _paged_shard_ok(mesh, 4, 2, 32, 128)   # Hd % 64
        assert not _paged_shard_ok(mesh, 4, 2, 64, 64)    # bs % 128

    def test_engine_decodes_through_sharded_kernel(self, monkeypatch):
        """THE acceptance pin for the kernel path: a tp=2 engine with a
        kernel-envelope model (Hd=64, block_size=128, backend='flash')
        dispatches the Pallas paged kernel (counted at trace time,
        interpret mode on CPU) instead of the SPMD einsum fallback — and
        its greedy tokens match the tp=1 einsum-path engine exactly."""
        pda = importlib.import_module(
            "deepspeed_tpu.ops.pallas.paged_decode_attention")
        calls = {"n": 0}
        orig = pda.paged_decode_attention

        def counting(*a, **k):
            calls["n"] += 1
            return orig(*a, **k)

        monkeypatch.setattr(pda, "paged_decode_attention", counting)

        # each tp shard holds 2 kv heads of 64: a whole 128-lane pool row
        kw = dict(vocab_size=64, n_layer=1, n_head=8, n_kv_head=4,
                  d_model=512, d_ff=128, max_seq=256, remat=False)
        m_ref = CausalLM(TransformerConfig(**kw, attention_backend="auto"))
        params = m_ref.init_params(jax.random.key(0))
        prompts = _prompts((9, 14), seed=1)

        dist.set_mesh(None)
        ref_e = deepspeed_tpu.init_inference(
            m_ref, params=params, dtype="fp32",
            serving={"block_size": 128, "max_running": 2})
        ref = ref_e.generate_batch(prompts, max_new_tokens=6)
        assert calls["n"] == 0, "einsum reference engine touched the kernel"

        dist.set_mesh(None)
        m_tp = CausalLM(TransformerConfig(**kw, attention_backend="flash"))
        tp_e = deepspeed_tpu.init_inference(
            m_tp, params=params, dtype="fp32",
            serving={"block_size": 128, "max_running": 2, "tp": 2})
        got = tp_e.generate_batch(prompts, max_new_tokens=6)
        assert calls["n"] > 0, (
            "tp=2 decode fell back to the SPMD einsum path instead of the "
            "shard_map'd paged kernel")
        _assert_same(ref, got)


# --------------------------------------------------------------------- #
# compile-budget contract: serving_sharded_steady


class TestShardedSteadyContract:

    @pytest.fixture(autouse=True)
    def clean_state(self):
        from deepspeed_tpu.monitor.metrics import get_registry
        from deepspeed_tpu.monitor.trace import get_compile_watchdog
        dist.set_mesh(None)
        get_registry().reset()
        get_registry().set_enabled(True)
        get_compile_watchdog().reset()
        yield
        dist.set_mesh(None)
        get_registry().reset()
        get_registry().set_enabled(True)
        get_compile_watchdog().reset()

    def test_serving_sharded_steady_contract(self):
        """Sharding must not multiply programs: one generate_batch under
        serving.tp=2 with prefix caching AND speculation on compiles each
        fused entry exactly as often as its tp=1 budget — paged decode and
        verify ONCE — verified through the CompileWatchdog."""
        from dslint.contracts import check_compile_budgets

        dist.set_mesh(None)
        engine = deepspeed_tpu.init_inference(
            tiny_model(), dtype="fp32", telemetry=True,
            serving={"block_size": 8, "max_running": 2, "tp": 2,
                     "speculative": {"mode": "ngram", "k": 4}})
        rng = np.random.default_rng(0)
        motif = rng.integers(0, 64, size=10).astype(np.int32)
        prompts = [np.tile(motif, 3),
                   rng.integers(0, 64, size=7).astype(np.int32),
                   rng.integers(0, 64, size=12).astype(np.int32)]
        engine.generate_batch(prompts, max_new_tokens=10)
        st = engine._last_serve_stats
        assert st["verify_steps"] >= 1, "scenario never speculated"
        by_fn = engine.telemetry_snapshot()["compile"]["by_fn"]
        assert by_fn.get("inference.paged_decode", 0) <= 1, (
            "fused decode recompiled under tp — sharding multiplied "
            "programs")
        violations = check_compile_budgets(by_fn, "serving_sharded_steady",
                                           strict=True)
        assert violations == [], "\n".join(violations)


# --------------------------------------------------------------------- #
# telemetry: global KV gauges annotated with the tp degree


class TestTpTelemetry:

    @pytest.fixture(autouse=True)
    def clean_registry(self):
        from deepspeed_tpu.monitor.metrics import get_registry
        get_registry().reset()
        get_registry().set_enabled(True)
        yield
        get_registry().reset()
        get_registry().set_enabled(True)

    def test_kv_gauges_global_with_tp_annotation(self):
        from deepspeed_tpu.monitor.health import (health_summary,
                                                  render_summary_table)
        dist.set_mesh(None)
        engine = deepspeed_tpu.init_inference(
            tiny_model(), dtype="fp32", telemetry=True,
            serving={"block_size": 8, "max_running": 2, "tp": 2,
                     "max_num_blocks": 9})
        engine.generate_batch(_prompts((5, 9)), max_new_tokens=4)
        snap = engine.telemetry_snapshot()
        g = snap["gauges"]
        assert g.get("serving/tp") == 2.0
        # block counts are GLOBAL per slice (allocator is replicated):
        # a 9-block pool reports 9-block capacity numbers, not 9 / tp
        assert g.get("serving/kv_blocks_free", -1) + \
            g.get("serving/kv_blocks_used", -1) >= 0
        assert g["serving/kv_blocks_free"] <= 8   # 9 minus dummy, global
        summary = health_summary(snap)
        assert summary["serving"]["tp"] == 2.0
        table = render_summary_table(summary)
        assert "[tp=2]" in table, table

    def test_no_tp_annotation_at_tp1(self):
        from deepspeed_tpu.monitor.health import (health_summary,
                                                  render_summary_table)
        dist.set_mesh(None)
        engine = deepspeed_tpu.init_inference(
            tiny_model(), dtype="fp32", telemetry=True,
            serving={"block_size": 8, "max_running": 2})
        engine.generate_batch(_prompts((5,)), max_new_tokens=3)
        table = render_summary_table(
            health_summary(engine.telemetry_snapshot()))
        assert "[tp=" not in table
