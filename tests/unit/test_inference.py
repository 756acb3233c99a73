"""Inference engine tests (reference: tests/unit/inference/test_inference.py
adapted to the zoo models on the virtual mesh)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
import deepspeed_tpu.comm as dist
from deepspeed_tpu.models import CausalLM
from deepspeed_tpu.models.transformer import TransformerConfig


def tiny_model():
    return CausalLM(TransformerConfig(vocab_size=64, n_layer=2, n_head=4, d_model=32, d_ff=64, max_seq=32,
                                      remat=False))


@pytest.fixture(autouse=True)
def clean_mesh():
    dist.set_mesh(None)
    yield
    dist.set_mesh(None)


def test_init_inference_and_forward():
    engine = deepspeed_tpu.init_inference(tiny_model(), dtype="fp32", tensor_parallel={"tp_size": 2})
    logits = engine.forward(jnp.ones((1, 8), jnp.int32))
    assert logits.shape == (1, 8, 64)


def test_generate_greedy_deterministic():
    engine = deepspeed_tpu.init_inference(tiny_model(), dtype="fp32")
    out1 = engine.generate(jnp.array([[1, 2, 3]], jnp.int32), max_new_tokens=5)
    out2 = engine.generate(jnp.array([[1, 2, 3]], jnp.int32), max_new_tokens=5)
    assert out1.shape == (1, 8)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


class _NoCacheLM:
    """CausalLM facade WITHOUT forward_cached/init_cache — forces
    ``generate`` onto its full-prefix-recompute fallback path."""

    def __init__(self, inner):
        self._inner = inner
        self.config = inner.config

    def init_params(self, rng):
        return self._inner.init_params(rng)

    def forward(self, params, tokens, attn_mask=None):
        return self._inner.forward(params, tokens, attn_mask)

    __call__ = forward


def test_generate_fallback_rng_single_use(monkeypatch):
    """Regression for the PR-8 dslint DS002 finding: the fallback generate
    loop sampled with ``rng`` and then split the SAME consumed key, so the
    first draw used the raw seed key and every later step's stream was
    correlated with the draw already made. Pin the split-first order: every
    key reaching ``_sample_host`` is a fresh split child — distinct from
    the seed key and from each other."""
    from deepspeed_tpu.inference.engine import InferenceEngine

    seen = []
    real_sample = InferenceEngine._sample_host

    def recording_sample(logits, temperature, top_k, rng):
        seen.append(np.asarray(jax.random.key_data(rng)).tobytes())
        return real_sample(logits, temperature, top_k, rng)

    monkeypatch.setattr(InferenceEngine, "_sample_host",
                        staticmethod(recording_sample))
    engine = deepspeed_tpu.init_inference(_NoCacheLM(tiny_model()),
                                          dtype="fp32")
    out = engine.generate(jnp.array([[1, 2, 3]], jnp.int32),
                          max_new_tokens=4, temperature=1.0, seed=0)
    assert out.shape == (1, 7)
    assert len(seen) == 4
    assert len(set(seen)) == 4, "a sampling step reused a key"
    seed_key = np.asarray(jax.random.key_data(jax.random.key(0))).tobytes()
    assert seed_key not in seen, \
        "the raw seed key was consumed by a draw (the DS002 bug)"


def test_generate_length_check():
    engine = deepspeed_tpu.init_inference(tiny_model(), dtype="fp32")
    with pytest.raises(ValueError, match="max_seq"):
        engine.generate(jnp.ones((1, 30), jnp.int32), max_new_tokens=10)


def test_auto_tp_specs_heuristics():
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.inference.auto_tp import auto_tp_specs
    params = {
        "h0": {"q_proj": np.zeros((8, 8)), "o_proj": np.zeros((8, 8)), "ln": np.zeros((8,))},
        "embed_tokens": np.zeros((64, 8)),
    }
    specs = auto_tp_specs(params)
    assert specs["h0"]["q_proj"] == P(None, "tp")
    assert specs["h0"]["o_proj"] == P("tp", None)
    assert specs["h0"]["ln"] == P(None)
    assert specs["embed_tokens"] == P("tp", None)


def test_client_optax_optimizer_descends():
    """A finalized optax chain (lr inside) must still descend (sign check)."""
    import optax

    from .simple_model import SimpleModel, random_batch
    model = SimpleModel(hidden_dim=16)
    params = model.init_params(jax.random.key(0))
    cfg = {"train_micro_batch_size_per_gpu": 4, "gradient_accumulation_steps": 1,
           "mesh": {"dp": 8}, "steps_per_print": 0}
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params, config=cfg,
                                               optimizer=optax.adam(1e-2))
    losses = [float(engine.train_batch(random_batch(32, 16, seed=i))) for i in range(35)]
    assert losses[-1] < losses[0] * 0.5, f"{losses[0]} -> {losses[-1]}"


def _drawn_biases(params):
    """``params`` with its attention biases (zeros as initialised), where
    it has them, drawn: a bias that is dropped or added twice shows."""
    attn = params["layers"]["attn"]
    for i, b in enumerate(n for n in ("bq", "bk", "bv", "bo") if n in attn):
        attn[b] = 0.5 * jax.random.normal(jax.random.key(10 + i),
                                          attn[b].shape)
    return params


class TestKVCacheDecode:
    """KV-cache decode path (reference: inference_context.h:49 workspace,
    softmax_context KV append pt_binding.cpp:1668-1793)."""

    def _model(self, **over):
        base = dict(vocab_size=64, n_layer=2, n_head=4, d_model=32, d_ff=64,
                    max_seq=32, remat=False)
        base.update(over)
        return CausalLM(TransformerConfig(**base))

    @pytest.mark.parametrize("style", [
        "gpt2", "gqa", "opt",
        pytest.param("llama", marks=pytest.mark.nightly),
        pytest.param("alibi", marks=pytest.mark.nightly),
        pytest.param("gptj", marks=pytest.mark.nightly),
        pytest.param("neox_partial", marks=pytest.mark.nightly)])
    def test_decode_logits_match_full_forward(self, style):
        over = {
            "gpt2": {},
            # OPT's projections: a bias on q, k, v and o (drawn below), ReLU
            "opt": dict(activation="relu", attn_bias=True),
            "llama": dict(pos_embedding="rope", norm="rmsnorm", activation="swiglu",
                          tie_embeddings=False),
            "alibi": dict(pos_embedding="alibi"),
            "gqa": dict(pos_embedding="rope", n_kv_head=2),
            # GPT-J: partial INTERLEAVED rotary + single-LN parallel residual
            "gptj": dict(pos_embedding="rope", rope_dim=4, rope_interleaved=True,
                         parallel_residual=True, tie_embeddings=False,
                         lm_head_bias=True),
            # NeoX rotary_pct < 1: partial half-split rotary
            "neox_partial": dict(pos_embedding="rope", rope_dim=4,
                                 parallel_residual=True, attn_bias=True),
        }[style]
        model = self._model(**over)
        params = _drawn_biases(model.init_params(jax.random.key(0)))
        toks = jax.random.randint(jax.random.key(1), (2, 10), 0, 64)

        full = model.forward(params, toks).astype(jnp.float32)

        cache = model.init_cache(2, 16, dtype=jnp.float32)
        lp, cache = model.forward_cached(params, toks[:, :6], cache, jnp.int32(0))
        np.testing.assert_allclose(np.asarray(lp), np.asarray(full[:, :6]),
                                   rtol=2e-4, atol=2e-4)
        for i in range(6, 10):
            ld, cache = model.forward_cached(params, toks[:, i:i + 1], cache, jnp.int32(i))
            np.testing.assert_allclose(np.asarray(ld[:, 0]), np.asarray(full[:, i]),
                                       rtol=2e-4, atol=2e-4, err_msg=f"step {i}")

    def test_cached_generate_matches_recompute(self):
        model = self._model()
        engine = deepspeed_tpu.init_inference(model, dtype="fp32")
        prompt = jnp.array([[1, 2, 3, 4]], jnp.int32)
        out = engine.generate(prompt, max_new_tokens=6)

        # reference: the old full-prefix recompute loop
        toks = prompt
        for _ in range(6):
            logits = engine.forward(toks)[:, -1, :].astype(jnp.float32)
            nxt = jnp.argmax(logits, axis=-1)
            toks = jnp.concatenate([toks, nxt[:, None].astype(jnp.int32)], axis=1)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(toks))

    def test_decode_compiles_once(self):
        model = self._model()
        engine = deepspeed_tpu.init_inference(model, dtype="fp32")
        prompt = jnp.array([[1, 2, 3]], jnp.int32)
        engine.generate(prompt, max_new_tokens=8)
        assert engine._decode_jit._cache_size() == 1, (
            "decode step recompiled during generation")

    def test_no_recompile_across_prompt_lengths_and_max_new(self):
        """Reference workspace semantics (inference_context.h:49): differing
        prompt lengths (same 128-bucket) and max_new values reuse ONE
        compiled prefill + ONE compiled decode loop and one KV workspace."""
        model = self._model()
        engine = deepspeed_tpu.init_inference(model, dtype="fp32")
        engine.generate(jnp.array([[1, 2, 3]], jnp.int32), max_new_tokens=4)
        ws0 = engine._workspace
        engine.generate(jnp.array([[1, 2, 3, 4, 5]], jnp.int32), max_new_tokens=7)
        engine.generate(jnp.array([[9, 8]], jnp.int32), max_new_tokens=2)
        assert engine._decode_jit._cache_size() == 1
        assert engine._prefill_jit._cache_size() == 1
        assert engine._workspace[1] == ws0[1]  # same workspace capacity reused

    def test_workspace_reused_for_smaller_batch(self):
        """A call with B smaller than the allocated workspace batch must
        slice (keeping the larger workspace for future calls), not
        reallocate — and produce the same per-row tokens."""
        model = self._model()
        engine = deepspeed_tpu.init_inference(model, dtype="fp32")
        prompt = jnp.array([[1, 2, 3], [4, 5, 6]], jnp.int32)
        out2 = engine.generate(prompt, max_new_tokens=5)
        ws = engine._workspace
        out1 = engine.generate(prompt[:1], max_new_tokens=5)
        assert engine._workspace is ws, (
            "smaller-batch call replaced the larger workspace")
        np.testing.assert_array_equal(np.asarray(out1)[0], np.asarray(out2)[0])
        # and the big batch immediately reuses the kept workspace
        out2b = engine.generate(prompt, max_new_tokens=5)
        assert engine._workspace[1] == ws[1]
        np.testing.assert_array_equal(np.asarray(out2b), np.asarray(out2))

    def test_decode_output_buffer_bounded_by_max_new(self):
        """The decode loop's token buffer is sized by the (128-bucketed)
        max_new, not the cache capacity Smax (HBM + host-transfer waste)."""
        model = self._model(max_seq=256)
        engine = deepspeed_tpu.init_inference(model, dtype="fp32")
        engine.generate(jnp.array([[1, 2, 3]], jnp.int32), max_new_tokens=5)
        assert engine._workspace[1] == 256  # cache capacity stays Smax
        # compiled decode loop's out buffer: bucket(5) = 128, not 256
        lowered = engine._decode_jit.lower(
            engine.params, engine._workspace[2],
            jnp.zeros((1,), jnp.int32), jnp.int32(3), jnp.int32(5),
            jax.random.key(0), jnp.float32(0.0), jnp.int32(0),
            jnp.int32(-1), 128)
        shapes = str(lowered.out_info)
        assert "(1, 128)" in shapes and "(1, 256)" not in shapes, shapes

    def test_eos_early_exit_on_device(self):
        """The decode loop must stop early at eos without per-token host
        syncs: the output stops at the first eos row-wide."""
        model = self._model()
        engine = deepspeed_tpu.init_inference(model, dtype="fp32")
        prompt = jnp.array([[1, 2, 3]], jnp.int32)
        free = engine.generate(prompt, max_new_tokens=10)
        # pick the token the model actually emits first, use it as eos
        eos = int(np.asarray(free)[0, 3])
        out = engine.generate(prompt, max_new_tokens=10, eos_token_id=eos)
        assert out.shape[1] == 4  # prompt + the eos token, loop exited early

    def test_sampled_generation_shapes(self):
        model = self._model()
        engine = deepspeed_tpu.init_inference(model, dtype="fp32")
        prompt = jnp.array([[1, 2, 3], [4, 5, 6]], jnp.int32)
        out = engine.generate(prompt, max_new_tokens=5, temperature=0.8, top_k=10, seed=3)
        assert out.shape == (2, 8)
        assert int(out.min()) >= 0 and int(out.max()) < 64


@pytest.mark.parametrize("program", ["decode", "prefill_chunk", "verify"])
def test_biased_projections_give_the_full_forwards_logits(program):
    """OPT's form of the q/k/v projections (a bias on each, drawn and not
    zero, learned positions, ReLU) through ``_qkv_project``: a paged prefill
    followed by decode steps, prefill chunks or a verify window gives the
    logits of the training ``forward()``, whose ``attention()`` does not
    share that function (the workspace path: ``TestKVCacheDecode``'s
    ``opt`` style). The other three forms have theirs: a norm of each head
    in ``test_sdar.py::test_pass_logits_are_the_references`` and
    ``test_lfm2_moe.py::test_logits_of_a_whole_prompt_and_four_decode_steps``,
    a gated output in ``test_trinity.py::test_prefill_then_decode_is_the_reference``,
    the latent ``wq_b`` in ``test_longcat_flash.py::
    test_prefill_then_absorbed_decode_gives_the_references_logits``."""
    model = CausalLM(TransformerConfig(
        vocab_size=64, n_layer=2, n_head=4, d_model=32, d_ff=64, max_seq=32,
        activation="relu", attn_bias=True, remat=False))
    params = _drawn_biases(model.init_params(jax.random.key(0)))
    S, n0, bs, nb = 14, 6, 8, 4
    toks = np.asarray(jax.random.randint(jax.random.key(1), (1, S), 0, 64))
    full = np.asarray(model.forward(params, toks).astype(jnp.float32))[0]

    def close(got, want, what):
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4,
                                   atol=2e-4, err_msg=what)

    # the request holds blocks 1..4 (0 is the dummy) and row 1 of 3
    pools = model.init_paged_cache(nb + 1, bs, dtype=jnp.float32)
    table = np.arange(1, nb + 1, dtype=np.int32)
    slot = lambda t: (table[t // bs] * bs + t % bs).astype(np.int32)  # noqa: E731
    t = np.arange(bs, dtype=np.int32)
    first = np.zeros((1, bs), np.int32)
    first[0, :n0] = toks[0, :n0]
    lg, pools = model.forward_paged_prefill(
        params, first, pools, np.where(t < n0, slot(t), t), np.int32(n0 - 1))
    close(lg[0], full[n0 - 1], "prefill")
    bt = np.zeros((3, nb), np.int32)
    bt[1] = table
    pos = np.zeros((3,), np.int32)
    if program == "decode":
        for i in range(n0, S):
            pos[1] = i
            nt = np.zeros((3, 1), np.int32)
            nt[1, 0] = toks[0, i]
            lg, pools = model.forward_paged_decode(params, nt, pools, bt, pos)
            close(lg[1], full[i], f"step {i}")
    elif program == "prefill_chunk":
        for start in (n0, n0 + 4):
            lg, pools = model.forward_paged_prefill_chunk(
                params, toks[:, start:start + 4], pools, table[None],
                slot(start + np.arange(4)), np.int32(start), np.int32(3))
            close(lg[0], full[start + 3], f"chunk at {start}")
    else:
        W = 4
        pos[1] = n0
        win = np.zeros((3, W), np.int32)
        win[1] = toks[0, n0:n0 + W]
        slots = np.tile(np.arange(W, dtype=np.int32), (3, 1))   # the dummy's
        slots[1] = slot(n0 + np.arange(W))
        lg, pools = model.forward_paged_verify(params, win, pools, bt, slots,
                                               pos)
        close(lg[1], full[n0:n0 + W], "window")


def test_the_training_forward_holds_no_projection_flat():
    """``_flat`` is the serving projections' (``_qkv_project``: a few dozen
    rows against a whole matrix); the training ``attention()`` multiplies
    thousands of rows, where the compiler's own layout is the better trade,
    and traces to what it did (PERF.md section 6, PR 57)."""
    model = tiny_model()
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    toks = jax.ShapeDtypeStruct((2, 8), jnp.int32)
    assert "optimization_barrier" not in str(
        jax.make_jaxpr(model.forward)(params, toks))
    cache = jax.eval_shape(lambda: model.init_cache(2, 16, dtype=jnp.float32))
    assert "optimization_barrier" in str(jax.make_jaxpr(model.forward_cached)(
        params, toks, cache, jnp.int32(0)))


def test_generate_rejects_encoder_modules():
    """generate() on an encoder (bidirectional BERT) must raise the loud
    causal-LM error instead of emitting autoregressive nonsense."""
    import deepspeed_tpu
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.models.bert import BertConfig, BertModel
    import jax

    dist.set_mesh(None)
    model = BertModel(BertConfig(vocab_size=64, max_seq=16, n_layer=1,
                                 n_head=2, d_model=16, d_ff=32))
    eng = deepspeed_tpu.init_inference(
        model, params=model.init_params(jax.random.key(0)), dtype="fp32")
    with pytest.raises(ValueError, match="requires a causal LM"):
        eng.generate(np.asarray([[1, 2, 3]], np.int32), max_new_tokens=2)


def test_profile_model_time_surface():
    """profile_model_time / model_times (reference inference engine
    latency profiling surface)."""
    import deepspeed_tpu
    import deepspeed_tpu.comm as dist
    import jax
    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.models.transformer import TransformerConfig

    dist.set_mesh(None)
    model = CausalLM(TransformerConfig(vocab_size=64, n_layer=1, n_head=2,
                                       d_model=16, max_seq=16))
    eng = deepspeed_tpu.init_inference(
        model, params=model.init_params(jax.random.key(0)), dtype="fp32")
    with pytest.raises(RuntimeError, match="not enabled"):
        eng.model_times()
    eng.profile_model_time()
    tok = np.asarray([[1, 2, 3]], np.int32)
    eng.forward(tok)
    eng.forward(tok)
    times = eng.model_times()
    assert len(times) == 2 and all(t > 0 for t in times)
    assert eng.model_times() == []  # drained
