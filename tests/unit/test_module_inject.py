"""HF ingestion parity tests (reference ``module_inject/containers`` +
``load_checkpoint.py``).

Gold standard: for each supported architecture, build a tiny
randomly-initialised ``transformers`` model, save it in HF format, ingest it
with the policy loader, and require LOGITS parity (which implies
token-for-token greedy-decode parity) against the torch forward pass.
"""

import json
import os

import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

import jax.numpy as jnp

import deepspeed_tpu.comm as dist
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.module_inject import load_hf_checkpoint


@pytest.fixture(autouse=True)
def no_mesh():
    dist.set_mesh(None)
    yield


from .hf_fixtures import save_hf  # noqa: E402  (shared checkpoint writer)


def parity(tmp_path, hf_model, hf_cfg, rtol=2e-2, atol=2e-3):
    """Ingest the saved checkpoint and compare full logits on random tokens."""
    d = save_hf(hf_model, hf_cfg, tmp_path)
    model, params = load_hf_checkpoint(d)
    # force the einsum attention path (flash is TPU-only; interpret is slow)
    import dataclasses
    model = type(model)(dataclasses.replace(model.config, attention_backend="xla"))

    rng = np.random.default_rng(0)
    tok = rng.integers(0, hf_cfg.vocab_size, size=(2, 24)).astype(np.int64)
    with torch.no_grad():
        ref = hf_model(input_ids=torch.from_numpy(tok)).logits.float().numpy()
    got = np.asarray(model.forward(params, jnp.asarray(tok.astype(np.int32))), np.float32)

    np.testing.assert_allclose(got, ref, rtol=rtol, atol=atol)
    # greedy decode parity follows from argmax equality
    np.testing.assert_array_equal(got.argmax(-1), ref.argmax(-1))


class TestHFPolicies:
    @pytest.mark.slow
    def test_gpt2(self, tmp_path):
        cfg = transformers.GPT2Config(vocab_size=96, n_positions=32, n_embd=32,
                                      n_layer=2, n_head=2)
        parity(tmp_path, transformers.GPT2LMHeadModel(cfg), cfg)

    def test_llama(self, tmp_path):
        cfg = transformers.LlamaConfig(vocab_size=96, hidden_size=32, num_hidden_layers=2,
                                       num_attention_heads=2, num_key_value_heads=2,
                                       intermediate_size=64, max_position_embeddings=32,
                                       tie_word_embeddings=False)
        parity(tmp_path, transformers.LlamaForCausalLM(cfg), cfg)

    def test_llama_gqa(self, tmp_path):
        cfg = transformers.LlamaConfig(vocab_size=96, hidden_size=64, num_hidden_layers=2,
                                       num_attention_heads=4, num_key_value_heads=2,
                                       intermediate_size=64, max_position_embeddings=32,
                                       tie_word_embeddings=False)
        parity(tmp_path, transformers.LlamaForCausalLM(cfg), cfg)

    def test_bloom(self, tmp_path):
        cfg = transformers.BloomConfig(vocab_size=96, hidden_size=32, n_layer=2, n_head=4)
        parity(tmp_path, transformers.BloomForCausalLM(cfg), cfg)

    def test_opt(self, tmp_path):
        cfg = transformers.OPTConfig(vocab_size=96, hidden_size=32, num_hidden_layers=2,
                                     num_attention_heads=2, ffn_dim=64,
                                     max_position_embeddings=32, word_embed_proj_dim=32)
        parity(tmp_path, transformers.OPTForCausalLM(cfg), cfg)

    def test_gpt_neox(self, tmp_path):
        cfg = transformers.GPTNeoXConfig(vocab_size=96, hidden_size=32, num_hidden_layers=2,
                                         num_attention_heads=2, intermediate_size=64,
                                         max_position_embeddings=32, rotary_pct=1.0,
                                         use_parallel_residual=True)
        parity(tmp_path, transformers.GPTNeoXForCausalLM(cfg), cfg)

    def test_gpt_neox_partial_rotary(self, tmp_path):
        """rotary_pct < 1: only the first pct of each head rotates."""
        cfg = transformers.GPTNeoXConfig(vocab_size=96, hidden_size=32, num_hidden_layers=2,
                                         num_attention_heads=2, intermediate_size=64,
                                         max_position_embeddings=32, rotary_pct=0.5,
                                         use_parallel_residual=True)
        parity(tmp_path, transformers.GPTNeoXForCausalLM(cfg), cfg)

    def test_gptj(self, tmp_path):
        """GPT-J: interleaved partial rotary, single-LN parallel residual,
        biased untied lm_head."""
        cfg = transformers.GPTJConfig(vocab_size=96, n_embd=32, n_layer=2,
                                      n_head=2, n_inner=64, n_positions=32,
                                      rotary_dim=8)
        parity(tmp_path, transformers.GPTJForCausalLM(cfg), cfg)

    def test_opt_post_ln_rejected(self):
        from deepspeed_tpu.module_inject.policies import policy_for
        hf = dict(vocab_size=96, hidden_size=32, num_hidden_layers=1,
                  num_attention_heads=2, ffn_dim=64, max_position_embeddings=32,
                  do_layer_norm_before=False)
        with pytest.raises(NotImplementedError, match="do_layer_norm_before"):
            policy_for("opt").zoo_config(hf)

    def test_llama_rope_scaling_rejected(self):
        from deepspeed_tpu.module_inject.policies import policy_for
        hf = dict(vocab_size=96, hidden_size=32, num_hidden_layers=1,
                  num_attention_heads=2, intermediate_size=64,
                  rope_scaling={"rope_type": "llama3", "factor": 8.0})
        with pytest.raises(NotImplementedError, match="rope_scaling"):
            policy_for("llama").zoo_config(hf)
        # explicit no-op spellings of plain rope must still load
        hf["rope_scaling"] = {"rope_type": "default"}
        assert policy_for("llama").zoo_config(hf).pos_embedding == "rope"
        hf["rope_scaling"] = {"type": "linear", "factor": 1.0}
        assert policy_for("llama").zoo_config(hf).pos_embedding == "rope"

    def test_neox_rope_theta_field_name(self):
        from deepspeed_tpu.module_inject.policies import policy_for
        base = dict(vocab_size=96, hidden_size=32, num_hidden_layers=1,
                    num_attention_heads=2, intermediate_size=64,
                    max_position_embeddings=32)
        cfg = policy_for("gpt_neox").zoo_config({**base, "rope_theta": 500000.0})
        assert cfg.rope_theta == 500000.0
        cfg = policy_for("gpt_neox").zoo_config({**base, "rotary_emb_base": 20000.0})
        assert cfg.rope_theta == 20000.0

    def test_unknown_arch_rejected(self, tmp_path):
        os.makedirs(tmp_path, exist_ok=True)
        with open(tmp_path / "config.json", "w") as f:
            json.dump({"model_type": "mamba"}, f)
        with open(tmp_path / "model.safetensors", "wb") as f:
            from safetensors.numpy import save_file as sf
            sf({"x": np.zeros(1, np.float32)}, str(tmp_path / "model.safetensors"))
        with pytest.raises(ValueError, match="no ingestion policy"):
            load_hf_checkpoint(str(tmp_path))


class TestInitInference:
    def test_init_inference_from_hf_path_greedy_parity(self, tmp_path):
        """Reference flow: deepspeed.init_inference + checkpoint loading —
        generate() must match transformers.generate token-for-token."""
        import deepspeed_tpu

        cfg = transformers.GPT2Config(vocab_size=96, n_positions=32, n_embd=32,
                                      n_layer=2, n_head=2)
        hf = transformers.GPT2LMHeadModel(cfg)
        d = save_hf(hf, cfg, tmp_path)

        eng = deepspeed_tpu.init_inference(d, dtype="fp32")
        tok = np.array([[1, 2, 3, 4]], np.int32)
        gen = np.asarray(eng.generate(tok, max_new_tokens=5))
        with torch.no_grad():
            ref = hf.generate(torch.tensor(tok, dtype=torch.long), max_new_tokens=5,
                              do_sample=False)
        np.testing.assert_array_equal(gen[0], ref[0].numpy())


class TestShardedIndex:
    def test_multi_file_streaming(self, tmp_path):
        """Sharded index checkpoints load identically to single-file."""
        cfg = transformers.GPT2Config(vocab_size=96, n_positions=32, n_embd=32,
                                      n_layer=2, n_head=2)
        m = transformers.GPT2LMHeadModel(cfg)
        d1 = tmp_path / "single"
        d1.mkdir()
        save_hf(m, cfg, d1)
        _, params1 = load_hf_checkpoint(str(d1))

        # split the same tensors across two shard files + index
        d2 = tmp_path / "sharded"
        d2.mkdir()
        from safetensors.numpy import load_file, save_file
        sd = load_file(str(d1 / "model.safetensors"))
        names = sorted(sd)
        half = len(names) // 2
        save_file({n: sd[n] for n in names[:half]}, str(d2 / "model-00001-of-00002.safetensors"))
        save_file({n: sd[n] for n in names[half:]}, str(d2 / "model-00002-of-00002.safetensors"))
        index = {"weight_map": {n: ("model-00001-of-00002.safetensors" if i < half
                                    else "model-00002-of-00002.safetensors")
                                for i, n in enumerate(names)}}
        with open(d2 / "model.safetensors.index.json", "w") as f:
            json.dump(index, f)
        with open(d2 / "config.json", "w") as f:
            f.write(cfg.to_json_string())

        _, params2 = load_hf_checkpoint(str(d2))
        import jax
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), params1, params2)


class TestGPTNeoPolicy:
    """HF gpt_neo ingestion (reference containers/gptneo.py): unscaled
    attention, gelu_new, bias-free q/k/v."""

    def test_gpt_neo_global(self, tmp_path):
        cfg = transformers.GPTNeoConfig(
            vocab_size=96, hidden_size=32, num_layers=2, num_heads=4,
            max_position_embeddings=32, attention_types=[[["global"], 2]],
            intermediate_size=64)
        parity(tmp_path, transformers.GPTNeoForCausalLM(cfg), cfg)

    def test_gpt_neo_local_capped_to_window(self, tmp_path):
        """Alternating global/local layers: exact at seq <= window_size, and
        max_seq is capped there so longer prompts are rejected."""
        cfg = transformers.GPTNeoConfig(
            vocab_size=96, hidden_size=32, num_layers=2, num_heads=4,
            max_position_embeddings=64, window_size=24,
            attention_types=[[["global", "local"], 1]], intermediate_size=64)
        hf_model = transformers.GPTNeoForCausalLM(cfg)
        d = save_hf(hf_model, cfg, tmp_path)
        model, params = load_hf_checkpoint(d)
        assert model.config.max_seq == 24
        assert model.config.attn_scale == 1.0
        rng = np.random.default_rng(1)
        tok = rng.integers(0, 96, size=(2, 20)).astype(np.int64)
        with torch.no_grad():
            ref = hf_model(input_ids=torch.from_numpy(tok)).logits.float().numpy()
        got = np.asarray(model.forward(params, jnp.asarray(tok.astype(np.int32))),
                         np.float32)
        np.testing.assert_allclose(got, ref, rtol=2e-2, atol=2e-3)
        np.testing.assert_array_equal(got.argmax(-1), ref.argmax(-1))


class TestDistilBertPolicy:
    """HF distilbert ingestion (reference containers/distil_bert.py): BERT
    encoder without token types/pooler, fill-mask head tied to embeddings."""

    def test_distilbert_fill_mask(self, tmp_path):
        cfg = transformers.DistilBertConfig(
            vocab_size=96, dim=32, n_layers=2, n_heads=4, hidden_dim=64,
            max_position_embeddings=32)
        hf_model = transformers.DistilBertForMaskedLM(cfg)
        d = save_hf(hf_model, cfg, tmp_path)
        model, params = load_hf_checkpoint(d)
        from deepspeed_tpu.models.bert import BertModel
        assert isinstance(model, BertModel) and model.with_mlm_head
        rng = np.random.default_rng(2)
        tok = rng.integers(0, 96, size=(2, 16)).astype(np.int64)
        with torch.no_grad():
            ref = hf_model(input_ids=torch.from_numpy(tok)).logits.float().numpy()
        got = np.asarray(model.forward(params, jnp.asarray(tok.astype(np.int32))),
                         np.float32)
        np.testing.assert_allclose(got, ref, rtol=2e-2, atol=2e-3)

    def test_distilbert_serves_through_init_inference(self, tmp_path):
        import deepspeed_tpu
        cfg = transformers.DistilBertConfig(
            vocab_size=96, dim=32, n_layers=2, n_heads=4, hidden_dim=64,
            max_position_embeddings=32)
        d = save_hf(transformers.DistilBertForMaskedLM(cfg), cfg, tmp_path)
        eng = deepspeed_tpu.init_inference(d, dtype="fp32")
        out = np.asarray(eng.forward(np.asarray([[1, 2, 3, 4]], np.int32)))
        assert out.shape == (1, 4, 96)
        assert np.isfinite(out).all()


class TestBertPolicy:
    """HF bert ingestion (reference containers/bert.py HFBertLayerPolicy):
    post-LN encoder + token types, optional pooler / fill-mask head."""

    def _cfg(self):
        return transformers.BertConfig(
            vocab_size=96, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=64,
            max_position_embeddings=32, type_vocab_size=2)

    def test_bert_fill_mask(self, tmp_path):
        cfg = self._cfg()
        torch.manual_seed(3)
        hf_model = transformers.BertForMaskedLM(cfg)
        d = save_hf(hf_model, cfg, tmp_path)
        model, params = load_hf_checkpoint(d)
        from deepspeed_tpu.models.bert import BertModel
        assert isinstance(model, BertModel) and model.with_mlm_head
        rng = np.random.default_rng(3)
        tok = rng.integers(0, 96, size=(2, 16)).astype(np.int64)
        with torch.no_grad():
            ref = hf_model(input_ids=torch.from_numpy(tok)).logits.float().numpy()
        got = np.asarray(model.forward(params, jnp.asarray(tok.astype(np.int32))),
                         np.float32)
        np.testing.assert_allclose(got, ref, rtol=2e-2, atol=2e-3)
        np.testing.assert_array_equal(got.argmax(-1), ref.argmax(-1))

    def test_bert_base_hidden_and_pooled(self, tmp_path):
        """Headless BertModel checkpoint (no 'bert.' prefix, real pooler)."""
        cfg = self._cfg()
        torch.manual_seed(4)
        hf_model = transformers.BertModel(cfg).eval()
        d = save_hf(hf_model, cfg, tmp_path)
        model, params = load_hf_checkpoint(d)
        rng = np.random.default_rng(4)
        tok = rng.integers(0, 96, size=(2, 16)).astype(np.int64)
        tt = rng.integers(0, 2, size=(2, 16)).astype(np.int64)
        with torch.no_grad():
            ref = hf_model(input_ids=torch.from_numpy(tok),
                           token_type_ids=torch.from_numpy(tt))
        hidden, pooled = model(params, jnp.asarray(tok.astype(np.int32)),
                               jnp.asarray(tt.astype(np.int32)))
        np.testing.assert_allclose(np.asarray(hidden),
                                   ref.last_hidden_state.numpy(),
                                   rtol=2e-2, atol=2e-3)
        np.testing.assert_allclose(np.asarray(pooled),
                                   ref.pooler_output.numpy(),
                                   rtol=2e-2, atol=2e-3)

    def test_bert_serves_through_init_inference(self, tmp_path):
        import deepspeed_tpu
        cfg = self._cfg()
        d = save_hf(transformers.BertForMaskedLM(cfg), cfg, tmp_path)
        eng = deepspeed_tpu.init_inference(d, dtype="fp32")
        out = np.asarray(eng.forward(np.asarray([[1, 2, 3, 4]], np.int32)))
        assert out.shape == (1, 4, 96)
        assert np.isfinite(out).all()

    def test_bert_relu_mlm_head(self, tmp_path):
        """hidden_act also drives the MLM transform (HF
        BertPredictionHeadTransform), not just the encoder layers."""
        cfg = self._cfg()
        cfg.hidden_act = "relu"
        torch.manual_seed(7)
        hf_model = transformers.BertForMaskedLM(cfg)
        d = save_hf(hf_model, cfg, tmp_path)
        model, params = load_hf_checkpoint(d)
        rng = np.random.default_rng(7)
        tok = rng.integers(0, 96, size=(2, 16)).astype(np.int64)
        with torch.no_grad():
            ref = hf_model(input_ids=torch.from_numpy(tok)).logits.float().numpy()
        got = np.asarray(model.forward(params, jnp.asarray(tok.astype(np.int32))),
                         np.float32)
        np.testing.assert_allclose(got, ref, rtol=2e-2, atol=2e-3)


class TestCLIPPolicy:
    """HF clip ingestion (reference containers/clip.py HFCLIPLayerPolicy +
    model_implementations/transformers/clip_encoder.py): standalone text
    tower, and the full two-tower CLIPModel -> DSClipEncoder."""

    def test_clip_text_model(self, tmp_path):
        cfg = transformers.CLIPTextConfig(
            vocab_size=99, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            max_position_embeddings=16, bos_token_id=1, eos_token_id=2)
        torch.manual_seed(5)
        hf_model = transformers.CLIPTextModel(cfg).eval()
        d = save_hf(hf_model, cfg, tmp_path)
        model, params = load_hf_checkpoint(d)
        from deepspeed_tpu.models.clip import CLIPTextEncoder
        assert isinstance(model, CLIPTextEncoder)
        rng = np.random.default_rng(5)
        tok = rng.integers(3, 98, size=(2, 16)).astype(np.int64)
        tok[:, -1] = 98  # max id last: HF's eos==2 legacy argmax pooling
        with torch.no_grad():
            ref = hf_model(input_ids=torch.from_numpy(tok))
        hidden, pooled = model(params, jnp.asarray(tok.astype(np.int32)))
        np.testing.assert_allclose(np.asarray(hidden),
                                   ref.last_hidden_state.numpy(),
                                   rtol=2e-2, atol=2e-3)
        np.testing.assert_allclose(np.asarray(pooled),
                                   ref.pooler_output.numpy(),
                                   rtol=2e-2, atol=2e-3)

    def test_clip_text_serves_through_init_inference(self, tmp_path):
        """A standalone text tower rides the generic forward path (last
        hidden states — the SD conditioning surface)."""
        import deepspeed_tpu
        cfg = transformers.CLIPTextConfig(
            vocab_size=99, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            max_position_embeddings=16, bos_token_id=1, eos_token_id=2)
        d = save_hf(transformers.CLIPTextModel(cfg), cfg, tmp_path)
        eng = deepspeed_tpu.init_inference(d, dtype="fp32")
        out = np.asarray(eng.forward(np.asarray([[1, 2, 3, 4]], np.int32)))
        assert out.shape == (1, 4, 32)
        assert np.isfinite(out).all()

    def test_clip_full_model_features(self, tmp_path):
        """Full CLIPModel: DSClipEncoder with projected text/image features
        matching get_text_features / get_image_features."""
        cfg = transformers.CLIPConfig(
            projection_dim=24,
            text_config={"vocab_size": 99, "hidden_size": 32,
                         "intermediate_size": 64, "num_hidden_layers": 2,
                         "num_attention_heads": 4,
                         "max_position_embeddings": 16,
                         "bos_token_id": 1, "eos_token_id": 2},
            vision_config={"image_size": 8, "patch_size": 4,
                           "hidden_size": 32, "intermediate_size": 64,
                           "num_hidden_layers": 2, "num_attention_heads": 4})
        torch.manual_seed(6)
        hf_model = transformers.CLIPModel(cfg).eval()
        d = save_hf(hf_model, cfg, tmp_path)
        model, params = load_hf_checkpoint(d)
        from deepspeed_tpu.models.clip import DSClipEncoder
        assert isinstance(model, DSClipEncoder)

        rng = np.random.default_rng(6)
        tok = rng.integers(3, 98, size=(2, 16)).astype(np.int64)
        tok[:, -1] = 98
        img = rng.standard_normal((2, 3, 8, 8)).astype(np.float32)  # NCHW
        with torch.no_grad():
            tfeat = hf_model.get_text_features(input_ids=torch.from_numpy(tok)).numpy()
            ifeat = hf_model.get_image_features(pixel_values=torch.from_numpy(img)).numpy()
        _, got_t = model.encode_text(params["text"], jnp.asarray(tok.astype(np.int32)))
        # zoo vision is NHWC (TPU-preferred layout)
        _, got_i = model.encode_image(params["vision"],
                                      jnp.asarray(img.transpose(0, 2, 3, 1)))
        np.testing.assert_allclose(np.asarray(got_t), tfeat, rtol=2e-2, atol=2e-3)
        np.testing.assert_allclose(np.asarray(got_i), ifeat, rtol=2e-2, atol=2e-3)


# --------------------------------------------------------------------- #
# The benchmark's plain reference of the Granite 4.0-H block against the
# published code. Here because this file's worker has paid for torch and
# transformers already (~30 s); what the PROGRAM is held to that reference
# by is in ``test_granite_hybrid.py``.

class _HFGraniteWeights:
    """``GraniteMoeHybridForCausalLM``'s state dict under the reference's
    names (``reference/maps/granite-4.0-h-micro.json`` says what each is in
    the program's tree): torch keeps ``[out, in]``, the conv ``[C, 1, K]``,
    and ``shared_mlp.input_linear`` the gate over the up projection."""

    def __init__(self, hf_model, d_ff):
        self.sd = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()}
        self.d_ff = d_ff

    def top(self):
        return {"wte": jnp.asarray(self.sd["model.embed_tokens.weight"]),
                "lnf_g": jnp.asarray(self.sd["model.norm.weight"])}

    def layer(self, l):
        sd, p, F = self.sd, f"model.layers.{l}.", self.d_ff
        w_in = sd[p + "shared_mlp.input_linear.weight"]
        w = {"ln1_g": sd[p + "input_layernorm.weight"],
             "ln2_g": sd[p + "post_attention_layernorm.weight"],
             "w_gate": w_in[:F].T, "w_up": w_in[F:].T,
             "w_down": sd[p + "shared_mlp.output_linear.weight"].T}
        if p + "mamba.A_log" in sd:
            q = p + "mamba."
            w.update(w_in=sd[q + "in_proj.weight"].T,
                     conv=sd[q + "conv1d.weight"][:, 0, :].T,
                     conv_b=sd[q + "conv1d.bias"], A_log=sd[q + "A_log"],
                     dt_bias=sd[q + "dt_bias"], D=sd[q + "D"],
                     norm_g=sd[q + "norm.weight"],
                     w_out=sd[q + "out_proj.weight"].T)
        else:
            w.update({k: sd[p + f"self_attn.{k[1]}_proj.weight"].T
                      for k in ("wq", "wk", "wv", "wo")})
        return {k: jnp.asarray(v) for k, v in w.items()}


@pytest.mark.parametrize("seed,shape", [(0, (2, 21)), (1, (1, 40))])
def test_the_granite_hybrid_reference_is_the_published_model(seed, shape):
    """One published period of ten at toy widths (the ``granite_hybrid``
    ``tiny`` preset's sizes), the four multipliers at their published
    values, random weights with non-trivial ``A_log``, ``dt_bias``, ``D``,
    conv bias and norm scales, every matrix four times the initializer's
    0.02 so that the mixers weigh beside the x12 embedding: logits of ~0.7
    to 1e-4 (measured: 1.2e-7, both float32; ``transformers``' naive torch
    path runs the CHUNKED form, the reference the sequential one)."""
    import sys
    bench = os.path.join(os.path.dirname(__file__), "..", "..", "perfbench")
    sys.path.insert(0, os.path.abspath(bench))
    from reference import granite_hybrid_decoder as ref
    import correctness

    toy = "rehearsal-granite-hybrid-tiny"
    with open(os.path.join(bench, "configs", toy + ".json")) as f:
        cfg = correctness.reference_config(json.load(f), correctness.load_map(toy))
    hc = transformers.GraniteMoeHybridConfig(
        vocab_size=512, hidden_size=cfg["d_model"], intermediate_size=cfg["d_ff"],
        shared_intermediate_size=cfg["d_ff"], num_hidden_layers=cfg["n_layer"],
        num_attention_heads=cfg["n_head"], num_key_value_heads=cfg["n_kv_head"],
        layer_types=cfg["layer_types"], num_local_experts=0,
        num_experts_per_tok=0, mamba_n_heads=cfg["ssm_heads"],
        mamba_d_head=cfg["ssm_head_dim"], mamba_d_state=cfg["ssm_state"],
        mamba_n_groups=cfg["ssm_groups"], mamba_d_conv=cfg["conv_kernel"],
        mamba_expand=2, mamba_chunk_size=cfg["ssm_chunk"],
        position_embedding_type="nope", rms_norm_eps=cfg["eps"],
        embedding_multiplier=cfg["embedding_multiplier"],
        attention_multiplier=cfg["attention_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        logits_scaling=cfg["logits_scaling"], tie_word_embeddings=True)
    torch.manual_seed(seed)
    hf_model = transformers.GraniteMoeHybridForCausalLM(hc).eval()
    assert sum(p.numel() for p in hf_model.parameters()) == 542_540
    with torch.no_grad():
        for name, p in hf_model.named_parameters():
            if name.endswith(("A_log", "dt_bias", ".D", "conv1d.bias",
                              "norm.weight", "layernorm.weight")):
                p.add_(0.3 * torch.randn_like(p))
            else:
                p.mul_(4.0)
    tok = torch.randint(0, 512, shape)
    with torch.no_grad():
        want = hf_model(input_ids=tok).logits.numpy()
    w = _HFGraniteWeights(hf_model, cfg["d_ff"])
    h = ref.final_hidden(cfg, w, jnp.asarray(tok.numpy().astype(np.int32)))
    got = np.asarray(ref.logits_rows(cfg, w, h.reshape(-1, h.shape[-1])))
    assert np.abs(want).max() > 0.3
    np.testing.assert_allclose(got.reshape(want.shape), want, rtol=0, atol=1e-4)


# --------------------------------------------------------------------- #
# The benchmark's plain reference of the LongCat-Flash layer against the
# published code (``LongcatFlashForCausalLM``: ``LongcatFlashMLA``,
# ``LongcatFlashTopkRouter``, ``LongcatFlashMoE``, ``LongcatFlashDecoderLayer``).
# Here for the same reason as Granite's above; what the PROGRAM is held to
# that reference by is in ``test_longcat_flash.py``.

class _HFLongcatWeights:
    """``LongcatFlashForCausalLM``'s state dict under the reference's names
    (``reference/maps/longcat-flash-omni.json`` says what each is in the
    program's tree): torch keeps ``[out, in]``; the experts are a module
    list, stacked here."""

    def __init__(self, hf_model, n_experts):
        self.sd = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()}
        self.n_experts = n_experts

    def top(self):
        return {"wte": jnp.asarray(self.sd["model.embed_tokens.weight"]),
                "head": jnp.asarray(self.sd["lm_head.weight"].T),
                "lnf_g": jnp.asarray(self.sd["model.norm.weight"])}

    def layer(self, l):
        sd, p = self.sd, f"model.layers.{l}."

        def sub(i):
            a, m = p + f"self_attn.{i}.", p + f"mlps.{i}."
            return {"ln1_g": sd[p + f"input_layernorm.{i}.weight"],
                    "wq_a": sd[a + "q_a_proj.weight"].T,
                    "q_g": sd[a + "q_a_layernorm.weight"],
                    "wq_b": sd[a + "q_b_proj.weight"].T,
                    "wkv_a": sd[a + "kv_a_proj_with_mqa.weight"].T,
                    "kv_g": sd[a + "kv_a_layernorm.weight"],
                    "wkv_b": sd[a + "kv_b_proj.weight"].T,
                    "wo": sd[a + "o_proj.weight"].T,
                    "ln2_g": sd[p + f"post_attention_layernorm.{i}.weight"],
                    "w_gate": sd[m + "gate_proj.weight"].T,
                    "w_up": sd[m + "up_proj.weight"].T,
                    "w_down": sd[m + "down_proj.weight"].T}

        stack = lambda name: np.stack([  # noqa: E731
            sd[p + f"mlp.experts.{e}.{name}.weight"].T
            for e in range(self.n_experts)])
        moe = {"router": sd[p + "mlp.router.classifier.weight"].T,
               "b_select": sd[p + "mlp.router.e_score_correction_bias"],
               "e_gate": stack("gate_proj"), "e_up": stack("up_proj"),
               "e_down": stack("down_proj")}
        as_jnp = lambda d: {k: jnp.asarray(v) for k, v in d.items()}  # noqa: E731
        return {"sub": [as_jnp(sub(0)), as_jnp(sub(1))], "moe": as_jnp(moe)}


@pytest.mark.parametrize("seed,shape", [(0, (2, 21)), (1, (1, 40))])
def test_the_longcat_flash_reference_is_the_published_model(seed, shape):
    """Two published layers at toy widths (the ``longcat_flash`` ``tiny``
    preset's sizes) with ALL 8 experts held (the published code has no
    shares), 4 zero-compute experts, top-3, factor 6, both lora scales, a
    random selection bias, norm scales off 1, every matrix 4 times the
    initializer's 0.02 and the router 40 times (logits of deviation ~1:
    a router that chooses): logits of ~1 agree to 1e-4, both float32."""
    import sys
    bench = os.path.join(os.path.dirname(__file__), "..", "..", "perfbench")
    sys.path.insert(0, os.path.abspath(bench))
    from reference import longcat_flash_decoder as ref
    import correctness

    toy = "rehearsal-longcat-flash-tiny"
    with open(os.path.join(bench, "configs", toy + ".json")) as f:
        cfg = correctness.reference_config(json.load(f), correctness.load_map(toy))
    cfg["experts_held"] = cfg["n_experts"]
    hc = transformers.LongcatFlashConfig(
        vocab_size=512, hidden_size=cfg["d_model"], num_layers=cfg["n_layer"],
        num_hidden_layers=2 * cfg["n_layer"],
        num_attention_heads=cfg["n_head"], ffn_hidden_size=cfg["d_ff"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        head_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        moe_topk=cfg["experts_per_token"], n_routed_experts=cfg["n_experts"],
        zero_expert_num=cfg["zero_experts"],
        expert_ffn_hidden_size=cfg["d_expert"],
        routed_scaling_factor=cfg["routed_scaling"],
        rms_norm_eps=cfg["eps"], rope_theta=cfg["rope_theta"],
        max_position_embeddings=1024, attention_bias=False,
        tie_word_embeddings=False)
    hc._attn_implementation = "eager"
    torch.manual_seed(seed)
    hf_model = transformers.LongcatFlashForCausalLM(hc).eval()
    with torch.no_grad():
        for name, p in hf_model.named_parameters():
            if name.endswith("norm.weight") or "layernorm" in name:
                p.add_(0.3 * torch.randn_like(p))
            elif "router.classifier" in name:
                p.mul_(40.0)
            else:
                p.mul_(4.0)
        for layer in hf_model.model.layers:
            layer.mlp.router.e_score_correction_bias.normal_(0.0, 0.05)
    tok = torch.randint(0, 512, shape)
    with torch.no_grad():
        want = hf_model(input_ids=tok).logits.numpy()
    w = _HFLongcatWeights(hf_model, cfg["n_experts"])
    h = ref.final_hidden(cfg, w, jnp.asarray(tok.numpy().astype(np.int32)))
    got = np.asarray(ref.logits_rows(cfg, w, h.reshape(-1, h.shape[-1])))
    assert np.abs(want).max() > 0.3
    np.testing.assert_allclose(got.reshape(want.shape), want, rtol=0, atol=1e-4)
