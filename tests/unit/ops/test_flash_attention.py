"""Pallas flash attention vs the XLA einsum reference (interpret mode on CPU).

Analogue of the reference's kernel-vs-torch comparisons in
tests/unit/ops/transformer/.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import dispatch
from deepspeed_tpu.ops.attention import mha_attention
from deepspeed_tpu.ops.pallas import flash_attention

# the module (the package's attribute of that name is the function)
flash_module = sys.modules["deepspeed_tpu.ops.pallas.flash_attention"]


def _qkv(key, B=1, S=256, H=2, Hd=64):
    kq, kk, kv = jax.random.split(key, 3)
    shape = (B, S, H, Hd)
    return (jax.random.normal(kq, shape, jnp.float32),
            jax.random.normal(kk, shape, jnp.float32),
            jax.random.normal(kv, shape, jnp.float32))


class TestFlashForward:

    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, causal):
        q, k, v = _qkv(jax.random.key(0))
        ref = mha_attention(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_unaligned_seq_pads(self):
        q, k, v = _qkv(jax.random.key(1), S=200)
        ref = mha_attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_mask(self):
        q, k, v = _qkv(jax.random.key(2))
        keep = jax.random.uniform(jax.random.key(3), (1, 256)) > 0.3
        keep = keep.at[:, 0].set(True)  # row 0 must see key 0 (else degenerate)
        bias = jnp.where(keep, 0.0, -1e9).astype(jnp.float32)
        ref = mha_attention(q, k, v, mask_bias=bias[:, None, None, :], causal=True)
        out = flash_attention(q, k, v, mask_bias=bias, causal=True, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_alibi(self):
        q, k, v = _qkv(jax.random.key(4))
        slopes = jnp.asarray([0.5, 0.0625], jnp.float32)
        ref = mha_attention(q, k, v, causal=True, alibi_slopes=slopes)
        out = flash_attention(q, k, v, causal=True, alibi_slopes=slopes, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_bf16(self):
        q, k, v = _qkv(jax.random.key(5))
        q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
        ref = mha_attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, interpret=True)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                                   rtol=3e-2, atol=3e-2)


class TestFlashBackward:

    @pytest.mark.parametrize("causal", [True, False])
    def test_grads_match_dense(self, causal):
        q, k, v = _qkv(jax.random.key(6), S=128)

        def loss_ref(q, k, v):
            return jnp.sum(mha_attention(q, k, v, causal=causal) ** 2)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=causal, interpret=True) ** 2)

        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4,
                                       err_msg=f"d{name} mismatch")

    def test_grads_with_mask_alibi(self):
        q, k, v = _qkv(jax.random.key(7), S=128)
        keep = jax.random.uniform(jax.random.key(8), (1, 128)) > 0.25
        keep = keep.at[:, 0].set(True)  # row 0 must see key 0 (else degenerate)
        bias = jnp.where(keep, 0.0, -1e9).astype(jnp.float32)
        slopes = jnp.asarray([0.25, 0.125], jnp.float32)

        def loss_ref(q, k, v):
            out = mha_attention(q, k, v, mask_bias=bias[:, None, None, :], causal=True,
                                alibi_slopes=slopes)
            return jnp.sum(out ** 2)

        def loss_flash(q, k, v):
            out = flash_attention(q, k, v, mask_bias=bias, causal=True, alibi_slopes=slopes,
                                  interpret=True)
            return jnp.sum(out ** 2)

        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4,
                                       err_msg=f"d{name} mismatch")

    def test_grads_unaligned_seq(self):
        q, k, v = _qkv(jax.random.key(9), S=100, H=1)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True, interpret=True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(mha_attention(q, k, v, causal=True) ** 2)

        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


def _dense(q, k, v, keep, bias=None):
    """Softmax attention in which query i sees key j where ``keep[..., i, j]``
    ([S, S] or broadcastable to [B, H, S, S]), scores plus ``bias``: the
    output [B, S, H, Hd] and the LOG2-domain logsumexp [B, H, S]."""
    G = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    if bias is not None:
        s = s + bias
    s = jnp.where(keep, s, -1e30)
    lse = jax.nn.logsumexp(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(s - lse[..., None]), v)
    return out, lse * np.log2(np.e)


def _walk_case(path, bq):
    """What ``path`` adds to a causal call at two blocks of ``bq`` a side:
    (heads, kv heads, S, the flash kwargs, the dense reference's keep and
    bias)."""
    S = 2 * bq - 56 if path == "padded" else 2 * bq
    i, j = np.arange(S)[:, None], np.arange(S)[None, :]
    keep, bias, kw, H, KV = i >= j, None, {}, 2, 2
    if path == "gqa4":
        H, KV = 4, 1
    elif path == "alibi_mask":
        seen = jax.random.uniform(jax.random.key(8), (1, S)) > 0.25
        mask = jnp.where(seen.at[:, 0].set(True), 0.0, -1e9).astype(jnp.float32)
        slopes = jnp.asarray([0.25, 0.125], jnp.float32)
        kw = dict(mask_bias=mask, alibi_slopes=slopes)
        bias = slopes[None, :, None, None] * (j - i) + mask[:, None, None, :]
    elif path == "stair4":
        keep, kw = i // 4 >= j // 4, dict(causal_block=4)
    elif path == "layout":
        # both diagonal blocks kept, the block below the diagonal dropped
        keep = keep & (i // bq == j // bq)
        kw = dict(block_layout=jnp.eye(2, dtype=jnp.float32))
    elif path == "lse":
        kw = dict(return_lse=True)
    if "block_layout" not in kw:
        kw.update(block_q=bq, block_k=bq)
    return H, KV, S, kw, jnp.asarray(keep), bias


class TestDiagonalWalk:
    """The backward kernels walk a diagonal block in chunks (PR 46): the
    gradients with the walk ENGAGED on every path, held to the dense
    reference; the chunk is patched small so the interpreter stays fast."""

    # chunks a block -> (block, chunk): a block spanning part of a sequence
    # is a multiple of 128
    BLOCKS = {2: (128, 64), 3: (384, 128), 4: (128, 32)}

    @pytest.mark.parametrize("chunks", [2, 3, 4])
    @pytest.mark.parametrize("path", ["packed", "gqa4", "alibi_mask", "padded",
                                      "stair4", "layout", "lse"])
    def test_grads_match_dense(self, monkeypatch, path, chunks):
        bq, c = self.BLOCKS[chunks]
        monkeypatch.setattr(flash_module, "_DIAG_CHUNK", c)
        H, KV, S, kw, keep, bias = _walk_case(path, bq)
        kq, kk, kv, kw_ = jax.random.split(jax.random.key(40 + chunks), 4)
        q = jax.random.normal(kq, (1, S, H, 64), jnp.float32)
        k = jax.random.normal(kk, (1, S, KV, 64), jnp.float32)
        v = jax.random.normal(kv, (1, S, KV, 64), jnp.float32)
        # a cotangent on lse too where the call returns one (ring attention)
        w = jax.random.normal(kw_, (1, H, S), jnp.float32)

        def loss(out, lse):
            return jnp.sum(out ** 2) + jnp.sum(w * lse)

        def loss_ref(q, k, v):
            out, lse = _dense(q, k, v, keep, bias)
            return loss(out, lse if path == "lse" else 0.0)

        def loss_flash(q, k, v):
            got = flash_attention(q, k, v, causal=True, interpret=True, **kw)
            return loss(*got) if path == "lse" else loss(got, 0.0)

        dispatch.reset()
        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        assert dispatch.selected().get("flash_bwd_diag=chunks") == 1
        assert "flash_bwd_diag=whole" not in dispatch.selected()
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gr, "qkv"):
            assert a.shape == b.shape
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4,
                                       err_msg=f"d{name} mismatch ({path}, {chunks} chunks)")

    @pytest.mark.parametrize("why,causal,c", [("non_causal", False, 64),
                                              ("one_chunk", True, 128),
                                              ("ragged_chunk", True, 96)])
    def test_takes_blocks_whole(self, monkeypatch, why, causal, c):
        """No walk where it cannot be one: a non-causal call, a block of one
        chunk, a block that is no whole number of chunks."""
        monkeypatch.setattr(flash_module, "_DIAG_CHUNK", c)
        q, k, v = _qkv(jax.random.key(50), S=256)

        def loss_ref(q, k, v):
            return jnp.sum(mha_attention(q, k, v, causal=causal) ** 2)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=causal, interpret=True,
                                           block_q=128, block_k=128) ** 2)

        dispatch.reset()
        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        assert dispatch.selected().get("flash_bwd_diag=whole") == 1
        assert "flash_bwd_diag=chunks" not in dispatch.selected()
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


class TestModelFlashBackend:

    @pytest.mark.slow
    def test_causal_lm_flash_matches_xla(self):
        """attention_backend='flash' (interpret on CPU) == 'xla' loss + grads."""
        from deepspeed_tpu.models import CausalLM
        from deepspeed_tpu.models.transformer import TransformerConfig

        base = dict(vocab_size=64, n_layer=1, n_head=2, d_model=32, d_ff=64,
                    max_seq=32, pos_embedding="rope", norm="rmsnorm",
                    activation="swiglu", remat=False)
        xla = CausalLM(TransformerConfig(**base, attention_backend="xla"))
        flash = CausalLM(TransformerConfig(**base, attention_backend="flash"))
        params = xla.init_params(jax.random.key(0))
        batch = {"input_ids": jax.random.randint(jax.random.key(1), (2, 32), 0, 64)}

        lr, gr = jax.value_and_grad(xla.loss)(params, batch)
        lf, gf = jax.value_and_grad(flash.loss)(params, batch)
        np.testing.assert_allclose(float(lf), float(lr), rtol=1e-5)
        flat_r = jax.tree.leaves(gr)
        flat_f = jax.tree.leaves(gf)
        for a, b in zip(flat_f, flat_r):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


class TestShardedFlash:
    """shard_map-wrapped flash attention on multi-device meshes (the
    single-chip kernel silently fell back to einsum on >1-device meshes
    before; these prove the Pallas path runs and matches)."""

    @pytest.mark.slow
    def test_flash_runs_under_dp_tp_mesh(self, monkeypatch):
        """attention_backend='flash' on a dp×tp mesh must use the Pallas
        kernel (einsum fallback is an error) and match the single-device
        reference loss + grads."""
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        import deepspeed_tpu.comm as dist
        from deepspeed_tpu.models import CausalLM
        from deepspeed_tpu.models.transformer import TransformerConfig
        import deepspeed_tpu.ops.attention as xla_attn

        base = dict(vocab_size=64, n_layer=2, n_head=4, d_model=32, d_ff=64,
                    max_seq=32, pos_embedding="rope", norm="rmsnorm",
                    activation="swiglu", remat=False)
        model = CausalLM(TransformerConfig(**base, attention_backend="flash"))
        ref = CausalLM(TransformerConfig(**base, attention_backend="xla"))
        params = model.init_params(jax.random.key(0))
        batch = {"input_ids": jax.random.randint(jax.random.key(1), (4, 32), 0, 64)}

        lr, gr = jax.value_and_grad(ref.loss)(params, batch)

        devs = np.array(jax.devices()[:4]).reshape(2, 2)
        mesh = Mesh(devs, ("dp", "tp"))
        dist.set_mesh(mesh)
        try:
            def boom(*a, **k):
                raise AssertionError("einsum attention fallback used on dp×tp mesh")
            monkeypatch.setattr(xla_attn, "mha_attention", boom)

            tp = model.tp_specs()
            shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), tp,
                                     is_leaf=lambda x: isinstance(x, P))
            sp = jax.device_put(params, shardings)
            db = {"input_ids": jax.device_put(batch["input_ids"], NamedSharding(mesh, P("dp", None)))}
            lf, gf = jax.jit(jax.value_and_grad(model.loss))(sp, db)
            np.testing.assert_allclose(float(lf), float(lr), rtol=2e-5)
            for a, b in zip(jax.tree.leaves(gf), jax.tree.leaves(gr)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)
        finally:
            dist.set_mesh(None)

    def test_flash_sharded_skips_pipeline_meshes(self):
        """Meshes with pp/ep/sp axes >1 must not take the shard_map path."""
        import numpy as np
        from jax.sharding import Mesh
        import deepspeed_tpu.comm as dist
        from deepspeed_tpu.models.transformer import TransformerConfig, _flash_mesh

        cfg = TransformerConfig(attention_backend="flash")
        devs = np.array(jax.devices()[:4]).reshape(2, 2)
        dist.set_mesh(Mesh(devs, ("pp", "dp")))
        try:
            assert _flash_mesh(cfg) is None
        finally:
            dist.set_mesh(None)
        dist.set_mesh(Mesh(devs, ("dp", "tp")))
        try:
            assert _flash_mesh(cfg) is not None
        finally:
            dist.set_mesh(None)


class TestGQAFlash:
    """GQA-native kernel: kv enters with KV < H heads (no jnp.repeat); the
    BlockSpec index map does the group lookup and dk/dv are group-summed
    in-kernel. Parity vs the einsum reference with explicitly repeated kv."""

    @pytest.mark.parametrize("ratio", [1, 4, 8])
    @pytest.mark.parametrize("causal", [True, False])
    def test_forward_matches_repeated(self, ratio, causal):
        H, KV = 8, 8 // ratio
        key = jax.random.key(10 + ratio)
        kq, kk, kv_ = jax.random.split(key, 3)
        q = jax.random.normal(kq, (2, 128, H, 64), jnp.float32)
        k = jax.random.normal(kk, (2, 128, KV, 64), jnp.float32)
        v = jax.random.normal(kv_, (2, 128, KV, 64), jnp.float32)
        kr = jnp.repeat(k, ratio, axis=2)
        vr = jnp.repeat(v, ratio, axis=2)
        ref = mha_attention(q, kr, vr, causal=causal)
        out = flash_attention(q, k, v, causal=causal, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("ratio", [4, 8])
    def test_grads_match_repeated(self, ratio):
        H, KV = 8, 8 // ratio
        key = jax.random.key(20 + ratio)
        kq, kk, kv_ = jax.random.split(key, 3)
        q = jax.random.normal(kq, (1, 128, H, 64), jnp.float32)
        k = jax.random.normal(kk, (1, 128, KV, 64), jnp.float32)
        v = jax.random.normal(kv_, (1, 128, KV, 64), jnp.float32)

        def loss_ref(q, k, v):
            kr = jnp.repeat(k, ratio, axis=2)
            vr = jnp.repeat(v, ratio, axis=2)
            return jnp.sum(mha_attention(q, kr, vr, causal=True) ** 2)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True, interpret=True) ** 2)

        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gr, "qkv"):
            assert a.shape == b.shape
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4,
                                       err_msg=f"d{name} mismatch (ratio {ratio})")

    def test_gqa_mask_alibi(self):
        H, KV = 4, 2
        key = jax.random.key(31)
        kq, kk, kv_ = jax.random.split(key, 3)
        q = jax.random.normal(kq, (2, 128, H, 64), jnp.float32)
        k = jax.random.normal(kk, (2, 128, KV, 64), jnp.float32)
        v = jax.random.normal(kv_, (2, 128, KV, 64), jnp.float32)
        keep = jax.random.uniform(jax.random.key(32), (2, 128)) > 0.25
        keep = keep.at[:, 0].set(True)
        bias = jnp.where(keep, 0.0, -1e9).astype(jnp.float32)
        slopes = jnp.asarray([0.5, 0.25, 0.125, 0.0625], jnp.float32)
        kr, vr = jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2)
        ref = mha_attention(q, kr, vr, mask_bias=bias[:, None, None, :],
                            causal=True, alibi_slopes=slopes)
        out = flash_attention(q, k, v, mask_bias=bias, causal=True,
                              alibi_slopes=slopes, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_model_gqa_no_repeat_into_kernel(self, monkeypatch):
        """A GQA CausalLM with attention_backend='flash' must hand the kernel
        KV-head k/v (not repeated) and still match the xla backend."""
        from deepspeed_tpu.models import CausalLM
        from deepspeed_tpu.models.transformer import TransformerConfig
        import deepspeed_tpu.ops.pallas as pallas_pkg

        seen = {}
        orig = pallas_pkg.flash_attention

        def spy(q, k, v, **kw):
            seen["kv_heads"] = k.shape[2]
            seen["q_heads"] = q.shape[2]
            return orig(q, k, v, **kw)

        # the model imports flash_attention inside the function body from
        # deepspeed_tpu.ops.pallas — patch it there
        monkeypatch.setattr(pallas_pkg, "flash_attention", spy)

        base = dict(vocab_size=64, n_layer=1, n_head=4, n_kv_head=2,
                    d_model=64, d_ff=128, max_seq=32, pos_embedding="rope",
                    norm="rmsnorm", activation="swiglu", remat=False)
        model = CausalLM(TransformerConfig(**base, attention_backend="flash"))
        ref = CausalLM(TransformerConfig(**base, attention_backend="xla"))
        params = model.init_params(jax.random.key(0))
        batch = {"input_ids": jax.random.randint(jax.random.key(1), (2, 32), 0, 64)}
        lf = model.loss(params, batch)
        lr = ref.loss(params, batch)
        assert seen == {"kv_heads": 2, "q_heads": 4}, seen
        np.testing.assert_allclose(float(lf), float(lr), rtol=2e-5)
