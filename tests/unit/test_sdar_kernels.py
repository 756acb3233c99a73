"""The two kernels a model that generates by blocks shares with the others,
under its shapes, in interpret mode on the CPU: ``flash_attention`` with the
causal triangle a STAIRCASE of the generation block (the prefill of whole
blocks) against its XLA twin ``mha_attention``, and the block step's
attention, a row's B positions on ``paged_decode_attention``'s position axis
(B x H / KV query rows a kv head) in one read of the row's KV, against the
gather + einsum form on random block tables. The compiled forms are the ``-m tpu``
tier's (``test_tpu_kernels.py``) and ``test_paged_pool_threading.py``'s.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu.comm as dist
from deepspeed_tpu.models.presets import get_model
from deepspeed_tpu.ops import dispatch
from deepspeed_tpu.ops.attention import mha_attention
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention


@pytest.fixture(autouse=True)
def _clean_mesh():
    dist.set_mesh(None)
    yield
    dist.set_mesh(None)


def _qkv(S, H, KV, Hd, seed=0):
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(kq, (2, S, H, Hd), jnp.float32),
            jax.random.normal(kk, (2, S, KV, Hd), jnp.float32),
            jax.random.normal(kv, (2, S, KV, Hd), jnp.float32))


# S 200: not a multiple of the tile (padded: the computed bias); 256: the
# plain path's precomputed diagonal block; blocks of 128 under S 384: three
# diagonal tiles and the visible ones under them. Hd 64 MHA packs heads.
@pytest.mark.parametrize("stair", [4, 8])
@pytest.mark.parametrize("S,H,KV,Hd,blocks", [
    (200, 4, 2, 128, None),
    (256, 4, 2, 128, None),
    (384, 4, 2, 128, 128),
    (256, 4, 4, 64, None),
    (384, 2, 2, 64, 128),
])
def test_flash_staircase_is_the_xla_twins(S, H, KV, Hd, blocks, stair):
    q, k, v = _qkv(S, H, KV, Hd)
    got = flash_attention(q, k, v, causal=True, causal_block=stair,
                          block_q=blocks, block_k=blocks, interpret=True)
    want = mha_attention(q, k, v, causal=True, causal_block=stair)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    # and it is not the triangle: inside a group a query sees what follows
    plain = mha_attention(q, k, v, causal=True)
    assert float(jnp.abs(want - plain).max()) > 1e-2


def test_the_twin_is_the_mask_it_says():
    """Position i sees j iff j // 4 <= i // 4, by hand."""
    S = 12
    q, k, v = _qkv(S, 2, 2, 8, seed=3)
    got = mha_attention(q, k, v, causal=True, causal_block=4)
    i, j = np.arange(S)[:, None], np.arange(S)[None, :]
    s = np.einsum("bihd,bjhd->bhij", q, k) / np.sqrt(8.0)
    s = np.where(j // 4 <= i // 4, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhij,bjhd->bihd", p / p.sum(-1, keepdims=True), v)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)


@pytest.mark.parametrize("S,Hd,KV", [(256, 128, 2), (200, 128, 2), (256, 64, 4)])
def test_flash_staircase_backward_honours_the_step(S, Hd, KV):
    """No training of such a model is built, but the kernels' backward takes
    the same bias: gradients are the twin's."""
    q, k, v = _qkv(S, 4, KV, Hd, seed=1)

    def loss(fn, **kw):
        return jax.grad(lambda q, k, v: jnp.sum(jnp.sin(fn(
            q, k, v, causal=True, causal_block=4, **kw))), argnums=(0, 1, 2))

    got = loss(flash_attention, interpret=True)(q, k, v)
    want = loss(mha_attention)(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5)


@pytest.mark.parametrize("kw", [dict(causal_block=3), dict(causal_block=16),
                                dict(causal_block=4, causal=False)])
def test_flash_staircase_refuses_what_it_cannot_tile(kw):
    q, k, v = _qkv(64, 2, 2, 64)
    with pytest.raises(ValueError, match="causal_block"):
        flash_attention(q, k, v, interpret=True, **{"causal": True, **kw})


# --------------------------------------------------------------------- #

BS = 128


def _block_step(backend, seed=5, rows=5, blocks=24):
    """One pass of block generation of the toy at a width the paged kernel
    takes (kv heads x 64 = 128 lanes), on random tables over a pool of
    random committed KV, rows at random depths (one idle)."""
    model = get_model("sdar", "tiny", head_size=64, attention_backend=backend,
                      init_std=0.3, embed_init_std=1.0)
    params = model.init_params(jax.random.key(1))
    r = np.random.default_rng(seed)
    pools = jax.tree.map(
        lambda a: jnp.asarray(r.standard_normal(a.shape), a.dtype),
        model.init_paged_cache(blocks, BS, dtype=jnp.float32))
    n_max = 4
    pos = (r.integers(0, (n_max * BS - 4) // 4, rows) * 4).astype(np.int32)
    pos[-1] = 0
    ids = iter(r.permutation(np.arange(1, blocks)))
    bt = np.zeros((rows, n_max), np.int32)
    for i in range(rows - 1):                     # the last row idles
        for j in range(pos[i] // BS + 1):
            bt[i, j] = next(ids)
    tokens = r.integers(0, 512, (rows, 4)).astype(np.int32)
    dispatch.reset()
    logits, new, counts = jax.jit(model.forward_paged_block)(
        params, jnp.asarray(tokens), pools, jnp.asarray(bt), jnp.asarray(pos))
    return logits, new, counts, dispatch.selected()


def test_block_attention_rides_the_paged_kernel():
    """4 positions x 2 heads a kv head on the position axis of ONE kernel
    call a layer (8 query rows a kv head over HALF a lane tile at head size
    64, so against the block-diagonal query): the live rows' logits, the
    pools past the dummy block and the experts' counts of the gather +
    einsum form (the idle row's attention is zeros from the kernel and the
    dummy block's first value from the gather; what it writes goes to the
    dummy block and nothing reads it)."""
    got, pools_k, counts_k, forms = _block_step("flash")
    assert forms.get("paged_block=paged_kernel") == 1, forms
    assert forms.get("paged_decode_attention=block_diagonal") == 1, forms
    assert "kernel/paged_decode_attention=interpret" in forms
    want, pools_x, counts_x, forms_x = _block_step("xla")
    assert forms_x.get("paged_block=gather_einsum") == 1, forms_x
    np.testing.assert_allclose(np.asarray(got[:-1]), np.asarray(want[:-1]),
                               atol=2e-5)
    for a, b in zip(jax.tree.leaves(pools_k), jax.tree.leaves(pools_x)):
        np.testing.assert_allclose(np.asarray(a[:, 1:]), np.asarray(b[:, 1:]),
                                   atol=1e-5)
    np.testing.assert_array_equal(np.asarray(counts_k), np.asarray(counts_x))
    # nothing dropped, and the idle row's 4 positions reach no expert: at
    # most 4 live rows x 4 positions x top-2 choices are owed a layer
    assert int(counts_k[0, :-1].sum()) == int(counts_k[0, -1]) <= 4 * 4 * 2
