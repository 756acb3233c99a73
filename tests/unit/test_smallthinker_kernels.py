"""The two kernels a stack with WINDOW layers shares with the others, under
a window, in interpret mode on the CPU: ``paged_decode_attention(window=W)``
(a row's loop from its first live block, over a RING table) against its XLA
twin, the ring gather + masked einsum of ``models/transformer.py``, on
random tables and depths; and ``flash_attention(window=W)`` (the banded
forward) against the masked einsum ``mha_attention(window=W)`` at S
512-2,048. The compiled forms are the chip's (``perfbench``'s cell runs
them; ``perfbench/tools/aot_size_state.py smallthinker 21b-a3b-12l``
compiles them without one, and so does ``test_paged_pool_threading.py``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu.comm as dist
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.ops.attention import mha_attention
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
from deepspeed_tpu.ops.pallas.paged_decode_attention import \
    paged_decode_attention


@pytest.fixture(autouse=True)
def _clean_mesh():
    dist.set_mesh(None)
    yield
    dist.set_mesh(None)


BS = 128
CFG = T.TransformerConfig(n_head=8, n_kv_head=2, head_size=64, d_model=64,
                          pos_embedding="none")


def _ring_case(window, pos, seed=0, slots=None):
    """Rows at depths ``pos`` (0 with slot 0: idle), each in a ring of
    ``window // BS + 1`` blocks of a pool of random KV."""
    r = np.random.default_rng(seed)
    B, R = len(pos), -(-window // BS) + 1
    H, KV, Hd = CFG.n_head, CFG.kv_heads, CFG.head_dim
    slots = np.asarray(slots if slots is not None else
                       r.permutation(np.arange(1, B + 1)), np.int32)
    tables = np.asarray(T.ring_tables(slots, R))
    pool = lambda: jnp.asarray(  # noqa: E731
        r.standard_normal((B * R + 1, BS, KV * Hd)), jnp.float32)
    q = jnp.asarray(r.standard_normal((B, H, Hd)), jnp.float32)
    return q, pool(), pool(), tables, np.asarray(pos, np.int32)


def _twin(q, kp, vp, tables, pos, window):
    """The XLA form the CPU tier serves with: ring gather, the positions the
    ring's slots hold, the band."""
    R = tables.shape[1]
    out = T._grouped_cache_einsum(
        CFG, q[:, None], T._paged_gather(kp, tables, CFG.kv_heads),
        T._paged_gather(vp, tables, CFG.kv_heads), jnp.asarray(pos)[:, None],
        None, kpos=T._ring_kpos(pos, R, BS), window=window)
    return out.reshape(q.shape)


# depths: shorter than the window; a first live block partly masked (W 256:
# pos 300 reads 45..300, block 0 from its key 45); on a block's border; the
# ring wrapped several times; an idle row
@pytest.mark.parametrize("window,pos", [
    (256, [5, 127, 128, 255, 256, 300, 0]),
    (256, [383, 384, 511, 512, 900, 1023, 2047]),
    (200, [10, 199, 200, 329, 640, 0, 777]),
    (512, [100, 511, 512, 640, 1300, 3001, 0]),
])
def test_paged_window_kernel_is_its_xla_twin(window, pos):
    slots = [i + 1 if p or i == 0 else 0 for i, p in enumerate(pos)]
    q, kp, vp, tables, pos = _ring_case(window, pos, seed=window, slots=slots)
    got = paged_decode_attention(q, kp, vp, tables, pos, window=window,
                                 interpret=True)
    want = _twin(q, kp, vp, tables, pos, window)
    live = np.asarray(slots) > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-5)


def test_the_window_bounds_what_a_row_reads():
    """Keys before the window do not move the result, keys inside do: the
    kernel against itself on a pool whose dead positions are rewritten."""
    window, pos = 256, [700]
    q, kp, vp, tables, pos = _ring_case(window, pos, seed=1)
    base = paged_decode_attention(q, kp, vp, tables, pos, window=window,
                                  interpret=True)
    # position 444 = 700 - 256 is the first one out: ring block (444 // 128)
    # % 3 = 0, offset 60; 445 is the first one in
    blk = tables[0, (444 // BS) % 3]
    dead = kp.at[blk, 444 % BS].add(100.0)
    same = paged_decode_attention(q, dead, vp, tables, pos, window=window,
                                  interpret=True)
    np.testing.assert_array_equal(np.asarray(same), np.asarray(base))
    alive = kp.at[blk, 445 % BS].add(100.0)
    moved = paged_decode_attention(q, alive, vp, tables, pos, window=window,
                                   interpret=True)
    assert float(jnp.abs(moved - base).max()) > 1e-3


def test_a_full_table_is_a_ring_too():
    """The same window over a table as long as the row (logical block j at
    entry j): what a stack would read that kept every block."""
    window, n = 256, 900
    r = np.random.default_rng(2)
    H, KV, Hd = CFG.n_head, CFG.kv_heads, CFG.head_dim
    nb = n // BS + 1
    kp, vp = (jnp.asarray(r.standard_normal((nb + 1, BS, KV * Hd)), jnp.float32)
              for _ in range(2))
    q = jnp.asarray(r.standard_normal((1, H, Hd)), jnp.float32)
    table = np.arange(1, nb + 1, dtype=np.int32)[None]
    got = paged_decode_attention(q, kp, vp, table, np.array([n], np.int32),
                                 window=window, interpret=True)
    want = T._grouped_cache_einsum(
        CFG, q[:, None], T._paged_gather(kp, table, KV),
        T._paged_gather(vp, table, KV), jnp.full((1, 1), n, jnp.int32), None,
        window=window).reshape(q.shape)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("kw,match", [
    (dict(window=256, tables=2), "table of at least"),
    (dict(window=256, pad=True), "no pad_bias"),
    (dict(window=256, block=True), "one query position"),
])
def test_the_window_kernel_refuses_what_it_does_not_do(kw, match):
    q, kp, vp, tables, pos = _ring_case(256, [300, 10])
    if kw.get("tables"):
        tables = tables[:, :kw["tables"]]
    pad = jnp.zeros((2, tables.shape[1] * BS)) if kw.get("pad") else None
    if kw.get("block"):
        q = jnp.stack([q, q], axis=1)
    with pytest.raises(ValueError, match=match):
        paged_decode_attention(q, kp, vp, tables, pos, window=kw["window"],
                               pad_bias=pad, interpret=True)


def test_ring_kpos_names_what_each_slot_holds():
    """By hand: a ring of 3 blocks of 4 after positions 0..9 holds 8, 9 in
    block 2's first slots (and what block -1 left there, nothing), 4..7 in
    block 1, 0..3 in block 0; after 0..13 block 0 holds 12, 13 and block
    1... the newest of each residue."""
    got = np.asarray(T._ring_kpos(np.array([9, 13, -1]), 3, 4))
    assert got[0].tolist() == [0, 1, 2, 3, 4, 5, 6, 7, 8, 9, -1, -1]
    assert got[1].tolist() == [12, 13, -1, -1, 4, 5, 6, 7, 8, 9, 10, 11]
    assert (got[2] < 0).all()


# --------------------------------------------------------------------- #

def _qkv(S, H, KV, Hd, seed=0):
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(kq, (1, S, H, Hd), jnp.float32),
            jax.random.normal(kk, (1, S, KV, Hd), jnp.float32),
            jax.random.normal(kv, (1, S, KV, Hd), jnp.float32))


# S 512 in one block (the computed bias: the band is no whole block); blocks
# of 256 under a band of one and of two blocks (the plain path: the
# diagonal's and the lower edge's precomputed biases, blocks below the band
# skipped and not copied); S 2,048 at the default block (1,024) with a
# window of 1,024; a window that is no whole block; a padded S
@pytest.mark.parametrize("S,window,blocks", [
    (512, 256, None),
    (1024, 256, 256),
    (1536, 512, 256),
    (2048, 1024, None),
    (1024, 300, 256),
    (700, 256, 128),
])
def test_flash_band_is_the_masked_einsum(S, window, blocks):
    q, k, v = _qkv(S, 4, 2, 128, seed=S)
    got = flash_attention(q, k, v, causal=True, window=window, block_q=blocks,
                          block_k=blocks, interpret=True)
    want = mha_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    # and it is not the triangle
    plain = mha_attention(q, k, v, causal=True)
    assert float(jnp.abs(want - plain).max()) > 1e-2


def test_the_twin_is_the_mask_it_says():
    """Position i sees i - 5 < j <= i, by hand."""
    S, W = 16, 5
    q, k, v = _qkv(S, 2, 2, 8, seed=3)
    got = mha_attention(q, k, v, causal=True, window=W)
    i, j = np.arange(S)[:, None], np.arange(S)[None, :]
    s = np.einsum("bihd,bjhd->bhij", q, k) / np.sqrt(8.0)
    s = np.where((j <= i) & (j > i - W), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhij,bjhd->bihd", p / p.sum(-1, keepdims=True), v)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)


def test_flash_band_has_no_backward_and_says_so():
    q, k, v = _qkv(256, 2, 2, 128)
    with pytest.raises(NotImplementedError, match="no backward"):
        jax.grad(lambda q: flash_attention(
            q, k, v, causal=True, window=128, interpret=True).sum())(q)


@pytest.mark.parametrize("kw", [dict(causal=False), dict(causal_block=4)])
def test_flash_band_refuses_other_masks(kw):
    q, k, v = _qkv(64, 2, 2, 64)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, window=32, interpret=True,
                        **{"causal": True, **kw})
