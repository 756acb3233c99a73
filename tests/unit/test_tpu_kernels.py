"""TPU kernel validation.

Two tiers live here:

* ``tpu``-marked tests (opt in: ``DS_TPU_TESTS=1 pytest -m tpu``) compile
  the kernels on REAL hardware — Mosaic lowering itself is what that tier
  covers (the env var stops the conftest from forcing the CPU platform).
  The tier only runs when asked for, so a missing chip FAILS it: a dead
  TPU must not read as a row of skips and a green run.
* The ``TestFusedCrossEntropy`` class runs in the DEFAULT CPU tier via
  ``interpret=True`` — the fused logits-free CE kernel's numerics
  (forward/backward parity vs the XLA logsumexp reference, ragged tiles,
  masked labels, custom_vjp under jit) are hardware-independent.
"""

import numpy as np
import pytest

tpu_tier = pytest.mark.tpu


@pytest.fixture(scope="module")
def tpu():
    import jax

    from deepspeed_tpu.accelerator import require_tpu
    require_tpu()   # raises (test ERROR, not skip) when jax is not on a TPU
    return jax.devices()[0]


@tpu_tier
def test_flash_attention_compiles_and_matches(tpu):
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.attention import mha_attention
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 512, 8, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(2, 512, 8, 64)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(2, 512, 8, 64)), jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, interpret=False)
    ref = mha_attention(q, k, v, causal=True)
    err = float(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)).max())
    assert err < 0.05, err

    # backward kernels
    g = jax.grad(lambda qq: flash_attention(qq, k, v, causal=True,
                                            interpret=False).astype(jnp.float32).sum())(q)
    gr = jax.grad(lambda qq: mha_attention(qq, k, v, causal=True)
                  .astype(jnp.float32).sum())(q)
    gerr = float(jnp.abs(g.astype(jnp.float32) - gr.astype(jnp.float32)).max())
    assert gerr < 0.1, gerr


@tpu_tier
def test_decode_attention_compiles_and_matches(tpu):
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas.decode_attention import decode_attention

    rng = np.random.default_rng(1)
    B, H, KV, Hd, Smax, pos = 2, 8, 2, 64, 512, 200
    q = jnp.asarray(rng.normal(size=(B, H, Hd)), jnp.bfloat16)
    ck = jnp.asarray(rng.normal(size=(B, Smax, KV, Hd)), jnp.bfloat16)
    cv = jnp.asarray(rng.normal(size=(B, Smax, KV, Hd)), jnp.bfloat16)
    out = decode_attention(q, ck, cv, pos, interpret=False)
    # einsum reference
    rep = H // KV
    kk = jnp.repeat(ck, rep, axis=2).astype(jnp.float32)
    vv = jnp.repeat(cv, rep, axis=2).astype(jnp.float32)
    s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32), kk) * Hd**-0.5
    s = jnp.where(jnp.arange(Smax)[None, None, :] <= pos, s, -1e30)
    import jax
    p = jax.nn.softmax(s, axis=-1)
    ref = jnp.einsum("bhs,bshd->bhd", p, vv)
    err = float(jnp.abs(out.astype(jnp.float32) - ref).max())
    assert err < 0.05, err


@tpu_tier
def test_fused_adam_kernel_compiles_and_matches(tpu):
    import jax.numpy as jnp

    from deepspeed_tpu.ops.adam.fused_adam_kernel import fused_adam_step

    rng = np.random.default_rng(2)
    n = 1_000_001
    p = jnp.asarray(rng.normal(size=n), jnp.float32)
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    m = jnp.zeros(n, jnp.float32)
    v = jnp.zeros(n, jnp.float32)
    kp, km, kv = fused_adam_step(p, g, m, v, step=1, lr=1e-3,
                                 weight_decay=0.01, interpret=False)
    # identical jnp math as the reference
    from deepspeed_tpu.ops.adam.fused_adam_kernel import _jnp_adam_flat
    ref, _, _ = _jnp_adam_flat(p, g, m, v, jnp.float32(1e-3),
                               jnp.float32(1 - 0.9), jnp.float32(1 - 0.999),
                               b1=0.9, b2=0.999, eps=1e-8, wd=0.01,
                               adam_w=True, emit="param")
    assert float(jnp.abs(kp - ref).max()) < 1e-6


@tpu_tier
def test_sr_quantizer_kernel_compiles_and_unbiased(tpu):
    import jax.numpy as jnp

    from deepspeed_tpu.ops.quantizer.kernels import ds_sr_quantize

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(8, 1024)), jnp.float32)
    outs = jnp.stack([ds_sr_quantize(x, 8, seed=s, interpret=False)
                      for s in range(32)])
    bias = float(jnp.abs(outs.mean(0) - x).max())
    step = float(jnp.abs(x).max()) / 127
    assert bias < step
    assert float(jnp.abs(outs[0] - outs[1]).max()) > 0  # seeds differ


@tpu_tier
def test_gqa_flash_compiles_matches_and_beats_repeat(tpu):
    """GQA-native kernel (kv enters with KV heads) vs repeat-then-MHA on
    hardware: parity in fwd+bwd, and the native path must not be slower —
    it moves H/KV x less kv through HBM/VMEM."""
    import time

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    B, S, H, KV, Hd = 4, 2048, 16, 4, 128
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(B, S, H, Hd)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(B, S, KV, Hd)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(B, S, KV, Hd)), jnp.bfloat16)

    def native_loss(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               interpret=False).astype(jnp.float32).sum()

    def repeat_loss(q, k, v):
        kr = jnp.repeat(k, H // KV, axis=2)
        vr = jnp.repeat(v, H // KV, axis=2)
        return flash_attention(q, kr, vr, causal=True,
                               interpret=False).astype(jnp.float32).sum()

    native = jax.jit(jax.value_and_grad(native_loss, argnums=(0, 1, 2)))
    repeat = jax.jit(jax.value_and_grad(repeat_loss, argnums=(0, 1, 2)))

    ln, gn = native(q, k, v)
    lr, gr = repeat(q, k, v)
    assert abs(float(ln) - float(lr)) / max(abs(float(lr)), 1.0) < 2e-2
    for a, b, name in zip(gn, gr, "qkv"):
        assert a.shape == b.shape, name
        bf = b.astype(jnp.float32)
        err = float(jnp.abs(a.astype(jnp.float32) - bf).max())
        # both operands are bf16 pipelines; bound the drift relative to the
        # gradient's own scale (sum-loss dv grads reach O(100) at S=2048)
        tol = 0.02 * max(1.0, float(jnp.abs(bf).max()))
        assert err < tol, (name, err, tol)

    def timeit(fn, *args):
        # best of three 10-iter windows: a single window is exposed to
        # transient host stalls (observed flaking this assertion when run
        # mid-tier); the min is the hardware's number
        jax.block_until_ready(fn(*args))
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(10):
                out = fn(*args)
            jax.block_until_ready(out)
            w = (time.perf_counter() - t0) / 10
            best = w if best is None else min(best, w)
        return best

    tn = timeit(native, q, k, v)
    tr = timeit(repeat, q, k, v)
    print(f"\ngqa native {tn*1e3:.2f} ms vs repeat {tr*1e3:.2f} ms "
          f"({tr/tn:.2f}x)")
    assert tn <= tr * 1.10, (tn, tr)


@tpu_tier
def test_decode_attention_alibi_and_pad_bias(tpu):
    """The alibi-slope and pad-bias operands ride their own block specs
    ([KV, P] full-block and [B, 1, Smax]); interpret mode cannot validate
    those Mosaic tilings — this does, against the einsum reference."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas.decode_attention import decode_attention

    rng = np.random.default_rng(4)
    B, H, KV, Hd, Smax, pos = 2, 8, 2, 64, 256, 100
    q = jnp.asarray(rng.normal(size=(B, H, Hd)), jnp.bfloat16)
    ck = jnp.asarray(rng.normal(size=(B, Smax, KV, Hd)), jnp.bfloat16)
    cv = jnp.asarray(rng.normal(size=(B, Smax, KV, Hd)), jnp.bfloat16)
    pad = jnp.where(jnp.arange(Smax)[None, :] < 3, -1e9, 0.0)
    pad = jnp.broadcast_to(pad, (B, Smax)).astype(jnp.float32)
    slopes = jnp.asarray([2.0 ** (-(i + 1)) for i in range(H)], jnp.float32)

    out = decode_attention(q, ck, cv, pos, pad_bias=pad, alibi_slopes=slopes,
                           interpret=False)

    rep = H // KV
    kk = jnp.repeat(ck, rep, axis=2).astype(jnp.float32)
    vv = jnp.repeat(cv, rep, axis=2).astype(jnp.float32)
    s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32), kk) * Hd**-0.5
    kpos = jnp.arange(Smax)[None, None, :]
    s = s + slopes[None, :, None] * (kpos - pos)
    s = s + pad[:, None, :]
    s = jnp.where(kpos <= pos, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    ref = jnp.einsum("bhs,bshd->bhd", p, vv)
    err = float(jnp.abs(out.astype(jnp.float32) - ref).max())
    assert err < 0.05, err


@tpu_tier
def test_flash_attention_masked_gqa(tpu):
    """GQA flash with a key-side pad mask — the mask operand's block spec on
    real Mosaic tiling, fwd + bwd."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.attention import mha_attention
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    rng = np.random.default_rng(5)
    B, S, H, KV, Hd = 2, 512, 8, 2, 64
    q = jnp.asarray(rng.normal(size=(B, S, H, Hd)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(B, S, KV, Hd)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(B, S, KV, Hd)), jnp.bfloat16)
    mask = (rng.uniform(size=(B, S)) > 0.2)
    mask[:, 0] = True
    bias = jnp.where(jnp.asarray(mask), 0.0, -1e9).astype(jnp.float32)

    def kernel_loss(q, k, v):
        return flash_attention(q, k, v, mask_bias=bias, causal=True,
                               interpret=False).astype(jnp.float32).sum()

    def ref_loss(q, k, v):
        return mha_attention(q, k, v, mask_bias=bias[:, None, None, :],
                             causal=True).astype(jnp.float32).sum()

    lk, gk = jax.jit(jax.value_and_grad(kernel_loss, argnums=(0, 1, 2)))(q, k, v)
    lr, gr = jax.jit(jax.value_and_grad(ref_loss, argnums=(0, 1, 2)))(q, k, v)
    assert abs(float(lk) - float(lr)) / max(abs(float(lr)), 1.0) < 2e-2
    for a, b, name in zip(gk, gr, "qkv"):
        bf = b.astype(jnp.float32)
        err = float(jnp.abs(a.astype(jnp.float32) - bf).max())
        tol = 0.02 * max(1.0, float(jnp.abs(bf).max()))
        assert err < tol, (name, err, tol)


@tpu_tier
def test_fused_lamb_kernel_compiles_and_matches(tpu):
    """The LAMB kernel's SMEM trust-ratio reduction on real Mosaic."""
    import jax.numpy as jnp

    from deepspeed_tpu.ops.lamb.fused_lamb_kernel import (_jnp_lamb_flat,
                                                          fused_lamb_step)

    rng = np.random.default_rng(6)
    n = 300_001
    p = jnp.asarray(rng.normal(size=n), jnp.float32)
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    m = jnp.zeros(n, jnp.float32)
    v = jnp.zeros(n, jnp.float32)
    kp, km, kv, tr = fused_lamb_step(p, g, m, v, step=1, lr=1e-3,
                                     weight_decay=0.01, interpret=False)
    # the kernel's plain-jnp twin is the reference (interpret mode is an
    # error on a TPU backend)
    rp, _, _, rtr, _ = _jnp_lamb_flat(
        p, g, m, v, jnp.float32(1e-3), jnp.float32(1 - 0.9),
        jnp.float32(1 - 0.999), b1=0.9, b2=0.999, eps=1e-6, wd=0.01,
        emit="param")
    assert float(jnp.abs(kp - rp).max()) < 1e-5
    assert abs(float(tr) - float(rtr)) < 1e-5


@tpu_tier
def test_blocksparse_flash_compiles_and_matches(tpu):
    """Block-sparse flash (layout-driven block skipping) on real Mosaic vs
    the dense-backend sparse attention reference."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    from deepspeed_tpu.ops.sparse_attention import (LocalSlidingWindowSparsityConfig,
                                                    SparseSelfAttention)

    rng = np.random.default_rng(8)
    B, S, H, Hd = 2, 512, 2, 64
    q = jnp.asarray(rng.normal(size=(B, S, H, Hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, Hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, Hd)), jnp.float32)
    cfg = LocalSlidingWindowSparsityConfig(num_heads=H, block=128,
                                           num_sliding_window_blocks=2)
    layout = jnp.asarray(cfg.make_layout(S), jnp.float32)

    out = flash_attention(q, k, v, causal=True, block_layout=layout,
                          interpret=False)
    ref = SparseSelfAttention(cfg, backend="dense")(q, k, v)
    err = float(jnp.abs(out - ref).max())
    assert err < 0.02, err

    g = jax.grad(lambda qq: flash_attention(qq, k, v, causal=True,
                                            block_layout=layout,
                                            interpret=False).sum())(q)
    gr = jax.grad(lambda qq: SparseSelfAttention(cfg, backend="dense")(
        qq, k, v).sum())(q)
    gerr = float(jnp.abs(g - gr).max())
    assert gerr < 0.05, gerr


@tpu_tier
@pytest.mark.parametrize("cell,B,H,alibi,kernels", [
    ("bloom560m_train_1chip", 4, 16, True, ("flash_dq", "flash_dkv")),
    ("opt1b3_train_zero3_4chip", 2, 32, False,
     ("flash_packed_dq", "flash_packed_dkv")),
])
def test_flash_backward_at_the_train_cells_shapes(tpu, cell, B, H, alibi, kernels):
    """``dq``, ``dk`` and ``dv`` COMPILED at a train cell's exact attention
    shapes (a chip's micro-batch, heads of 64, 2,048 positions: 1,024 x 1,024
    blocks, the diagonal ones walked in chunks since PR 46) against the
    float32 reference's gradients. The cells' own check is a forward-only
    eval loss and "the loss fell", blunt to a wrong gradient: this test is
    what holds the compiled backward kernels."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.transformer import _alibi_slopes
    from deepspeed_tpu.ops import dispatch
    from deepspeed_tpu.ops.attention import mha_attention
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    S, Hd = 2048, 64
    slopes = jnp.asarray(_alibi_slopes(H), jnp.float32) if alibi else None
    q, k, v, g = (jax.random.normal(kk, (B, S, H, Hd), jnp.float32)
                  .astype(jnp.bfloat16)
                  for kk in jax.random.split(jax.random.key(46), 4))

    def grads(attn):
        def run(q, k, v, g):
            o, vjp = jax.vjp(attn, q, k, v)
            return vjp(g.astype(o.dtype))
        return jax.jit(run)

    dispatch.reset()
    flash = grads(lambda q, k, v: flash_attention(
        q, k, v, causal=True, alibi_slopes=slopes, interpret=False))
    text = flash.lower(q, k, v, g).as_text()
    assert all(f'"{name}"' in text for name in kernels), cell
    assert dispatch.selected().get("flash_bwd_diag=chunks") == 1, dispatch.selected()
    got = flash(q, k, v, g)
    with jax.default_matmul_precision("highest"):
        want = grads(lambda q, k, v: mha_attention(
            q, k, v, causal=True, alibi_slopes=slopes))(
                *(x.astype(jnp.float32) for x in (q, k, v, g)))
    for a, b, name in zip(got, want, ("dq", "dk", "dv")):
        assert a.dtype == jnp.bfloat16
        b = np.asarray(b)
        err = float(np.abs(np.asarray(a, np.float32) - b).max() / np.abs(b).max())
        # bf16 operands (p and ds are rounded to bf16 before their products)
        assert err < 0.02, (cell, name, err)


def _paged_reference(q, kp, vp, bt, pos, pad_bias=None, slopes=None):
    """fp32 einsum reference for paged decode attention: gather each
    request's logical cache through its block table, then masked softmax."""
    import jax
    import jax.numpy as jnp
    B, H, Hd = q.shape
    bs, KV = kp.shape[1], kp.shape[2] // Hd     # pools [blocks, bs, KV*Hd]
    S = bt.shape[1] * bs
    k = kp[bt].reshape(B, S, KV, Hd).astype(jnp.float32)
    v = vp[bt].reshape(B, S, KV, Hd).astype(jnp.float32)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32), k) * Hd**-0.5
    kpos = jnp.arange(S)[None, None, :]
    qpos = pos[:, None, None]
    if slopes is not None:
        s = s + slopes[None, :, None] * (kpos - qpos)
    if pad_bias is not None:
        s = s + pad_bias[:, None, :]
    s = jnp.where(kpos <= qpos, s, -1e30)
    return jnp.einsum("bhs,bshd->bhd", jax.nn.softmax(s, axis=-1), v)


@tpu_tier
@pytest.mark.parametrize("H,KV,Hd", [(12, 12, 64),     # GPT-2: MHA, group 1
                                      (32, 32, 64),     # OPT-1.3B: 2,048-lane rows
                                      (12, 4, 128)])    # GQA, group 3
@pytest.mark.parametrize("with_bias,with_alibi", [(False, False), (True, False),
                                                  (False, True), (True, True)])
def test_paged_decode_attention_compiles_and_matches(tpu, H, KV, Hd,
                                                     with_bias, with_alibi):
    """The paged kernel at the geometries the serving path uses (block 128,
    ragged per-request positions, shuffled block tables with junk in the
    dead tail), with and without pad bias and ALiBi, vs the einsum
    reference. Interpret mode cannot see the bias block's tiling, the
    group-1 row slices or the bf16 kv-head loads — Mosaic does."""
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas.paged_decode_attention import \
        paged_decode_attention

    rng = np.random.default_rng(9)
    B, bs, n_max, num_blocks = 8, 128, 6, 64
    # one request per depth class: first slot, block edges, mid-block, full
    pos = np.array([0, 127, 128, 200, 383, 384, 700, n_max * bs - 1], np.int32)
    live = rng.permutation(np.arange(1, num_blocks))[:B * n_max]
    bt = live.reshape(B, n_max).astype(np.int32)
    for b in range(B):      # dead tail entries may be anything in range
        bt[b, pos[b] // bs + 1:] = rng.integers(0, num_blocks)
    q = jnp.asarray(rng.normal(size=(B, H, Hd)), jnp.bfloat16)
    kp = jnp.asarray(rng.normal(size=(num_blocks, bs, KV * Hd)), jnp.bfloat16)
    vp = jnp.asarray(rng.normal(size=(num_blocks, bs, KV * Hd)), jnp.bfloat16)
    pad = None
    if with_bias:
        pad = np.zeros((B, n_max * bs), np.float32)
        pad[:, 1:3] = -1e9          # masked cache slots inside the prefix
        pad[0] = 0.0                # (request 0 has a single live slot)
        pad = jnp.asarray(pad)
    slopes = (jnp.asarray([2.0 ** (-(i + 1) * 8 / H) for i in range(H)],
                          jnp.float32) if with_alibi else None)

    out = paged_decode_attention(q, kp, vp, jnp.asarray(bt), jnp.asarray(pos),
                                 pad_bias=pad, alibi_slopes=slopes,
                                 interpret=False)
    ref = _paged_reference(q, kp, vp, jnp.asarray(bt), jnp.asarray(pos),
                           pad, slopes)
    assert out.shape == (B, H, Hd)
    err = float(jnp.abs(out.astype(jnp.float32) - ref).max())
    assert np.isfinite(err) and err < 0.05, err


@tpu_tier
@pytest.mark.parametrize("Q,H,KV,Hd,rows,width", [
    (4, 32, 4, 128, 64, 8),     # sdar30b_serve_blockgen: 32 rows a kv head
    (1, 64, 8, 128, 16, 16),    # Solar-Open2's softmax layer: 8 rows
    (4, 4, 2, 64, 8, 6),        # positions against the block-diagonal query
])
def test_paged_position_axis_compiles_and_matches(tpu, Q, H, KV, Hd, rows,
                                                  width):
    """The paged kernel's position axis COMPILED: the block-generation
    cell's shape (64 rows of a block of 4 positions x 32 heads over 4 kv
    heads of 128, 8 table entries) and both sides of the line between the
    forms, bf16 pools, rows from one live block to the whole table and an
    idle row, against the float32 reference. Mosaic sees what interpret
    mode cannot: a kv head's lane slice of a block, the stacked terms'
    tiles, the position-by-position rows of the output."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops import dispatch
    from deepspeed_tpu.ops.pallas.paged_decode_attention import (
        _per_kv_head, paged_decode_attention)

    rng = np.random.default_rng(36)
    bs, num_blocks = 128, rows * width + 1
    pos = rng.integers(0, width * bs - Q, rows).astype(np.int32)
    pos[:4] = [Q - 1, bs - 1, bs, width * bs - 1]
    pos[-1] = 0
    bt = (rng.permutation(num_blocks - 1) + 1).reshape(rows, width) \
        .astype(np.int32)
    for b in range(rows):       # dead tail entries may be anything in range
        bt[b, pos[b] // bs + 1:] = rng.integers(0, num_blocks)
    bt[-1] = 0                  # an idle row on the dummy block
    q = jnp.asarray(rng.normal(size=(rows, Q, H, Hd)), jnp.bfloat16)
    kp = jnp.asarray(rng.normal(size=(num_blocks, bs, KV * Hd)), jnp.bfloat16)
    vp = jnp.asarray(rng.normal(size=(num_blocks, bs, KV * Hd)), jnp.bfloat16)

    dispatch.reset()
    out = paged_decode_attention(q, kp, vp, jnp.asarray(bt), jnp.asarray(pos),
                                 interpret=False)
    form = "per_kv_head" if _per_kv_head(Q * H // KV, Hd) else "block_diagonal"
    assert dispatch.selected().get(f"paged_decode_attention={form}") == 1
    ref = jax.vmap(lambda one: _paged_reference(
        one, kp, vp, jnp.asarray(bt), jnp.asarray(pos)), 1, 1)(q)
    assert out.shape == (rows, Q, H, Hd)
    err = float(jnp.abs(out.astype(jnp.float32) - ref)[:-1].max())
    assert np.isfinite(err) and err < 0.05, err
    assert not np.asarray(out[-1].astype(jnp.float32)).any()    # idle: zeros


# one row a depth into its newest block, idle rows (None) first, two in a
# row in the middle, and last
LIVE_ROWS = [None, 0, 31, 32, None, None, 63, 64, 95, 96, 127, None]


@tpu_tier
@pytest.mark.parametrize("Q,H,KV,Hd,window", [
    (1, 32, 32, 64, 0),         # OPT-1.3B: 2,048-lane rows, one block a group
    (1, 16, 16, 128, 0),        # OLMoE
    (4, 32, 4, 128, 0),         # SDAR: 4 positions, groups of 6 blocks
    (1, 28, 4, 128, 4096),      # SmallThinker's window layers: rings of 33
])
def test_paged_kernel_copies_only_live_rows(tpu, Q, H, KV, Hd, window):
    """COMPILED, at the cells' shapes: rows without a request (their first
    live table entry names the dummy block) first, last and two in a row
    yield exactly zero and break no live row's chain of first copies (under
    a window: in rings that have not wrapped and that have, several times).
    NaN fills the whole dummy block in both pools and every KEY slot past
    ``pos`` in a row's newest block; the output is finite and the float32
    reference's on the clean pools. Before PR 49 an idle row copied the
    dummy block and the NaN reached its output. The tables and the dummy
    block are a stacked pool's second layer's (``dummy_block`` traced)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import transformer as T
    from deepspeed_tpu.ops.pallas.paged_decode_attention import \
        paged_decode_attention

    rng = np.random.default_rng(49 + H)
    bs, rows = 128, len(LIVE_ROWS)
    width = window // bs + 1 if window else 8
    live = np.array([d is not None for d in LIVE_ROWS])
    # whole blocks before the newest: none, a few, and past a ring's width
    before = rng.permutation(np.arange(rows)) % (width - 1)
    if window:
        before = before * 5
    before[1] = 0 if Q == 1 else 1
    pos = np.where(live, before * bs + np.array(
        [d or 0 for d in LIVE_ROWS]), 0).astype(np.int32)
    slots = np.where(live, np.arange(1, rows + 1), 0)
    bt = np.asarray(T.ring_tables(slots, width))    # row i: its own blocks
    kp = jnp.asarray(rng.normal(size=(rows * width + 1, bs, KV * Hd)),
                     jnp.bfloat16)
    vp = jnp.asarray(rng.normal(size=kp.shape), jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(rows, Q, H, Hd)), jnp.bfloat16)
    if window:
        cfg = T.TransformerConfig(n_head=H, n_kv_head=KV, head_size=Hd,
                                  d_model=H * Hd, pos_embedding="none")
        ref = T._grouped_cache_einsum(
            cfg, q.astype(jnp.float32),
            T._paged_gather(kp, bt, KV).astype(jnp.float32),
            T._paged_gather(vp, bt, KV).astype(jnp.float32),
            jnp.asarray(pos)[:, None], None,
            kpos=T._ring_kpos(pos, width, bs), window=window
        ).reshape(q.shape)
    else:
        ref = jax.vmap(lambda one: _paged_reference(
            one, kp, vp, jnp.asarray(bt), jnp.asarray(pos)), 1, 1)(q)

    kp, vp = kp.at[0].set(jnp.nan), vp.at[0].set(jnp.nan)
    for b in np.flatnonzero(live):
        kp = kp.at[bt[b, pos[b] // bs % width], pos[b] % bs + 1:].set(jnp.nan)
    # as the programs hand them over: pools stacked over layers, this
    # layer's blocks (its dummy among them) after another's, and where the
    # dummy lies an operand (the layer scan's index times a layer's blocks)
    block0 = 3
    kp, vp = (jnp.concatenate([jnp.full_like(pool[:block0], jnp.nan), pool])
              for pool in (kp, vp))
    out = jax.jit(lambda bt, dummy: paged_decode_attention(
        q[:, 0] if Q == 1 else q, kp, vp, bt, jnp.asarray(pos),
        window=window, interpret=False, dummy_block=dummy))(
            jnp.asarray(bt) + block0, block0)
    out = np.asarray(out.astype(jnp.float32)).reshape(q.shape)
    assert not out[~live].any()
    err = np.abs(out - np.asarray(ref))[live].max()
    assert np.isfinite(err) and err < 0.05, err


@tpu_tier
def test_window_kernels_compile_and_match(tpu):
    """Both kernels under a WINDOW, COMPILED, at the widths of
    ``smallthinker21b_serve_longctx`` (28 query and 4 key/value heads of
    128, window 4,096, blocks of 128, bf16): the paged kernel over ring
    tables of 33 entries, rows shorter than the window, with a first live
    block partly masked, on a block's border, wrapped several times and an
    idle row, against its XLA twin (the ring gather and the masked einsum the
    CPU tier serves with); the banded forward flash kernel at 8,192 tokens
    on its plain path against the masked einsum. The served check of that
    cell is blunt to the mask (PERF.md section 7, PR 37): this is what holds
    the compiled band."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import transformer as T
    from deepspeed_tpu.ops.attention import mha_attention
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    from deepspeed_tpu.ops.pallas.paged_decode_attention import \
        paged_decode_attention

    rng = np.random.default_rng(37)
    H, KV, Hd, W, bs = 28, 4, 128, 4096, 128
    R = W // bs + 1
    cfg = T.TransformerConfig(n_head=H, n_kv_head=KV, head_size=Hd,
                              d_model=H * Hd, pos_embedding="none")
    pos = np.array([5, 4095, 4096, 4200, 6145, 8191, 8192, 11519, 16000, 0],
                   np.int32)
    slots = np.arange(1, len(pos) + 1, dtype=np.int32)
    slots[-1] = 0                                    # an idle row
    tables = T.ring_tables(slots, R)
    q = jnp.asarray(rng.normal(size=(len(pos), H, Hd)), jnp.bfloat16)
    kp = jnp.asarray(rng.normal(size=(len(pos) * R + 1, bs, KV * Hd)),
                     jnp.bfloat16)
    vp = jnp.asarray(rng.normal(size=kp.shape), jnp.bfloat16)
    out = paged_decode_attention(q, kp, vp, tables, jnp.asarray(pos),
                                 window=W, interpret=False)
    ref = T._grouped_cache_einsum(
        cfg, q[:, None].astype(jnp.float32),
        T._paged_gather(kp, tables, KV).astype(jnp.float32),
        T._paged_gather(vp, tables, KV).astype(jnp.float32),
        jnp.asarray(pos)[:, None], None, kpos=T._ring_kpos(pos, R, bs),
        window=W).reshape(q.shape)
    err = float(jnp.abs(out.astype(jnp.float32) - ref)[:-1].max())
    assert np.isfinite(err) and err < 0.05, err

    S = 8192
    q = jnp.asarray(rng.normal(size=(1, S, H, Hd)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, S, KV, Hd)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, S, KV, Hd)), jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, window=W, interpret=False)
    ref = mha_attention(q[:, :, :7], k[:, :, :1], v[:, :, :1], causal=True,
                        window=W)                    # one kv head's group
    err = float(jnp.abs(out[:, :, :7].astype(jnp.float32)
                        - ref.astype(jnp.float32)).max())
    assert np.isfinite(err) and err < 0.05, err
    # and the band is not the triangle
    tri = flash_attention(q, k, v, causal=True, interpret=False)
    assert float(jnp.abs(out.astype(jnp.float32)
                         - tri.astype(jnp.float32))[:, W + 8:].max()) > 0.05


@tpu_tier
def test_paged_matches_dense_decode_kernel(tpu):
    """Same cache content through both decode kernels: the paged kernel on a
    shuffled pool and ``decode_attention`` on the contiguous workspace give
    the same attention (one shared position — the dense kernel's contract)."""
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas.decode_attention import decode_attention
    from deepspeed_tpu.ops.pallas.paged_decode_attention import \
        paged_decode_attention

    rng = np.random.default_rng(10)
    B, H, KV, Hd, bs, n_max, pos = 4, 12, 12, 64, 128, 4, 300
    ck = jnp.asarray(rng.normal(size=(B, n_max * bs, KV, Hd)), jnp.bfloat16)
    cv = jnp.asarray(rng.normal(size=(B, n_max * bs, KV, Hd)), jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(B, H, Hd)), jnp.bfloat16)
    bt = rng.permutation(np.arange(1, 1 + B * n_max)).reshape(B, n_max)
    kp = jnp.zeros((1 + B * n_max, bs, KV * Hd), jnp.bfloat16)
    vp = jnp.zeros_like(kp)
    kp = kp.at[bt.reshape(-1)].set(ck.reshape(B * n_max, bs, KV * Hd))
    vp = vp.at[bt.reshape(-1)].set(cv.reshape(B * n_max, bs, KV * Hd))

    dense = decode_attention(q, ck, cv, pos, interpret=False)
    paged = paged_decode_attention(q, kp, vp, jnp.asarray(bt, jnp.int32),
                                   jnp.full((B,), pos, jnp.int32),
                                   interpret=False)
    err = float(jnp.abs(dense.astype(jnp.float32)
                        - paged.astype(jnp.float32)).max())
    assert err < 1e-2, err


@tpu_tier
@pytest.mark.parametrize("live", [128, 8])
def test_kda_decode_update_compiles_and_matches(tpu, live):
    """The KDA decode kernel compiled at ``solaropen2_serve_decode``'s widths
    (128 rows, 64 heads of a 128 x 128 float32 state, 129 slots), every row
    live and at 8 live rows of 128, against the plain-XLA form on the same
    chip: the live rows' ``o`` and every pool row past the dummy (the twin
    also steps the dummy) to 1e-5 of their largest value, the dummy and the
    slots of no row bit-identical to what they were."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.state_mixers import _kda_slot_update
    from deepspeed_tpu.ops.pallas.kda_decode_update import kda_decode_update
    from tests.unit.ops.test_kda_decode_update import draw_step

    r = np.random.default_rng(live)
    B, H, dk, dv, n_slots = 128, 64, 128, 128, 129
    step = draw_step(r, B, H, dk, dv)
    slots = np.zeros(B, np.int32)
    slots[r.choice(B, live, replace=False)] = \
        r.permutation(np.arange(1, n_slots))[:live]
    pool = jax.random.normal(jax.random.key(live), (n_slots, H, dk, dv),
                             jnp.float32)
    before = np.asarray(pool)
    want_o, want = jax.jit(_kda_slot_update, static_argnums=(8,))(
        pool, *step, jnp.asarray(slots), 0, n_slots)
    o, new = jax.jit(lambda S, *a: kda_decode_update(S, *a, interpret=False))(
        pool, *step, jnp.asarray(slots), 0)
    o, new, want_o, want = (np.asarray(a) for a in (o, new, want_o, want))
    on = slots != 0
    assert np.abs(o[on] - want_o[on]).max() <= 1e-5 * np.abs(want_o[on]).max()
    assert np.abs(new[1:] - want[1:]).max() <= 1e-5 * np.abs(want).max()
    idle = np.ones(n_slots, bool)
    idle[slots[on]] = False
    np.testing.assert_array_equal(new[idle], before[idle])
    assert not o[~on].any()


@tpu_tier
@pytest.mark.parametrize("live", [64, 9, 5, 0])
def test_mamba2_decode_update_compiles_and_matches(tpu, live):
    """The Mamba-2 decode kernel compiled at ``granite4hmicro_serve_chat``'s
    widths (64 rows, a 128 x 4,096 float32 state a row: 64 heads of 64 x
    128, two periods of 65 slots), every row live (eight phases of eight
    rows), at 9 live rows of 64 (a phase and one row), at 5 (a short phase)
    and with no live row (no copy is issued: the pool goes out as it came
    in), against the plain-XLA form on the same chip: the live rows' ``y`` and
    states to 1e-5 of their largest value, every other pool row
    bit-identical to what it was, an idle row's ``y`` zero."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.state_mixers import _ssd_decode_update
    from deepspeed_tpu.ops.pallas.mamba2_decode_update import \
        mamba2_decode_update
    from tests.unit.ops.test_mamba2_decode_update import draw_step

    r = np.random.default_rng(live)
    B, H, P, N, n_slots, base = 64, 64, 64, 128, 65, 65
    x, dt, A, Bm, Cm, D = draw_step(r, B, H, P, N)
    slots = np.zeros(B, np.int32)
    slots[r.choice(B, live, replace=False)] = \
        r.permutation(np.arange(1, n_slots))[:live]
    pool = jax.random.normal(jax.random.key(live), (2 * n_slots, N, H * P),
                             jnp.float32)
    before = np.asarray(pool)
    zero = jnp.zeros_like(D)
    want_y, want = jax.jit(_ssd_decode_update)(
        pool, x, dt, A, Bm, Cm, zero, jnp.asarray(slots), base)
    y, new = jax.jit(lambda S, *a: mamba2_decode_update(S, *a, interpret=False))(
        pool, x, dt, A, Bm, Cm, jnp.asarray(slots), base)
    y, new, want_y, want = (np.asarray(a) for a in (y, new, want_y, want))
    on = slots != 0
    if live:
        assert np.abs(y[on] - want_y[on]).max() <= 1e-5 * np.abs(want_y[on]).max()
    assert np.abs(new - want).max() <= 1e-5 * np.abs(want).max()
    idle = np.ones(2 * n_slots, bool)
    idle[base + slots[on]] = False
    np.testing.assert_array_equal(new[idle], before[idle])
    assert not y[~on].any()


@tpu_tier
@pytest.mark.parametrize("rows,deepest", [(64, 3071), (5, 400)])
def test_latent_decode_attention_compiles_and_matches(tpu, rows, deepest):
    """The latent paged kernel at the LongCat cell's shapes (64 query heads
    over rows of 512 + 64 values in 640 lanes, bf16, tables of 36 blocks,
    rows at depths from 0 to ``deepest``) against the plain form in
    float32: bf16 probabilities over up to 3,072 keys."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas.latent_decode_attention import \
        latent_decode_attention

    rng = np.random.default_rng(rows)
    H, R, row, bs, width = 64, 512, 640, 128, 36
    pos = rng.integers(0, deepest + 1, size=rows)
    pos[0], pos[-1] = 0, deepest
    n_blocks = int((pos // bs + 1).sum()) + 1
    cp = rng.standard_normal((n_blocks, bs, row)).astype(np.float32)
    cp[:, :, 576:] = 0.0
    q = rng.standard_normal((rows, H, row)).astype(np.float32)
    bt = np.zeros((rows, width), np.int32)
    free = list(rng.permutation(np.arange(1, n_blocks)))
    for b in range(rows):
        for j in range(pos[b] // bs + 1):
            bt[b, j] = free.pop()
    scale = 192 ** -0.5 / 8.0          # the rows are not normed here
    qb, cb = jnp.asarray(q, jnp.bfloat16), jnp.asarray(cp, jnp.bfloat16)
    got = latent_decode_attention(qb, cb, jnp.asarray(bt),
                                  jnp.asarray(pos, jnp.int32), latent=R,
                                  scale=scale, interpret=False)
    c = cb[jnp.asarray(bt)].reshape(rows, -1, row).astype(jnp.float32)
    s = jnp.einsum("bhr,bsr->bhs", qb.astype(jnp.float32), c,
                   precision="highest") * scale
    s = jnp.where(jnp.arange(c.shape[1])[None, None] <= pos[:, None, None],
                  s, -jnp.inf)
    want = jnp.einsum("bhs,bsr->bhr", jax.nn.softmax(s, axis=-1), c[..., :R],
                      precision="highest")
    err = float(jnp.abs(got.astype(jnp.float32) - want).max())
    assert got.shape == (rows, H, R) and err < 0.03, err


@tpu_tier
def test_flash_with_keys_wider_than_values_compiles_and_matches(tpu):
    """The latent prefill's call of the flash kernel: 64 heads of keys 192
    wide, the values (128) padded to the keys' width, at the cell's longest
    prompt bucket; against the einsum with the two widths as they are."""
    import jax.numpy as jnp

    from deepspeed_tpu.ops.attention import mha_attention
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    rng = np.random.default_rng(7)
    S, H = 3072, 64
    q = jnp.asarray(rng.normal(size=(1, S, H, 192)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, S, H, 192)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, S, H, 128)), jnp.bfloat16)
    vp = jnp.pad(v, ((0, 0),) * 3 + ((0, 64),))
    out = flash_attention(q, k, vp, causal=True, scale=192 ** -0.5,
                          interpret=False)[..., :128]
    want = mha_attention(q[:, :, :8], k[:, :, :8], v[:, :, :8], causal=True,
                         scale=192 ** -0.5)
    err = float(jnp.abs(out[:, :, :8].astype(jnp.float32)
                        - want.astype(jnp.float32)).max())
    assert err < 0.05, err


@tpu_tier
def test_solar_toy_logits_through_the_compiled_kda_kernel(tpu):
    """PERF.md section 7(a): the served check of ``solaropen2_serve_decode``
    sees only a wrong or lost state, so the kernel owes the unit tests'
    logits on the chip. The Solar toy at a width the kernel tiles (two KDA
    heads of 128 x 128; GQA heads of 64, which the paged and flash kernels
    take), float32 weights and matmuls in full float32: a prompt prefilled
    and 12 tokens decoded through the COMPILED kernel, logits against the
    plain reference's full forward. The same run on the plain-XLA form
    bounds what the chip's own float32 arithmetic leaves: the kernel may
    not be further from the reference than twice that, and both lie far
    under the 3e-3 a bf16 state moves them by."""
    import jax

    from deepspeed_tpu.ops import dispatch
    from tests.unit import test_solar_open2 as solar

    tokens = solar.tokens_of(32, 70 + 12)
    err = {}
    with jax.default_matmul_precision("highest"):
        # the prompt through the plain-XLA prefill in both runs: the decode
        # step is what differs
        toy = solar.load_toy("xla", head_size=64, **solar.WIDE)
        want = solar.reference_logits(toy, tokens)[69:]
        for backend, form in (("auto", "kda_kernel"), ("xla", "slot_update")):
            decoder = solar.load_toy(backend, head_size=64, **solar.WIDE)[0]
            dispatch.reset()
            got, _ = solar.paged_logits(*toy[:2], tokens, 70,
                                        decode_model=decoder)
            assert dispatch.selected().get(f"kda_decode={form}") == 3
            assert ("kernel/kda_decode_update=compiled" in dispatch.selected()) \
                == (backend == "auto")
            err[form] = float(np.abs(got - want).max())
    print("logit error on the chip:", err)
    assert err["slot_update"] <= 2e-4, err
    assert err["kda_kernel"] <= max(2 * err["slot_update"], 2e-5), err


@tpu_tier
def test_sdar_toy_logits_through_the_compiled_kernels(tpu):
    """Generation by blocks through the COMPILED kernels: the SDAR toy at a
    head size both kernels take (64; 2 kv heads x 64 = 128 lanes), float32
    weights and matmuls in full float32, a 138-token prompt (remainder 2)
    prefilled through ``flash_attention`` with the staircase of 4 and 14
    tokens generated by denoise and commit passes whose 4 positions ride
    ``paged_decode_attention``'s position axis (8 query rows a kv head),
    every deciding pass's logits against the plain reference's. The same
    replay on the plain-XLA forms bounds what the chip's own float32
    arithmetic leaves."""
    import jax

    from deepspeed_tpu.ops import dispatch
    from tests.unit import test_sdar as sdar

    err = {}
    with jax.default_matmul_precision("highest"):
        toy = sdar.load_toy(head_size=64, attention_backend="xla")
        prompt = sdar.prompts_of([138], seed=33)[0]
        rec = []
        new = sdar.reference_tokens(toy, prompt, 14, record=rec)
        for backend in ("auto", "xla"):
            run = sdar.load_toy(head_size=64, attention_backend=backend)
            dispatch.reset()
            pairs = sdar.replay((run[0],) + toy[1:], prompt, rec, new,
                                bs=128, n_blocks=4)
            forms = dispatch.selected()
            kernels = {"paged_block=paged_kernel", "paged_prefill=flash",
                       "kernel/paged_decode_attention=compiled",
                       "kernel/flash_attention=compiled"}
            assert (kernels <= set(forms)) == (backend == "auto"), forms
            err[backend] = sdar.worst(pairs)
    print("logit error on the chip:", err)
    assert err["xla"] <= 2e-4, err
    assert err["auto"] <= max(2 * err["xla"], 1e-4), err


@tpu_tier
def test_sdar_riders_through_the_compiled_kernels_at_the_cells_shape(tpu):
    """A block's commit as a rider entry, COMPILED at the cell's shape: 64
    main and 16 rider entries of 4 positions, GQA 32/4 at 128 (32 query
    rows a kv head, the products a kv head), pool blocks of 128, d 2,048
    and 16 held experts of a router's 128 (two layers of the 48; float32
    weights, matmuls in full float32). 16 rows ride, their A/B pairs inside
    one pool block and across two (the whole block ends a pool block), 47
    do not, one main entry idles. The next blocks' logits and every pool
    slot are those of a commit pass followed by a denoise pass through the
    plain-XLA forms; the same fused pass on the XLA forms bounds what the
    chip's float32 arithmetic leaves between two batchings; with the riders
    left out the next blocks' logits move by far more."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.presets import get_model
    from deepspeed_tpu.ops import dispatch
    from tests.unit import test_sdar as sdar

    W, E, bs = 64, 16, 128
    r = np.random.default_rng(55)
    depths = (r.integers(0, (4 * bs - 8) // 4, W - 1) * 4).tolist()
    depths[:6] = [0, 8, bs - 8, bs - 4, 2 * bs - 4, 3 * bs - 4]
    rows = [(int(p), i < E) for i, p in enumerate(depths)]
    rides = np.array([ride for _, ride in rows])
    err = {}
    with jax.default_matmul_precision("highest"):
        models = {b: get_model("sdar", "30b-a3b-ep8", n_layer=2,
                               attention_backend=b, **sdar.REGIME)
                  for b in ("auto", "xla")}
        params = models["xla"].init_params(jax.random.key(2))
        pools = jax.tree.map(
            lambda a: jnp.asarray(r.standard_normal(a.shape), a.dtype),
            models["xla"].init_paged_cache(1 + 4 * len(rows), bs,
                                           dtype=jnp.float32))
        case = sdar.ride_case(models["xla"], rows, W, E, bs, seed=56)
        _, first, second = sdar.ride_passes(models["xla"], params, pools,
                                            case, W)
        want = np.where(rides[:, None, None], np.asarray(second[0])[:W - 1],
                        np.asarray(first[0])[:W - 1])
        scale = float(np.abs(want).max())
        for backend in ("auto", "xla"):
            dispatch.reset()
            fused = sdar.ride_passes(models[backend], params, pools, case,
                                     W)[0]
            forms = dispatch.selected()
            kernels = {"paged_block=paged_kernel", "experts=grouped_kernel",
                       "paged_decode_attention=per_kv_head",
                       "kernel/paged_decode_attention=compiled"}
            assert (kernels <= set(forms)) == (backend == "auto"), forms
            err[backend] = float(np.abs(
                np.asarray(fused[0])[:W - 1] - want).max()) / scale
            err[backend + ".pools"] = max(
                float(np.abs(np.asarray(a[:, 1:]) - np.asarray(b[:, 1:])).max())
                for a, b in zip(jax.tree.leaves(fused[1]),
                                jax.tree.leaves(second[1])))
            np.testing.assert_array_equal(
                np.asarray(fused[2]),
                np.asarray(first[2]) + np.asarray(second[2]))
        toks, bt, pos = case["fused"]
        bare = dict(case, fused=(toks, np.concatenate([bt[:W], 0 * bt[W:]]),
                                 pos))
        lost = sdar.ride_passes(models["auto"], params, pools, bare, W)[0][0]
        err["riders_left_out"] = float(np.abs(
            np.asarray(lost)[:W - 1][rides] - want[rides]).max()) / scale
    print("riders on the chip, logit error over the largest logit:", err)
    assert err["xla"] <= 1e-4 and err["xla.pools"] <= 1e-3, err
    assert err["auto"] <= max(2 * err["xla"], 1e-4), err
    assert err["auto.pools"] <= max(2 * err["xla.pools"], 1e-3), err
    assert err["riders_left_out"] > 100 * max(err["auto"], 1e-5), err


# the four routed cells' calls: rows, top-k, held experts, of a router's, D, F
EXPERT_CELLS = {
    "smallthinker": (16, 6, 64, 64, 2560, 768),
    "olmoe": (64, 8, 64, 64, 2048, 1024),
    "sdar_bucket": (128, 8, 16, 128, 2048, 768),
    "solar": (128, 8, 40, 320, 4096, 1280),
    # calls past one row tile (PR 53: a visit computes its expert's own
    # rows): LFM2's decode step, SDAR's 256-position pass, Solar's largest
    # taken prefill bucket, and every row of a step on ONE expert
    "lfm2_step": (256, 4, 64, 64, 2048, 1536),
    "sdar_pass": (256, 8, 16, 128, 2048, 768),
    "solar_bucket_512": (512, 8, 40, 320, 4096, 1280),
    "lfm2_step_every_row_on_one_expert": (256, 1, 64, 64, 2048, 1536),
}


@tpu_tier
@pytest.mark.parametrize("cell", sorted(EXPERT_CELLS))
def test_grouped_expert_mlp_compiles_and_matches(tpu, cell):
    """The COMPILED grouped expert kernel at a routed cell's widths, layer 1
    of a two-layer stack read through the layer offset, against its
    plain-XLA twin over that layer's slice: bf16 operands, float32 sums.
    Both round the hidden values to bf16 (the twin after the routing
    weight, the kernel before it), so they differ by that rounding. A call
    past one row tile is held to the twin in float32 instead (the same bf16
    weights and rows, every product and sum in float32)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.moe.sharded_moe import dense_dispatch, topk_routing
    from deepspeed_tpu.ops.pallas.grouped_expert_mlp import (
        dense_expert_mlp, grouped_expert_mlp)

    T, k, E, R, D, F = EXPERT_CELLS[cell]
    ks = jax.random.split(jax.random.key(40), 5)
    draw = lambda key, shape, std: (  # noqa: E731
        jax.random.normal(key, shape, jnp.float32) * std).astype(jnp.bfloat16)
    w_gate, w_up = draw(ks[0], (2 * E, D, F), 0.02), draw(ks[1], (2 * E, D, F), 0.02)
    w_down = draw(ks[2], (2 * E, F, D), 0.02)
    x = draw(ks[3], (T, D), 1.0)
    weights, experts, _ = topk_routing(jax.random.normal(ks[4], (T, R)), k, True)
    experts = jnp.where(experts < E, experts, E)       # held elsewhere
    if cell.endswith("one_expert"):
        experts = jnp.full_like(experts, 3)
    valid = (jnp.arange(T) % 5 != 4).astype(jnp.int32)  # padding rows
    f32 = (lambda a: a.astype(jnp.float32)) if T > 128 else (lambda a: a)

    @jax.jit
    def both(x, w_gate, w_up, w_down):
        got, n = dense_dispatch(
            x, weights, experts, E,
            lambda xs, c: grouped_expert_mlp(xs, c, w_gate, w_up, w_down,
                                             jnp.int32(E), interpret=False),
            valid)
        with jax.default_matmul_precision("highest"):
            want, _ = dense_dispatch(
                f32(x), weights, experts, E,
                lambda xs, c: dense_expert_mlp(xs, c, f32(w_gate[E:]),
                                               f32(w_up[E:]), f32(w_down[E:])),
                valid)
        return got, want, n

    got, want, n = both(x, w_gate, w_up, w_down)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert 0 < int((np.asarray(n) > 0).sum()) <= E
    scale = float(np.abs(want).max())
    assert scale > 0
    assert float(np.abs(got - want).max()) <= 0.02 * scale, (
        float(np.abs(got - want).max()), scale)
    assert not got[np.asarray(valid) == 0].any()


def expert_stack_moves(hlo_text, n, E, D, F):
    """The instructions of a compiled program that MAKE an array of the
    shape of a layer's experts (``[E, D, F]`` / ``[E, F, D]``: a slice or a
    copy of a layer) or make a whole stack (``[n, E, ...]`` or its merged
    view ``[n * E, ...]``) by anything but a parameter, a tuple element or
    a bitcast."""
    import re
    mats = rf"({D},{F}|{F},{D})\]"
    layer = re.compile(rf"^bf16\[{E},{mats}")
    stack = re.compile(rf"^bf16\[({n},{E}|{n * E}),{mats}")
    moves = []
    for line in hlo_text.splitlines():
        made = re.match(r"\s*(?:ROOT )?%\S+ = (\S+) ([\w\-]+)\(", line)
        if not made:
            continue
        shape, op = made.groups()
        if layer.match(shape) or (stack.match(shape) and op not in (
                "parameter", "get-tuple-element", "bitcast")):
            moves.append(line.strip()[:240])
    return moves


@tpu_tier
def test_the_decode_program_reads_the_expert_stacks_in_place(tpu):
    """The compiled decode program of a scanned MoE stack at lane-whole toy
    widths, on one device: the experts are the grouped kernel's custom call
    and the layer stacks reach it through bitcasts alone: no instruction
    makes a layer's slice of an expert stack or a copy of one
    (``expert_stack_moves``), as the same program on the dense form does."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import transformer as T
    from deepspeed_tpu.models.moe_lm import MoECausalLM, MoEConfig
    from deepspeed_tpu.ops import dispatch

    n, E, D, F, rows = 4, 8, 2048, 1024, 4
    texts = {}
    for backend in ("auto", "xla"):
        model = MoECausalLM(
            T.TransformerConfig(vocab_size=512, n_layer=n, n_head=16, d_model=D,
                                max_seq=512, remat=False,
                                attention_backend=backend),
            MoEConfig(dispatch="nodrop", num_experts=E, k=2, expert_d_ff=F,
                      expert_activation="swiglu"), param_dtype=jnp.bfloat16)
        params = jax.jit(model.init_params)(jax.random.key(0))
        pools = model.init_paged_cache(9, 128, jnp.bfloat16)
        args = (params, jnp.ones((rows, 1), jnp.int32), pools,
                jnp.ones((rows, 2), jnp.int32), jnp.full((rows,), 5, jnp.int32))
        dispatch.reset()
        texts[backend] = jax.jit(model.forward_paged_decode).lower(
            *args).compile().as_text()
        assert ("experts=grouped_kernel" in dispatch.selected()) \
            == (backend == "auto")
    assert "grouped_expert_mlp" in texts["auto"]
    assert expert_stack_moves(texts["auto"], n, E, D, F) == []
    assert expert_stack_moves(texts["xla"], n, E, D, F)      # the check bites


@tpu_tier
@pytest.mark.parametrize("remat,forwards", [("dots", 1), (True, 2)])
def test_dots_runs_the_forward_flash_kernel_once_a_layer(tpu, remat, forwards):
    """``remat="dots"`` keeps the flash kernel's (o, lse), so the OPTIMISED
    program of a BLOOM-560m-shaped stack's gradient (two scanned layers, d
    1024, 16 heads of 64, ALiBi, 2,048 positions) holds one ``flash_fwd``
    beside one ``flash_dq`` and one ``flash_dkv``: an XLA that made the
    forward kernel again behind the policy's back would show here. Full
    remat is the control that the count sees a second forward."""
    import re

    import jax
    import jax.numpy as jnp

    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.models.transformer import TransformerConfig

    dist.set_mesh(None)
    m = CausalLM(TransformerConfig(
        vocab_size=1024, max_seq=2048, n_layer=2, n_head=16, d_model=1024,
        pos_embedding="alibi", norm="layernorm", activation="gelu",
        tie_embeddings=True, embed_layernorm=True, attn_bias=True,
        remat=remat))
    assert m.config.scan_layers
    p = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16),
                     jax.eval_shape(m.init_params, jax.random.key(0)))
    b = {"input_ids": jax.ShapeDtypeStruct((2, 2048), jnp.int32)}
    text = jax.jit(jax.grad(lambda p, b: m.loss(p, b))).lower(p, b) \
        .compile().as_text()
    kernels = [re.match(r"\s*%?([A-Za-z_]+)", line).group(1)
               for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    flash = sorted(k for k in kernels if k.startswith("flash"))
    assert flash == ["flash_dkv", "flash_dq"] + ["flash_fwd"] * forwards, kernels


# --------------------------------------------------------------------- #
# Fused logits-free cross-entropy: numerics run in the DEFAULT CPU tier
# (interpret mode); the class is deliberately NOT tpu-marked.


class TestFusedCrossEntropy:
    @staticmethod
    def _ref(h, w, b, labels, valid):
        """XLA logsumexp reference — the exact math chunked_vocab_ce runs."""
        import jax
        import jax.numpy as jnp
        D = h.shape[-1]
        logits = (h.astype(jnp.float32).reshape(-1, D) @ w.astype(jnp.float32)
                  + b.astype(jnp.float32))
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels.reshape(-1)[:, None],
                                   axis=-1)[:, 0]
        vf = valid.reshape(-1).astype(jnp.float32)
        return jnp.sum((lse - gold) * vf) / jnp.maximum(jnp.sum(vf), 1)

    @staticmethod
    def _case(seed, B, S, D, V, dtype, mask_frac=0.3):
        import jax.numpy as jnp
        rng = np.random.default_rng(seed)
        h = jnp.asarray(rng.normal(size=(B, S, D)), dtype)
        w = jnp.asarray(rng.normal(size=(D, V)) * 0.1, dtype)
        b = jnp.asarray(rng.normal(size=(V,)) * 0.1, jnp.float32)
        labels = jnp.asarray(rng.integers(0, V, size=(B, S)), jnp.int32)
        valid = jnp.asarray(rng.random((B, S)) > mask_frac)
        return h, w, b, labels, valid

    @pytest.mark.parametrize("B,S,D,V", [
        (2, 16, 32, 96),     # single tile
        (2, 300, 64, 1200),  # multiple ragged token AND vocab tiles
        (1, 77, 48, 517),    # nothing divides anything
    ])
    def test_forward_matches_xla_fp32(self, B, S, D, V):
        import jax.numpy as jnp
        from deepspeed_tpu.ops.pallas.fused_cross_entropy import fused_cross_entropy

        h, w, b, labels, valid = self._case(0, B, S, D, V, jnp.float32)
        out = fused_cross_entropy(h, w, labels, bias=b, valid=valid,
                                  interpret=True)
        ref = self._ref(h, w, b, labels, valid)
        assert abs(float(out) - float(ref)) < 1e-5, (float(out), float(ref))

    def test_backward_matches_xla_fp32(self):
        import jax
        import jax.numpy as jnp
        from deepspeed_tpu.ops.pallas.fused_cross_entropy import fused_cross_entropy

        h, w, b, labels, valid = self._case(1, 2, 300, 64, 1200, jnp.float32)
        gk = jax.grad(lambda h, w, b: fused_cross_entropy(
            h, w, labels, bias=b, valid=valid, interpret=True),
            argnums=(0, 1, 2))(h, w, b)
        gr = jax.grad(lambda h, w, b: self._ref(h, w, b, labels, valid),
                      argnums=(0, 1, 2))(h, w, b)
        for name, a, r in zip("h w bias".split(), gk, gr):
            err = float(jnp.abs(a - r).max())
            assert err < 1e-5, (name, err)

    def test_forward_backward_bf16_inputs(self):
        import jax
        import jax.numpy as jnp
        from deepspeed_tpu.ops.pallas.fused_cross_entropy import fused_cross_entropy

        h, w, b, labels, valid = self._case(2, 2, 300, 64, 1200, jnp.bfloat16)
        out = fused_cross_entropy(h, w, labels, bias=b, valid=valid,
                                  interpret=True)
        ref = self._ref(h, w, b, labels, valid)
        assert abs(float(out) - float(ref)) < 2e-2

        gk = jax.grad(lambda h, w: fused_cross_entropy(
            h, w, labels, bias=b, valid=valid,
            interpret=True).astype(jnp.float32), argnums=(0, 1))(h, w)
        gr = jax.grad(lambda h, w: self._ref(h, w, b, labels, valid),
                      argnums=(0, 1))(h, w)
        for name, a, r in zip("h w".split(), gk, gr):
            err = float(jnp.abs((a - r).astype(jnp.float32)).max())
            assert err < 2e-2, (name, err)

    def _value_and_grads_match(self, h, w, b, labels, valid, tol=1e-5, **blocks):
        """Loss and all three gradients against ``_ref``, each within ``tol``
        of the reference's own scale."""
        import jax
        import jax.numpy as jnp
        from deepspeed_tpu.ops.pallas.fused_cross_entropy import fused_cross_entropy

        got = jax.value_and_grad(lambda h, w, b: fused_cross_entropy(
            h, w, labels, bias=b, valid=valid, interpret=True, **blocks),
            argnums=(0, 1, 2))(h, w, b)
        want = jax.value_and_grad(lambda h, w, b: self._ref(h, w, b, labels, valid),
                                  argnums=(0, 1, 2))(h, w, b)
        for name, a, r in zip("loss h w bias".split(), jax.tree.leaves(got),
                              jax.tree.leaves(want)):
            err = float(jnp.abs(a - r).max())
            assert err < tol * max(1.0, float(jnp.abs(r).max())), (name, err)

    @pytest.mark.parametrize("tokens,bt_fwd", [
        (1500, 512),   # Np 1,536 = 12 x 128 = 3 x 512: a tile of its own
        (1100, 128),   # Np 1,152 = 9 x 128: nothing larger divides it
    ])
    def test_forward_tile_of_its_own(self, tokens, bt_fwd):
        """The forward walks larger token tiles than the backward where the
        padded token count allows: value and gradients are the reference's,
        the pad rows of the last tile included."""
        import jax.numpy as jnp
        from deepspeed_tpu.ops import dispatch
        from deepspeed_tpu.ops.pallas.fused_cross_entropy import _tiles

        assert _tiles(tokens, 64, 700, 4, block_t=128) == (128, bt_fwd, 512, 512)
        h, w, b, labels, valid = self._case(6, 1, tokens, 64, 700, jnp.float32)
        dispatch.reset()
        self._value_and_grads_match(h, w, b, labels, valid, block_t=128)
        assert dispatch.details()["fused_ce_fwd=lane_state"] == (
            f"bt_fwd={bt_fwd} bt=128 bv=512")

    @pytest.mark.parametrize("order", ["rising", "falling"])
    def test_row_maximum_moves_between_lanes_and_blocks(self, order):
        """Columns 80 apart in logit, by vocab block (a block of large
        columns after blocks of small ones, and the reverse) and by lane
        inside a block: every lane's running maximum is rescaled on the way
        and the lanes meet at different maxima on the last step."""
        import jax.numpy as jnp

        h, w, b, labels, valid = self._case(7, 2, 150, 64, 1200, jnp.float32)
        cols = np.arange(1200)
        block, lane = cols // 256, cols % 128
        steps = block if order == "rising" else block.max() - block
        shift = 20.0 * steps + np.where(lane < 64, 0.0, -40.0) * (steps % 2)
        assert shift.max() - shift.min() >= 80
        self._value_and_grads_match(h, w, b + jnp.asarray(shift, jnp.float32),
                                    labels, valid, block_v=256)

    @pytest.mark.parametrize("V", [
        40,    # one block of 128: lanes 40-127 see pad columns alone
        520,   # the last block of 512 holds 8 real columns
    ])
    def test_labels_at_the_edges_of_the_vocabulary(self, V):
        """Labels in the first column, a block's last and next block's first
        column, and the last real column (the one before the pad)."""
        import jax.numpy as jnp

        h, w, b, _, valid = self._case(8, 2, 60, 32, V, jnp.float32)
        edges = np.asarray(sorted({0, min(511, V - 2), min(512, V - 1), V - 1}))
        labels = jnp.asarray(edges[np.arange(120) % len(edges)].reshape(2, 60),
                             jnp.int32)
        self._value_and_grads_match(h, w, b, labels, valid)

    @pytest.mark.parametrize("tokens,D,V,dtype,tiles", [
        (4 * 2048, 1024, 250880, "bfloat16", "bt_fwd=1024 bt=256 bv=512"),
        (32 * 1024, 768, 50257, "bfloat16", "bt_fwd=1024 bt=256 bv=512"),
        (4096, 4096, 32000, "bfloat16", "bt_fwd=1024 bt=128 bv=256"),
        (100, 128, 517, "float32", "bt_fwd=104 bt=104 bv=512"),
    ])
    def test_dispatch_site_reports_the_forwards_form_and_tile(
            self, tokens, D, V, dtype, tiles):
        """D, N, V and the dtype decide the forward's tile; the site says
        which was taken (shapes only: no kernel runs)."""
        import jax
        import jax.numpy as jnp
        from deepspeed_tpu.ops import dispatch
        from deepspeed_tpu.ops.pallas.fused_cross_entropy import fused_cross_entropy

        dispatch.reset()
        out = jax.eval_shape(
            lambda h, w, labels: fused_cross_entropy(h, w, labels, interpret=True),
            jax.ShapeDtypeStruct((tokens, D), jnp.dtype(dtype)),
            jax.ShapeDtypeStruct((D, V), jnp.dtype(dtype)),
            jax.ShapeDtypeStruct((tokens,), jnp.int32))
        assert out.shape == () and out.dtype == jnp.float32
        assert dispatch.selected()["fused_ce_fwd=lane_state"] == 1
        assert dispatch.details()["fused_ce_fwd=lane_state"] == tiles

    def test_masked_labels_and_empty_mask(self):
        import jax.numpy as jnp
        from deepspeed_tpu.ops.pallas.fused_cross_entropy import fused_cross_entropy

        h, w, b, labels, _ = self._case(3, 2, 24, 32, 96, jnp.float32)
        # heavy masking (ignore-index style: labels already clamped to 0)
        valid = jnp.asarray(np.random.default_rng(3).random((2, 24)) > 0.9)
        out = fused_cross_entropy(h, w, labels, bias=b, valid=valid,
                                  interpret=True)
        ref = self._ref(h, w, b, labels, valid)
        assert abs(float(out) - float(ref)) < 1e-5
        # all-masked batch: 0 loss, finite (no 0/0), matching _token_ce
        z = fused_cross_entropy(h, w, labels, bias=b,
                                valid=jnp.zeros((2, 24), bool), interpret=True)
        assert float(z) == 0.0

    def test_grad_through_custom_vjp_under_jit(self):
        """jit(grad(...)) through the custom_vjp, no bias, no mask — the
        tied-embedding lm_loss shape (grads flow through w's transpose)."""
        import jax
        import jax.numpy as jnp
        from deepspeed_tpu.ops.pallas.fused_cross_entropy import fused_cross_entropy

        h, _, _, labels, _ = self._case(4, 2, 40, 32, 96, jnp.float32)
        rng = np.random.default_rng(5)
        embed = jnp.asarray(rng.normal(size=(96, 32)) * 0.1, jnp.float32)

        def fused(h, e):
            return fused_cross_entropy(h, e.T, labels, interpret=True)

        def ref(h, e):
            return self._ref(h, e.T, jnp.zeros((96,)), labels,
                             jnp.ones(labels.shape, bool))

        la, ga = jax.jit(jax.value_and_grad(fused, argnums=(0, 1)))(h, embed)
        lr, gr = jax.jit(jax.value_and_grad(ref, argnums=(0, 1)))(h, embed)
        assert abs(float(la) - float(lr)) < 1e-5
        for name, a, r in zip("h embed".split(), ga, gr):
            err = float(jnp.abs(a - r).max())
            assert err < 1e-5, (name, err)

    def test_lm_loss_fused_matches_chunked(self):
        """End-to-end dispatch: lm_loss with fused_cross_entropy='on'
        (interpret mode on CPU) equals the 'off' XLA streaming path, values
        AND grads — the default-selection contract of vocab_head_ce."""
        import dataclasses

        import jax
        import jax.numpy as jnp
        from deepspeed_tpu.models.transformer import (TransformerConfig,
                                                      init_params, lm_loss)

        cfg = TransformerConfig(vocab_size=135, n_layer=2, n_head=2,
                                d_model=32, max_seq=24, remat=False,
                                attention_backend="xla",
                                fused_cross_entropy="off", loss_chunk=16)
        params = init_params(cfg, jax.random.key(0))
        rng = np.random.default_rng(0)
        batch = {"input_ids": jnp.asarray(rng.integers(0, 135, size=(2, 24)),
                                          jnp.int32)}
        cfg_on = dataclasses.replace(cfg, fused_cross_entropy="on")
        l_off, g_off = jax.value_and_grad(lambda p: lm_loss(cfg, p, batch))(params)
        l_on, g_on = jax.value_and_grad(lambda p: lm_loss(cfg_on, p, batch))(params)
        assert abs(float(l_off) - float(l_on)) < 1e-5
        err = max(float(jnp.abs(a - b).max()) for a, b in
                  zip(jax.tree.leaves(g_off), jax.tree.leaves(g_on)))
        assert err < 1e-5, err

    @pytest.mark.slow
    def test_bert_mlm_fused_matches_chunked(self):
        """BERT MLM head (decoder bias + ignore-index labels + gather
        budget): fused vs XLA paths agree."""
        import dataclasses

        import jax
        import jax.numpy as jnp
        from deepspeed_tpu.models.bert import BertConfig, BertModel

        bc = BertConfig(vocab_size=211, max_seq=16, n_layer=2, n_head=2,
                        d_model=32, d_ff=64, remat=False,
                        attention_backend="xla", mlm_gather_budget=0.5,
                        fused_cross_entropy="off")
        m = BertModel(bc, with_mlm_head=True)
        p = m.init_params(jax.random.key(1))
        rng = np.random.default_rng(2)
        ids = rng.integers(0, 211, size=(2, 16)).astype(np.int32)
        labels = np.full_like(ids, -100)
        pos = rng.random((2, 16)) < 0.15
        labels[pos] = ids[pos]
        batch = {"input_ids": jnp.asarray(ids), "labels": jnp.asarray(labels)}

        for budget in (0.5, 0.0):
            m.config = dataclasses.replace(bc, mlm_gather_budget=budget)
            l_off = m.loss(p, batch)
            m.config = dataclasses.replace(bc, mlm_gather_budget=budget,
                                           fused_cross_entropy="on")
            l_on = m.loss(p, batch)
            assert abs(float(l_off) - float(l_on)) < 1e-5, budget


@tpu_tier
def test_fused_cross_entropy_compiles_and_matches(tpu):
    """Mosaic lowering of the fused CE kernel on real hardware (the CPU tier
    above covers numerics in interpret mode only): fwd + bwd vs the XLA
    logsumexp reference, on a ragged sub-tile token count (bt < 128 path)
    AND a multi-tile bf16 shape — the row BlockSpecs, VMEM scratch
    broadcasts, and the transposed dw grid are exactly what interpret mode
    cannot validate."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas.fused_cross_entropy import fused_cross_entropy

    for seed, (B, S, D, V), dtype, tol in [
        (0, (2, 50, 128, 517), jnp.float32, 1e-4),     # ragged bt=104-ish
        (1, (2, 300, 256, 1200), jnp.bfloat16, 2e-2),  # multi-tile bf16
        # a 7B-class head: the forward's (1024, 4096) token tile beside the
        # backward's 128 rows, 24 MB of VMEM asked for
        (2, (2, 1024, 4096, 32000), jnp.bfloat16, 2e-2),
    ]:
        h, w, b, labels, valid = TestFusedCrossEntropy._case(seed, B, S, D, V,
                                                             dtype)
        out = fused_cross_entropy(h, w, labels, bias=b, valid=valid,
                                  interpret=False)
        ref = TestFusedCrossEntropy._ref(h, w, b, labels, valid)
        assert abs(float(out) - float(ref)) < tol, (dtype, float(out), float(ref))

        gk = jax.grad(lambda h, w: fused_cross_entropy(
            h, w, labels, bias=b, valid=valid,
            interpret=False).astype(jnp.float32), argnums=(0, 1))(h, w)
        gr = jax.grad(lambda h, w: TestFusedCrossEntropy._ref(h, w, b, labels,
                                                              valid),
                      argnums=(0, 1))(h, w)
        for name, a, r in zip("h w".split(), gk, gr):
            err = float(jnp.abs((a - r).astype(jnp.float32)).max())
            assert err < tol, (dtype, name, err)


@tpu_tier
def test_fused_cross_entropy_gpt2_width(tpu):
    """The fused CE kernels at the shape the GPT-2 train step runs them:
    D 768, V 50,257 (ragged: pads to 50,688), N = 32 x 1024 bf16 tokens,
    forward and gradients, vs the XLA ``loss_chunk`` streaming path (the
    [N, V] fp32 logits of a one-shot reference would not fit next to it)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.transformer import chunked_vocab_ce
    from deepspeed_tpu.ops.pallas.fused_cross_entropy import fused_cross_entropy

    B, S, D, V = 32, 1024, 768, 50257
    rng = np.random.default_rng(11)
    h = jnp.asarray(rng.normal(size=(B, S, D)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(D, V)) * 0.02, jnp.bfloat16)
    labels = jnp.asarray(rng.integers(0, V, size=(B, S)), jnp.int32)
    valid = jnp.asarray(rng.random((B, S)) > 0.1)

    fused = jax.jit(jax.value_and_grad(
        lambda h, w: fused_cross_entropy(h, w, labels, valid=valid,
                                         interpret=False), argnums=(0, 1)))
    ref = jax.jit(jax.value_and_grad(
        lambda h, w: chunked_vocab_ce(h, w, 0, labels, valid, 2048),
        argnums=(0, 1)))
    lf, gf = fused(h, w)
    lr, gr = ref(h, w)
    assert np.isfinite(float(lf))
    assert abs(float(lf) - float(lr)) < 2e-2, (float(lf), float(lr))
    for name, a, r in zip(("dh", "dw"), gf, gr):
        assert a.shape == r.shape and a.dtype == r.dtype, name
        a32, r32 = a.astype(jnp.float32), r.astype(jnp.float32)
        assert bool(jnp.isfinite(a32).all()), name
        # both sides are bf16 pipelines: bound the drift by the gradient's
        # own scale (elementwise bf16 rounding is ~0.4% of magnitude)
        tol = 0.03 * float(jnp.abs(r32).max())
        err = float(jnp.abs(a32 - r32).max())
        assert err < tol, (name, err, tol)


@tpu_tier
def test_fused_cross_entropy_bloom_width(tpu):
    """The fused CE kernels at the shape ``bloom560m_train_1chip`` runs
    them, the one cell of the benchmark that does: D 1,024, V 250,880,
    N = 4 x 2,048 bf16 tokens, a tenth masked; forward (token tiles of
    1,024 rows, four of the backward's) and gradients, vs the XLA
    ``loss_chunk`` streaming path at the GPT-2 width's tolerances."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.transformer import chunked_vocab_ce
    from deepspeed_tpu.ops import dispatch
    from deepspeed_tpu.ops.pallas.fused_cross_entropy import fused_cross_entropy

    B, S, D, V = 4, 2048, 1024, 250880
    rng = np.random.default_rng(12)
    h = jnp.asarray(rng.normal(size=(B, S, D)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(D, V)) * 0.02, jnp.bfloat16)
    labels = jnp.asarray(rng.integers(0, V, size=(B, S)), jnp.int32)
    valid = jnp.asarray(rng.random((B, S)) > 0.1)

    fused = jax.jit(jax.value_and_grad(
        lambda h, w: fused_cross_entropy(h, w, labels, valid=valid,
                                         interpret=False), argnums=(0, 1)))
    ref = jax.jit(jax.value_and_grad(
        lambda h, w: chunked_vocab_ce(h, w, 0, labels, valid, 2048),
        argnums=(0, 1)))
    dispatch.reset()
    lf, gf = fused(h, w)
    assert dispatch.details()["fused_ce_fwd=lane_state"] == (
        "bt_fwd=1024 bt=256 bv=512")
    lr, gr = ref(h, w)
    assert np.isfinite(float(lf))
    assert abs(float(lf) - float(lr)) < 2e-2, (float(lf), float(lr))
    for name, a, r in zip(("dh", "dw"), gf, gr):
        assert a.shape == r.shape and a.dtype == r.dtype, name
        a32, r32 = a.astype(jnp.float32), r.astype(jnp.float32)
        assert bool(jnp.isfinite(a32).all()), name
        # both sides are bf16 pipelines: bound the drift by the gradient's
        # own scale (elementwise bf16 rounding is ~0.4% of magnitude)
        tol = 0.03 * float(jnp.abs(r32).max())
        err = float(jnp.abs(a32 - r32).max())
        assert err < tol, (name, err, tol)
