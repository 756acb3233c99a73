"""The latent paged decode kernel (``ops/pallas/latent_decode_attention.py``)
in interpret mode against the plain form: the rows gathered through the
table, scores over all their lanes, values their first ``latent`` lanes, a
float32 softmax. Randomised block tables, rows at different depths (one at
position 0, one at a block's last slot, one past a group of blocks), dead
table entries pointing at blocks full of NaN. The compiled kernel at the
cell's shapes is ``test_tpu_kernels.py``'s (``-m tpu``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas.latent_decode_attention import (
    _group_blocks, latent_decode_attention, latent_envelope_ok)


def plain(q, cp, bt, pos, latent, scale):
    B = q.shape[0]
    c = cp[bt].reshape(B, -1, cp.shape[-1]).astype(jnp.float32)
    s = jnp.einsum("bhr,bsr->bhs", q.astype(jnp.float32), c,
                   precision="highest") * scale
    kpos = jnp.arange(c.shape[1])[None, None, :]
    s = jnp.where(kpos <= pos[:, None, None], s, -jnp.inf)
    return jnp.einsum("bhs,bsr->bhr", jax.nn.softmax(s, axis=-1),
                      c[..., :latent], precision="highest")


def case(seed, B, H, latent, rope, bs, n_blocks, width, pos, dtype):
    rng = np.random.default_rng(seed)
    row = latent + rope
    cp = rng.standard_normal((n_blocks, bs, row)).astype(np.float32)
    cp[0] = np.nan                       # nothing live ever points here
    q = rng.standard_normal((B, H, row)).astype(np.float32)
    bt = np.zeros((B, width), np.int32)
    free = list(rng.permutation(np.arange(1, n_blocks)))
    for b in range(B):
        for j in range(pos[b] // bs + 1):
            bt[b, j] = free.pop()
    return (jnp.asarray(q, dtype), jnp.asarray(cp, dtype), jnp.asarray(bt),
            jnp.asarray(pos, jnp.int32))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("shape", [
    # (B, H, latent, lanes after it, bs, blocks, table width, positions)
    (4, 8, 128, 128, 128, 40, 12, (0, 127, 700, 1500)),
    (3, 16, 256, 128, 128, 30, 5, (128, 639, 300)),
    (2, 64, 512, 128, 128, 24, 9, (1100, 1)),         # the cell's row of 640
])
def test_the_kernel_is_the_plain_form(shape, dtype, tol):
    B, H, latent, rope, bs, n_blocks, width, pos = shape
    q, cp, bt, p = case(B + H, B, H, latent, rope, bs, n_blocks, width,
                        np.array(pos), dtype)
    scale = (latent // 4 + rope) ** -0.5
    got = latent_decode_attention(q, cp, bt, p, latent=latent, scale=scale,
                                  interpret=True)
    assert got.shape == (B, H, latent) and got.dtype == dtype
    # dead blocks hold NaN only in block 0: the plain form reads entry 0 of
    # dead table slots, so it is given a pool whose block 0 is zeros
    want = plain(q, cp.at[0].set(0), bt, p, latent, scale)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=0, atol=tol)


def test_the_envelope_and_the_group():
    assert latent_envelope_ok(64, 512, 640, 128)
    assert latent_envelope_ok(8, 128, 256, 128)
    assert not latent_envelope_ok(64, 512, 640, 16)       # the CPU tier's blocks
    assert not latent_envelope_ok(4, 128, 256, 128)       # half a sublane tile
    assert not latent_envelope_ok(64, 512, 576, 128)      # half a lane tile
    q = jnp.zeros((1, 4, 256))
    assert latent_decode_attention(q, jnp.zeros((4, 128, 256)),
                                   jnp.zeros((1, 2), jnp.int32),
                                   jnp.zeros((1,), jnp.int32), latent=128,
                                   scale=1.0, interpret=True) is None
    # groups: what two slots of one pool hold of the stream budget, a power
    # of two, never more than a table is wide
    assert _group_blocks(128, 640, 2, 36) == 8
    assert _group_blocks(128, 640, 2, 5) == 4
    assert _group_blocks(128, 256, 4, 36) == 8
