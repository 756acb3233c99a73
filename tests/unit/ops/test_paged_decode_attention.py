"""Paged decode-attention kernel vs the dense ``decode_attention`` kernel
and the einsum reference, on randomized block tables (interpret mode on the
CPU tier). The ISSUE acceptance pin: parity 1e-5 (fp32) / 2e-2 (bf16)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import dispatch
from deepspeed_tpu.ops.pallas.decode_attention import decode_attention
from deepspeed_tpu.ops.pallas.paged_decode_attention import \
    paged_decode_attention


def random_paged_case(r, B, KV, Hd, bs, n_max, dtype=jnp.float32, group=None):
    """Pools ``[num_blocks, bs, KV*Hd]`` (the layout
    ``init_paged_kv_cache`` gives one layer) + per-request non-overlapping
    random block tables + positions."""
    H = KV * (int(r.choice([1, 2, 4])) if group is None else group)
    num_blocks = B * n_max + 1
    kp = jnp.asarray(r.normal(size=(num_blocks, bs, KV * Hd)), dtype)
    vp = jnp.asarray(r.normal(size=(num_blocks, bs, KV * Hd)), dtype)
    q = jnp.asarray(r.normal(size=(B, H, Hd)), dtype)
    perm = r.permutation(num_blocks - 1) + 1  # dummy block 0 never mapped
    bt = jnp.asarray(perm[:B * n_max].reshape(B, n_max), jnp.int32)
    pos = jnp.asarray(r.integers(0, n_max * bs, size=B), jnp.int32)
    return q, kp, vp, bt, pos


def gather_dense(pool, bt, Hd):
    """Dense per-request cache ``[B, S, KV, Hd]`` via the block table (the
    reference layout decode_attention expects)."""
    return pool[bt].reshape(bt.shape[0], -1, pool.shape[2] // Hd, Hd)


@pytest.mark.parametrize("seed", range(4))
def test_paged_matches_dense_kernel(seed):
    """Kernel parity vs decode_attention per request on random tables."""
    r = np.random.default_rng(200 + seed)
    B = int(r.integers(1, 4))
    KV = int(r.choice([1, 2, 4]))
    Hd = int(r.choice([64, 128]))
    n_max = int(r.integers(1, 5))
    q, kp, vp, bt, pos = random_paged_case(r, B, KV, Hd, 128, n_max)
    with_bias = bool(r.integers(0, 2))
    with_alibi = bool(r.integers(0, 2))
    H = q.shape[1]
    bias = (jnp.asarray(r.normal(size=(B, n_max * 128)) * 0.2, jnp.float32)
            if with_bias else None)
    slopes = (jnp.asarray(r.uniform(0.05, 0.4, size=H), jnp.float32)
              if with_alibi else None)

    out = paged_decode_attention(q, kp, vp, bt, pos, pad_bias=bias,
                                 alibi_slopes=slopes)
    ck, cv = gather_dense(kp, bt, q.shape[2]), gather_dense(vp, bt, q.shape[2])
    for b in range(B):
        want = decode_attention(
            q[b:b + 1], ck[b:b + 1], cv[b:b + 1], int(pos[b]),
            pad_bias=None if bias is None else bias[b:b + 1],
            alibi_slopes=slopes)
        err = float(jnp.abs(out[b] - want[0]).max())
        assert err < 1e-5, (seed, b, err)


@pytest.mark.parametrize("KV,group,Hd", [
    (2, 4, 128),    # KV < H at Hd 128: each kv head is one whole lane tile
    (4, 1, 64),     # Hd 64: odd kv heads are the upper HALF of a lane tile
])
def test_paged_lane_slice_per_head(KV, group, Hd):
    """The pool row holds a token's kv heads side by side; kv head ``g`` is
    the lane slice ``[g*Hd, (g+1)*Hd)``. Every head's output is checked on
    its own against the dense kernel fed that head's slice alone, so a
    slice that read a neighbour's lanes could not hide in a max over heads
    (at Hd 64 heads 1 and 3 start mid-tile)."""
    r = np.random.default_rng(31 + Hd)
    B, n_max = 2, 3
    q, kp, vp, bt, pos = random_paged_case(r, B, KV, Hd, 128, n_max,
                                           group=group)
    out = paged_decode_attention(q, kp, vp, bt, pos)
    ck, cv = gather_dense(kp, bt, Hd), gather_dense(vp, bt, Hd)
    for b in range(B):
        for g in range(KV):
            heads = slice(g * group, (g + 1) * group)
            want = decode_attention(q[b:b + 1, heads], ck[b:b + 1, :, g:g + 1],
                                    cv[b:b + 1, :, g:g + 1], int(pos[b]))
            err = float(jnp.abs(out[b, heads] - want[0]).max())
            assert err < 1e-5, (b, g, err)


def test_paged_bf16_pools():
    r = np.random.default_rng(9)
    q, kp, vp, bt, pos = random_paged_case(r, 2, 2, 64, 128, 3,
                                           dtype=jnp.bfloat16)
    out = paged_decode_attention(q, kp, vp, bt, pos)
    assert out.dtype == jnp.bfloat16
    ck, cv = gather_dense(kp, bt, q.shape[2]), gather_dense(vp, bt, q.shape[2])
    for b in range(2):
        want = decode_attention(q[b:b + 1].astype(jnp.float32),
                                ck[b:b + 1].astype(jnp.float32),
                                cv[b:b + 1].astype(jnp.float32), int(pos[b]))
        err = float(jnp.abs(out[b].astype(jnp.float32) - want[0]).max())
        assert err < 2e-2, (b, err)


def test_paged_per_request_positions_differ():
    """Requests at very different depths share one fused call — each row
    must mask strictly by ITS OWN pos (first token vs nearly-full table)."""
    r = np.random.default_rng(11)
    q, kp, vp, bt, _ = random_paged_case(r, 3, 2, 64, 128, 4)
    pos = jnp.asarray([0, 200, 511], jnp.int32)
    out = paged_decode_attention(q, kp, vp, bt, pos)
    ck, cv = gather_dense(kp, bt, q.shape[2]), gather_dense(vp, bt, q.shape[2])
    for b in range(3):
        want = decode_attention(q[b:b + 1], ck[b:b + 1], cv[b:b + 1],
                                int(pos[b]))
        assert float(jnp.abs(out[b] - want[0]).max()) < 1e-5


def test_paged_shared_pool_isolation():
    """Two requests interleaved in one pool: permuting BOTH tables the same
    way only relabels storage — outputs must be identical (no request reads
    another's blocks)."""
    r = np.random.default_rng(13)
    q, kp, vp, bt, pos = random_paged_case(r, 2, 2, 64, 128, 3)
    out = paged_decode_attention(q, kp, vp, bt, pos)
    # swap two pool blocks AND fix both tables accordingly
    a, b = 1, 2
    swap = jnp.arange(kp.shape[0]).at[a].set(b).at[b].set(a)
    out2 = paged_decode_attention(q, kp[swap], vp[swap], swap[bt], pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2),
                               rtol=1e-6, atol=1e-6)


def test_paged_envelope_fallback():
    """Each envelope rejection independently returns None."""
    # block size not 128-aligned
    q = jnp.zeros((1, 4, 64), jnp.float32)
    kp = jnp.zeros((3, 64, 4 * 64), jnp.float32)
    bt = jnp.zeros((1, 2), jnp.int32)
    assert paged_decode_attention(q, kp, kp, bt, jnp.zeros(1, jnp.int32)) is None
    # head dim not lane-aligned
    q = jnp.zeros((1, 4, 48), jnp.float32)
    kp = jnp.zeros((3, 128, 4 * 48), jnp.float32)
    assert paged_decode_attention(q, kp, kp, bt, jnp.zeros(1, jnp.int32)) is None
    # a pool row that is not whole lane tiles (MQA at Hd 64)
    q = jnp.zeros((1, 4, 64), jnp.float32)
    kp = jnp.zeros((3, 128, 64), jnp.float32)
    assert paged_decode_attention(q, kp, kp, bt, jnp.zeros(1, jnp.int32)) is None


def test_paged_traced_pos_and_tables():
    """pos and block tables may be traced (the serving decode jit carries
    them as arguments, not constants)."""
    r = np.random.default_rng(17)
    q, kp, vp, bt, pos = random_paged_case(r, 2, 2, 64, 128, 2)

    @jax.jit
    def f(bt, pos):
        return paged_decode_attention(q, kp, vp, bt, pos)

    out = f(bt, pos)
    ck, cv = gather_dense(kp, bt, q.shape[2]), gather_dense(vp, bt, q.shape[2])
    for b in range(2):
        want = decode_attention(q[b:b + 1], ck[b:b + 1], cv[b:b + 1],
                                int(pos[b]))
        assert float(jnp.abs(out[b] - want[0]).max()) < 1e-5


# --------------------------------------------------------------------- #
# The streaming form: grid over rows, a row's LIVE blocks copied by the
# kernel itself in groups of G, all heads of a group in one product.

def paged_reference(q, kp, vp, bt, pos, bias=None, slopes=None, window=0):
    """float32 gather + softmax over each row's live blocks. Dead table
    entries are taken off before the gather: what they name is not read.
    q [B, Q, H, Hd]: a row's Q positions read the same keys. ``window``: the
    band ``pos - window < kpos <= pos`` over a table as long as the row
    (logical block j at entry j: a ring that never wrapped). A row whose
    first live entry names the dummy block 0 holds no request: zeros."""
    if q.ndim == 4:
        return jax.vmap(lambda one: paged_reference(one, kp, vp, bt, pos,
                                                    bias, slopes, window),
                        in_axes=1, out_axes=1)(q)
    B, H, Hd = q.shape
    bs, KV = kp.shape[1], kp.shape[2] // Hd
    first = jnp.maximum(pos - (window - 1), 0) // bs if window \
        else jnp.zeros_like(pos)
    entries = jnp.arange(bt.shape[1])[None, :]
    live = (entries >= first[:, None]) & (entries <= (pos // bs)[:, None])
    idle = bt[jnp.arange(B), first] == 0
    bt = jnp.where(live, bt, 0)
    k, v = (jnp.repeat(pool[bt].reshape(B, -1, KV, Hd).astype(jnp.float32),
                       H // KV, axis=2) for pool in (kp, vp))
    s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32) * Hd**-0.5, k,
                   precision="highest")
    kpos = jnp.arange(k.shape[1])[None, None, :]
    qpos = pos[:, None, None]
    if slopes is not None:
        s = s + slopes[None, :, None] * (kpos - qpos)
    if bias is not None:
        s = s + bias[:, None, :]
    keep = kpos <= qpos
    if window:
        keep = keep & (kpos > qpos - window)
    s = jnp.where(keep, s, -1e30)
    out = jnp.einsum("bhs,bshd->bhd", jax.nn.softmax(s, axis=-1), v,
                     precision="highest")
    return jnp.where(idle[:, None, None], 0.0, out)


def force_group(monkeypatch, G):
    """Blocks a loop iteration (the program takes them from the shapes: at
    these toy widths a whole table would be one group)."""
    import importlib
    mod = importlib.import_module(
        "deepspeed_tpu.ops.pallas.paged_decode_attention")
    monkeypatch.setattr(mod, "_group_blocks", lambda *a: G)


def max_err(out, want):
    err = jnp.abs(out.astype(jnp.float32) - want).max()
    return float(jnp.where(jnp.isnan(err), jnp.inf, err))


@pytest.mark.parametrize("G", [1, 2, 3])
def test_live_blocks_one_a_multiple_of_the_group_and_full_width(G, monkeypatch):
    """One batch holds rows of 1 live block, exactly G, G + 1, 2 G and the
    table's whole width: the loop's trip count is each row's own, the last
    group is full, partly filled, or the only one."""
    force_group(monkeypatch, G)
    r = np.random.default_rng(40 + G)
    n_max, bs = 6, 128
    lives = [1, G, G + 1, 2 * G, n_max]
    q, kp, vp, bt, _ = random_paged_case(r, len(lives), 2, 64, bs, n_max,
                                         group=2)
    pos = jnp.asarray([n * bs - 1 - int(r.integers(0, bs)) for n in lives],
                      jnp.int32)
    assert (np.asarray(pos) // bs + 1).tolist() == lives
    out = paged_decode_attention(q, kp, vp, bt, pos)
    assert max_err(out, paged_reference(q, kp, vp, bt, pos)) < 1e-5


@pytest.mark.parametrize("G", [1, 2])
def test_dead_tail_is_never_copied(G, monkeypatch):
    """Dead table entries hold ids beyond the pool and the id of a block of
    NaNs (where an index clamped by the interpreter would land too): a copy
    of either would reach the output through 0 x NaN."""
    force_group(monkeypatch, G)
    r = np.random.default_rng(50 + G)
    n_max, bs = 5, 128
    q, kp, vp, bt, _ = random_paged_case(r, 4, 2, 64, bs, n_max, group=1)
    last = kp.shape[0] - 1
    kp, vp = kp.at[last].set(jnp.nan), vp.at[last].set(jnp.nan)
    pos = np.asarray([5, 130, 300, 511], np.int32)
    bt = np.array(bt)
    bt[bt == last] = 1                     # no LIVE entry names the NaNs
    for b in range(4):
        tail = slice(int(pos[b]) // bs + 1, None)
        bt[b, tail] = [last, 10**6, -7, 2**31 - 1][b]
    bt, pos = jnp.asarray(bt), jnp.asarray(pos)
    out = paged_decode_attention(q, kp, vp, bt, pos)
    assert max_err(out, paged_reference(q, kp, vp, bt, pos)) < 1e-5


@pytest.mark.parametrize("G", [1, 2])
def test_pos_at_a_blocks_first_and_last_slot(G, monkeypatch):
    """The mask bites in a row's last block only, and there exactly at
    ``pos``: first slot of the table, last slot of a block, first slot of
    the next, the table's very last slot."""
    force_group(monkeypatch, G)
    r = np.random.default_rng(60 + G)
    n_max, bs = 4, 128
    edges = [0, bs - 1, bs, 2 * bs - 1, 2 * bs, n_max * bs - 1]
    q, kp, vp, bt, _ = random_paged_case(r, len(edges), 2, 64, bs, n_max,
                                         group=2)
    pos = jnp.asarray(edges, jnp.int32)
    out = paged_decode_attention(q, kp, vp, bt, pos)
    assert max_err(out, paged_reference(q, kp, vp, bt, pos)) < 1e-5


def test_shared_prefix_blocks_and_idle_rows_on_the_dummy_block(monkeypatch):
    """Rows 0 and 1 share their first two pool blocks (a cached prefix) and
    part; rows 2 and 4 are idle: position 0 under a zeroed table, so their
    first entry names the dummy block 0, with a decoding row between them."""
    force_group(monkeypatch, 2)
    r = np.random.default_rng(70)
    n_max, bs = 4, 128
    q, kp, vp, bt, _ = random_paged_case(r, 5, 2, 64, bs, n_max, group=2)
    bt = np.array(bt)
    bt[1, :2] = bt[0, :2]
    bt[2] = bt[4] = 0
    pos = jnp.asarray([300, 450, 0, 200, 0], jnp.int32)
    bt = jnp.asarray(bt)
    out = paged_decode_attention(q, kp, vp, bt, pos)
    assert max_err(out, paged_reference(q, kp, vp, bt, pos)) < 1e-5
    # an idle row reads nothing, the dummy block neither: zeros
    np.testing.assert_array_equal(np.asarray(out[jnp.asarray([2, 4])]), 0.0)


# --------------------------------------------------------------------- #
# Only live rows are copied: a row without a request (its first live table
# entry names the dummy block 0) starts no copy and takes no step.

IDLE_ROWS = {"start": [0], "end": [5], "middle": [2], "two_in_a_row": [2, 3],
             "both_ends_and_a_pair": [0, 1, 4, 5], "all": list(range(6))}


@pytest.mark.parametrize("kind", ["plain", "window", "positions",
                                  "stacked_pools"])
@pytest.mark.parametrize("G", [1, 2])
@pytest.mark.parametrize("idle", sorted(IDLE_ROWS))
def test_rows_without_a_request_read_nothing_and_yield_zeros(idle, G, kind,
                                                             monkeypatch):
    """Idle rows wherever they fall: their outputs are exactly zero, and the
    chain of first copies passes over them (a live row's first group is
    started by the live row before it, or by the call's first step), so every
    live row equals the reference. The dummy block holds NaN in both pools:
    a copy of it would reach an output."""
    force_group(monkeypatch, G)
    r = np.random.default_rng(130 + G)
    n_max, bs, Q = 5, 128, 4
    q, kp, vp, bt, pos = random_paged_case(r, 6, 2, 64, bs, n_max, group=2)
    kw = {}
    if kind == "positions":
        q = jnp.asarray(r.normal(size=(6, Q, 4, 64)), jnp.float32)
        pos = jnp.maximum(pos, Q - 1)
    elif kind == "window":
        kw["window"] = 200
    kp, vp = kp.at[0].set(jnp.nan), vp.at[0].set(jnp.nan)
    gone = jnp.asarray(IDLE_ROWS[idle])
    bt, pos = bt.at[gone].set(0), pos.at[gone].set(0)
    want = paged_reference(q, kp.at[0].set(0.0), vp.at[0].set(0.0), bt, pos,
                           **kw)
    if kind == "stacked_pools":
        # the SECOND layer of pools stacked over two: its blocks, the dummy
        # among them, lie one layer's blocks further on, and where the dummy
        # lies is an operand of the program (the layer scan's index)
        block0 = kp.shape[0]
        kp, vp = (jnp.concatenate([jnp.ones_like(pool), pool])
                  for pool in (kp, vp))
        out = jax.jit(lambda bt, dummy: paged_decode_attention(
            q, kp, vp, bt, pos, dummy_block=dummy))(bt + block0, block0)
    else:
        out = paged_decode_attention(q, kp, vp, bt, pos, **kw)
    np.testing.assert_array_equal(np.asarray(out[gone]), 0.0)
    assert max_err(out, want) < 1e-5


POISON_SHAPES = {"per_kv_head": (2, 8, 128), "block_diagonal": (2, 2, 64)}


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("form", sorted(POISON_SHAPES))
@pytest.mark.parametrize("depth", [0, 31, 32, 63, 64, 95, 96, 127])
def test_poisoned_dead_slots_are_not_read(depth, form, dtype, tol,
                                          monkeypatch):
    """Rows ``depth`` slots into their newest block, which is the first,
    second and third of their table (either member of a group of 2), and an
    idle row. NaN fills the whole dummy block in both pools and every KEY
    slot past ``pos`` in a row's newest block: the output is finite and the
    reference's on the clean pools. Before PR 49 the idle row copied the
    dummy block and its NaN reached its output. (The VALUE slots past
    ``pos`` stay pool rows: the newest block is copied whole, PERF.md
    section 6, PR 49, and a dead value meets p = 0 as a product.)"""
    force_group(monkeypatch, 2)
    KV, P, Hd = POISON_SHAPES[form]
    r = np.random.default_rng(140 + depth)
    bs, n_max = 128, 3
    q, kp, vp, bt, _ = random_paged_case(r, 4, KV, Hd, bs, n_max, dtype=dtype,
                                         group=P)
    pos = np.asarray([depth, bs + depth, 2 * bs + depth, 0], np.int32)
    bt = np.array(bt)
    bt[3] = 0
    want = paged_reference(q, kp, vp, jnp.asarray(bt), jnp.asarray(pos))
    kp, vp = kp.at[0].set(jnp.nan), vp.at[0].set(jnp.nan)
    for b in range(3):
        kp = kp.at[bt[b, pos[b] // bs], depth + 1:].set(jnp.nan)
    out = paged_decode_attention(q, kp, vp, jnp.asarray(bt), jnp.asarray(pos))
    assert out.dtype == dtype
    assert max_err(out, want) < tol


CELL_HEADS = [(32, 1, 64),      # OPT-1.3B: 32 heads of 64, a 2,048-lane row
              (16, 1, 128),     # OLMoE: 16 heads of 128
              (2, 4, 64),       # a GQA group of 4, kv heads half a lane tile
              (2, 4, 128)]


@pytest.mark.parametrize("terms", ["alibi", "pad_bias"])
@pytest.mark.parametrize("KV,group,Hd", CELL_HEADS)
def test_cell_head_shapes_with_alibi_and_pad_bias(KV, group, Hd, terms):
    """Both head shapes of the serving cells and a GQA group of 4, each
    with ALiBi slopes and with a key-side bias over logical positions, at
    the group size the program takes from the shapes."""
    r = np.random.default_rng(80 + KV + Hd)
    B, n_max, bs = 3, 3, 128
    q, kp, vp, bt, _ = random_paged_case(r, B, KV, Hd, bs, n_max, group=group)
    pos = jnp.asarray([100, 129, n_max * bs - 1], jnp.int32)
    H = KV * group
    slopes = bias = None
    if terms == "alibi":
        slopes = jnp.asarray(2.0 ** (-8.0 * np.arange(1, H + 1) / H),
                             jnp.float32)
    else:
        bias = np.zeros((B, n_max * bs), np.float32)
        bias[:, 1:40] = -1e9               # a left-padded batch's dead slots
        bias[:, 40:] = r.normal(size=(B, n_max * bs - 40)) * 0.2
        bias = jnp.asarray(bias)
    out = paged_decode_attention(q, kp, vp, bt, pos, pad_bias=bias,
                                 alibi_slopes=slopes)
    want = paged_reference(q, kp, vp, bt, pos, bias, slopes)
    assert max_err(out, want) < 1e-5


@pytest.mark.parametrize("KV,group,Hd", CELL_HEADS[:3])
def test_bf16_pools_against_float32_reference(KV, group, Hd):
    """The stored operands go to the MXU as bf16; probabilities, sums and
    the numerator stay float32. Against the float32 reference of the same
    bf16 values only the output's own rounding is left."""
    r = np.random.default_rng(90 + KV + Hd)
    q, kp, vp, bt, pos = random_paged_case(r, 3, KV, Hd, 128, 3,
                                           dtype=jnp.bfloat16, group=group)
    out = paged_decode_attention(q, kp, vp, bt, pos)
    assert out.dtype == jnp.bfloat16
    assert max_err(out, paged_reference(q, kp, vp, bt, pos)) < 2e-2


# --------------------------------------------------------------------- #
# The position axis: q [B, Q, H, Hd], a row's Q positions over the same
# keys. Q x P query rows a kv head: from _PER_KV_HEAD_MIN_ROWS of them (in
# whole sublane tiles, over a whole lane tile) the products are taken a kv
# head, below against the block-diagonal query.

POSITION_SHAPES = {              # (Q, KV, P, Hd): the form it takes
    (4, 4, 8, 128): "per_kv_head",       # SDAR: 32 rows a kv head
    (4, 2, 2, 128): "per_kv_head",       # 8 rows a kv head
    (4, 2, 1, 64): "block_diagonal",     # many positions, half a lane tile
    (1, 2, 8, 128): "per_kv_head",       # Solar-Open2's group at one position
}


def position_case(r, Q, KV, P, Hd, dtype, n_max=5, bs=128):
    """Rows of 1 live block, 2 and 4 (multiples of a group of 2), the
    table's whole width (an odd count: its last group is half filled) and
    an idle row at position 0 on the dummy block."""
    lives = [1, 2, 4, n_max, 1]
    q, kp, vp, bt, _ = random_paged_case(r, len(lives), KV, Hd, bs, n_max,
                                         dtype=dtype, group=P)
    q = jnp.asarray(r.normal(size=(len(lives), Q, KV * P, Hd)), dtype)
    pos = np.asarray([n * bs - 1 - int(r.integers(0, bs - Q)) for n in lives],
                     np.int32)
    pos[-1] = 0
    bt = np.array(bt)
    bt[-1] = 0
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(pos)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("Q,KV,P,Hd", sorted(POSITION_SHAPES))
def test_positions_of_a_row_read_the_same_keys(Q, KV, P, Hd, dtype, tol,
                                               monkeypatch):
    """Both forms of the products, float32 and bf16 pools, against the
    float32 gather + softmax, at a group of 2 blocks an iteration."""
    force_group(monkeypatch, 2)
    r = np.random.default_rng(100 + Q + KV + P + Hd)
    q, kp, vp, bt, pos = position_case(r, Q, KV, P, Hd, dtype)
    out = paged_decode_attention(q, kp, vp, bt, pos)
    assert out.shape == q.shape and out.dtype == dtype
    assert max_err(out, paged_reference(q, kp, vp, bt, pos)) < tol


@pytest.mark.parametrize("terms", ["alibi", "pad_bias"])
@pytest.mark.parametrize("Q,KV,P,Hd", [(4, 2, 2, 128), (4, 2, 1, 64)])
def test_positions_with_alibi_and_pad_bias(Q, KV, P, Hd, terms, monkeypatch):
    """A slope goes with a query row wherever the form put it; distances
    and the bias are the last position's, as the mask is."""
    force_group(monkeypatch, 2)
    r = np.random.default_rng(110 + P + Hd)
    q, kp, vp, bt, pos = position_case(r, Q, KV, P, Hd, jnp.float32)
    H = KV * P
    slopes = bias = None
    if terms == "alibi":
        slopes = jnp.asarray(2.0 ** (-8.0 * np.arange(1, H + 1) / H),
                             jnp.float32)
    else:
        bias = jnp.asarray(r.normal(size=(q.shape[0], bt.shape[1] * 128))
                           * 0.2, jnp.float32)
    out = paged_decode_attention(q, kp, vp, bt, pos, pad_bias=bias,
                                 alibi_slopes=slopes)
    want = paged_reference(q, kp, vp, bt, pos, bias, slopes)
    assert max_err(out, want) < 1e-5


@pytest.mark.parametrize("KV,P,Hd", [(2, 8, 128), (2, 1, 64)])
def test_one_position_on_the_axis_is_the_call_without_it(KV, P, Hd):
    """q [B, 1, H, Hd] and q [B, H, Hd] are one program: bit for bit."""
    r = np.random.default_rng(120 + Hd)
    q, kp, vp, bt, pos = random_paged_case(r, 3, KV, Hd, 128, 3, group=P)
    flat = paged_decode_attention(q, kp, vp, bt, pos)
    axis = paged_decode_attention(q[:, None], kp, vp, bt, pos)
    assert axis.shape == (3, 1, KV * P, Hd)
    np.testing.assert_array_equal(np.asarray(axis[:, 0]), np.asarray(flat))


@pytest.mark.parametrize("Q,KV,P,Hd", sorted(POSITION_SHAPES) + [
    (1, kv, group, hd) for kv, group, hd in CELL_HEADS])
def test_the_form_a_shape_took_is_recorded(Q, KV, P, Hd):
    """Static a shape, so a trace is all it takes: ``dispatch.selected()``
    names the form. One position of MHA (OPT, OLMoE) and a group of 4 stay
    on the block-diagonal query."""
    want = POSITION_SHAPES.get((Q, KV, P, Hd), "block_diagonal")
    sds = jax.ShapeDtypeStruct
    pool = sds((9, 128, KV * Hd), jnp.bfloat16)
    dispatch.reset()
    out = jax.eval_shape(
        paged_decode_attention, sds((2, Q, KV * P, Hd), jnp.bfloat16), pool,
        pool, sds((2, 4), jnp.int32), sds((2,), jnp.int32))
    assert out.shape == (2, Q, KV * P, Hd)
    forms = {k: n for k, n in dispatch.selected().items()
             if k.startswith("paged_decode_attention=")}
    assert forms == {f"paged_decode_attention={want}": 1}, forms


def test_every_layers_dummy_block_is_told_to_the_kernel():
    """Model level: the pools are stacked over layers, so a layer's tables
    and its dummy block lie ``layer x blocks`` further on; the decode step
    hands the kernel both. Every layer's dummy block holds NaN (but for the
    slot the idle row's own token is written to): the idle row's logits stay
    finite, so no layer's kernel copied its dummy block, and the live rows'
    are what they are over clean pools."""
    from deepspeed_tpu.models.causal_lm import CausalLM
    from deepspeed_tpu.models.transformer import TransformerConfig

    import deepspeed_tpu.comm as dist
    dist.set_mesh(None)
    cfg = TransformerConfig(vocab_size=128, max_seq=512, n_layer=3, n_head=4,
                            n_kv_head=2, d_model=256, pos_embedding="rope",
                            norm="rmsnorm", activation="swiglu", remat=False,
                            attention_backend="flash")
    model = CausalLM(cfg)
    params = model.init_params(jax.random.key(0))
    r = np.random.default_rng(29)
    pools = jax.tree.map(
        lambda a: jnp.asarray(r.normal(size=a.shape), a.dtype),
        model.init_paged_cache(6, 128, dtype=jnp.float32))
    bt = jnp.asarray([[3, 1], [0, 0], [2, 5]], jnp.int32)
    pos = jnp.asarray([200, 0, 130], jnp.int32)
    toks = jnp.asarray([[5], [0], [7]], jnp.int32)
    dispatch.reset()
    clean, _ = model.forward_paged_decode(params, toks, pools, bt, pos)
    assert dispatch.selected().get("paged_decode=paged_kernel") == 1
    poisoned = jax.tree.map(lambda a: a.at[:, 0].set(jnp.nan), pools)
    got, _ = model.forward_paged_decode(params, toks, poisoned, bt, pos)
    assert np.isfinite(np.asarray(got)).all()
    live = jnp.asarray([0, 2])
    np.testing.assert_allclose(np.asarray(got[live]), np.asarray(clean[live]),
                               atol=1e-5)


def test_forward_paged_matches_forward_cached():
    """Model-level parity: paged prefill + decode reproduces the dense
    cached path's logits (GQA + rope) with attention_backend='flash', so
    the PAGED KERNEL (interpret mode) sits in the decode loop. The xla
    backend's paged path is pinned bitwise by the test_serving greedy
    identity tests — not repeated here."""
    from deepspeed_tpu.models.causal_lm import CausalLM
    from deepspeed_tpu.models.transformer import TransformerConfig

    import deepspeed_tpu.comm as dist
    dist.set_mesh(None)
    r = np.random.default_rng(23)
    for backend in ("flash",):
        cfg = TransformerConfig(vocab_size=128, max_seq=256, n_layer=2,
                                n_head=4, n_kv_head=2, d_model=256,
                                pos_embedding="rope", norm="rmsnorm",
                                activation="swiglu", remat=False,
                                attention_backend=backend)
        model = CausalLM(cfg)
        params = model.init_params(jax.random.key(0))
        plen = 10
        toks = jnp.asarray(r.integers(0, 128, size=(1, plen)), jnp.int32)

        cache = model.init_cache(1, 256, dtype=jnp.float32)
        lp, cache = model.forward_cached(params, toks, cache, jnp.int32(0))
        ref = [lp[:, plen - 1]]

        pools = model.init_paged_cache(4, 128, dtype=jnp.float32)
        table = np.asarray([2, 1], np.int32)
        t = np.arange(128)
        slots = np.where(t < plen, table[t // 128] * 128 + t % 128, t % 128)
        logits, pools = model.forward_paged_prefill(
            params, jnp.pad(toks, ((0, 0), (0, 128 - plen))), pools,
            jnp.asarray(slots, jnp.int32), jnp.int32(plen - 1))
        got = [logits]

        bt = jnp.asarray(table[None, :], jnp.int32)
        nxt = jnp.argmax(logits, axis=-1)
        for step in range(3):
            pos = plen + step
            ld, cache = model.forward_cached(
                params, nxt[:, None].astype(jnp.int32), cache, jnp.int32(pos))
            lpd, pools = model.forward_paged_decode(
                params, nxt[:, None].astype(jnp.int32), pools, bt,
                jnp.asarray([pos], jnp.int32))
            ref.append(ld[:, 0])
            got.append(lpd)
            nxt = jnp.argmax(lpd, axis=-1)
        for i, (a, b) in enumerate(zip(got, ref)):
            err = float(jnp.abs(a - b).max())
            assert err < 1e-3, (backend, i, err)
