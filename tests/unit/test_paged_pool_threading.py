"""Guards that the KV pool keeps ONE buffer and ONE layout through a serving
step (``models/transformer.py`` ``_scan_paged_layers``).

(a) Structural, on the CPU: in the jaxpr of each ``forward_paged_*`` the
pools are the layer scan's CARRY, viewed as ``[n_layer*num_blocks, bs,
KV*Hd]``, and the scan has no input or output of a layer's pool size — so
no per-layer slice is taken and nothing is stacked.

(b) Compile fact, where XLA:TPU can be described without a device (as
``perfbench/tools/aot_size.py`` does; skipped otherwise): decode and prefill
of a two-layer model with head_dim 64 compiled for a described v5e keep
their temporaries under a quarter of the pool and hold no ``copy``,
``dynamic-slice`` or ``dynamic-update-slice`` of a layer's pool size. With
the pool stored ``[.., KV, Hd=64]`` and scanned as inputs/outputs (before PR
24) the same programs had six such copies and two pool-sized temporaries.

(c) The same compile at the cells' published widths: no decode program
yields a buffer of an attention projection's shape, i.e. the products read
their weights where they lie in the layers' stack (PR 57).
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.causal_lm import CausalLM
from deepspeed_tpu.models.transformer import TransformerConfig

I32 = jnp.int32
BS = 128


def paged_programs(model, rows, width, tokens):
    """name -> (fn(params, pools, *rest), shapes of rest): the four paged
    programs with the argument shapes the serving engine gives them."""
    n_max = -(-model.config.max_seq // BS)
    return {
        "prefill": (
            lambda p, po, t, s, li: model.forward_paged_prefill(
                p, t, po, s, li),
            [((1, tokens), I32), ((tokens,), I32), ((), I32)]),
        "prefill_chunk": (
            lambda p, po, t, bt, s, sp, li: model.forward_paged_prefill_chunk(
                p, t, po, bt, s, sp, li),
            [((1, tokens), I32), ((1, n_max), I32), ((tokens,), I32),
             ((), I32), ((), I32)]),
        "verify": (
            lambda p, po, t, bt, s, pos: model.forward_paged_verify(
                p, t, po, bt, s, pos),
            [((rows, width), I32), ((rows, n_max), I32),
             ((rows, width), I32), ((rows,), I32)]),
        "decode": (
            lambda p, po, t, bt, pos: model.forward_paged_decode(
                p, t, po, bt, pos),
            [((rows, 1), I32), ((rows, n_max), I32), ((rows,), I32)]),
    }


# --------------------------------------------------------------------- #
# (a) the jaxpr: pools in the carry, nothing pool-sized scanned

TINY = dict(vocab_size=64, max_seq=256, n_layer=3, n_head=2, n_kv_head=2,
            d_model=32, d_ff=64, remat=False)
NUM_BLOCKS = 9


def _scans(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scans(sub)


@pytest.mark.parametrize("name", ["prefill", "prefill_chunk", "verify",
                                  "decode"])
def test_pools_are_the_layer_scans_carry(name):
    import deepspeed_tpu.comm as dist
    dist.set_mesh(None)
    model = CausalLM(TransformerConfig(**TINY))
    cfg = model.config
    params = jax.eval_shape(model.init_params, jax.random.key(0))
    pools = jax.eval_shape(
        lambda: model.init_paged_cache(NUM_BLOCKS, BS, dtype=jnp.float32))
    row = cfg.kv_heads * cfg.head_dim
    assert pools["k"].shape == (cfg.n_layer, NUM_BLOCKS, BS, row)
    fn, rest = paged_programs(model, rows=2, width=2, tokens=BS)[name]
    jaxpr = jax.make_jaxpr(fn)(
        params, pools, *[jax.ShapeDtypeStruct(s, d) for s, d in rest])

    layer_scans = [e for e in _scans(jaxpr.jaxpr)
                   if e.params["length"] == cfg.n_layer]
    assert len(layer_scans) == 1, [str(e.primitive) for e in layer_scans]
    eqn = layer_scans[0]
    n_const, n_carry = eqn.params["num_consts"], eqn.params["num_carry"]
    carry = [v.aval.shape for v in eqn.invars[n_const:n_const + n_carry]]
    view = (cfg.n_layer * NUM_BLOCKS, BS, row)
    assert carry.count(view) == 2, (name, carry)
    # everything else the scan takes in or gives out is smaller than ONE
    # layer's pool: no per-layer slice goes in, nothing is stacked
    layer_pool = NUM_BLOCKS * BS * row
    others = ([v.aval for v in eqn.invars[:n_const]]
              + [v.aval for v in eqn.invars[n_const + n_carry:]]
              + [v.aval for v in eqn.outvars[n_carry:]])
    big = [a.shape for a in others if int(np.prod(a.shape)) >= layer_pool]
    assert not big, (name, big)
    out_pools = jax.eval_shape(fn, params, pools, *[
        jax.ShapeDtypeStruct(s, d) for s, d in rest])[1]
    assert out_pools["k"].shape == pools["k"].shape


# --------------------------------------------------------------------- #
# (b) the compile fact on a described v5e

@pytest.fixture(scope="module")
def one_v5e():
    """A described (not attached) v5e chip's sharding. Only inside a test:
    one process at a time may load libtpu, so nothing here may run while a
    worker merely imports this file."""
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    try:
        from jax.experimental import topologies
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:1x1",
            chips_per_host_bounds=(1, 1, 1), num_slices=1)
    except Exception as e:  # noqa: BLE001 — no TPU compiler here: nothing to check
        pytest.skip(f"no v5e can be described here: {e!r:.200}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("name", ["decode", "prefill"])
def test_no_pool_sized_copy_compiled_for_v5e(name, one_v5e, monkeypatch):
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.ops import dispatch
    dist.set_mesh(None)
    sh = one_v5e
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    # head_dim 64: half a lane tile, the case the device once stored
    # transposed; the pool is larger than any weight or activation here
    model = CausalLM(TransformerConfig(
        vocab_size=512, max_seq=512, n_layer=2, n_head=4, n_kv_head=4,
        d_model=256, d_ff=512, remat=False))
    cfg = model.config
    assert cfg.head_dim == 64
    num_blocks = 16
    params = jax.tree.map(lambda a: sds(a.shape, jnp.bfloat16),
                          jax.eval_shape(model.init_params, jax.random.key(0)))
    pools = jax.tree.map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(
        lambda: model.init_paged_cache(num_blocks, BS, dtype=jnp.bfloat16)))
    fn, rest = paged_programs(model, rows=8, width=2, tokens=256)[name]
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, pools, *[sds(s, d) for s, d in rest]).compile()

    pool_bytes = sum(int(np.prod(a.shape)) * 2 for a in jax.tree.leaves(pools))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes, "the pools are not aliased"
    assert mem.temp_size_in_bytes < pool_bytes / 4, (
        mem.temp_size_in_bytes, pool_bytes)
    layer_pool = num_blocks * BS * cfg.kv_heads * cfg.head_dim
    moved = []
    for m in re.finditer(
            r"= \w+\[([\d,]+)\]\S* "
            r"(copy|dynamic-slice|dynamic-update-slice)\(", compiled.as_text()):
        if int(np.prod([int(d) for d in m.group(1).split(",")])) >= layer_pool:
            moved.append(m.group(0))
    assert not moved, moved


def _weight_sized_moves(text, shapes):
    """The instructions of a compiled program's text that YIELD a buffer of
    one of ``shapes`` (each a matrix's dims; a leading 1, the scan's slice,
    and either order of the two count as the same buffer): copies,
    transposes, dynamic slices and fusions outside any fused computation. A
    product that reads its weight where it lies has the slice INSIDE its
    fusion and yields [rows, width], so it is not one of them."""
    want = {tuple(sorted(s)) for s in shapes}
    moved, fused = [], False
    for line in text.splitlines():
        if line and not line[0].isspace():
            fused = line.startswith("%fused_computation")
        m = re.match(r"\s+(?:ROOT )?(%\S+) = \w+\[([\d,]+)\]\S* "
                     r"(copy|transpose|dynamic-slice|fusion)\(", line)
        if not m or fused:
            continue
        dims = tuple(sorted(int(d) for d in m.group(2).split(",")
                            if d != "1"))
        if dims in want:
            moved.append(f"{m.group(1)} {m.group(3)} [{m.group(2)}]")
    return moved


@pytest.mark.parametrize("family,size,rows", [
    # attn_bias, head size 64: opt1b3_serve_decode / _mixed
    pytest.param("opt", "1.3b", 40, id="opt"),
    # RMSNorm of each head of q and k; sdar30b_serve_blockgen's fused pass too
    pytest.param("sdar", "30b-a3b-ep8", 64, id="sdar"),
    # window and full layers: smallthinker21b_serve_longctx
    pytest.param("smallthinker", "21b-a3b-12l", 16, id="smallthinker"),
    # one period unrolled, gated outputs: trinitylarge_serve_shortlong
    pytest.param("trinity", "large-preview-5l-ep8", 64, id="trinity"),
    # latent attention: longcatflashomni_serve_ctx3k
    pytest.param("longcat_flash", "omni-4l-ep32", 64, id="longcat"),
    # latent attention with a direct query behind a latent lead:
    # kanana2_30b_serve_longdoc
    pytest.param("deepseek_v3", "kanana-2-30b-8l", 12, id="kanana"),
])
def test_no_stacked_projection_weight_is_copied_for_v5e(
        family, size, rows, one_v5e, monkeypatch):
    """A decode step multiplies a few dozen rows by each attention matrix of
    a layer: the product reads the matrix where it lies in the [L, ...]
    stack. Compiled for a described v5e at the cells' published widths and
    rows, no program yields a buffer of an attention projection's shape.
    Before PR 57 each of these cases failed: a query (or per-head-normed
    key) split into heads right after its product was computed as a product
    batched over heads, for which the layer's ``wq`` (``wk``; LongCat's
    ``wq_b``) was sliced out of the stack and transposed, the whole matrix
    twice a layer a step (``models/transformer.py`` ``_flat``). What is left
    and allowed: LongCat's ``wkv_b`` [512, 16384], sliced and transposed for
    the absorbed form's ``bhd,rhd->bhr`` (ROADMAP S11)."""
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.inference import blockgen
    from deepspeed_tpu.inference.block_allocator import BlockAllocator
    from deepspeed_tpu.models.presets import get_model
    from deepspeed_tpu.ops import dispatch
    dist.set_mesh(None)
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e)

    model = get_model(family, size)
    cfg, num_blocks = model.config, 8 * rows + 1
    shapes = jax.eval_shape(model.init_params, jax.random.key(0))
    params = jax.tree.map(lambda a: sds(a.shape, jnp.bfloat16), shapes)
    weights = {path[-1].key: leaf.shape[1:]
               for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]
               if "['attn']" in jax.tree_util.keystr(path) and leaf.ndim == 3}
    # q and k projections, or a latent stack's (either form of its query)
    assert {"wq", "wk"} <= set(weights) or "wkv_a" in weights
    weights.pop("wkv_b", None)                         # S11's, see above
    # what a row keeps beside its KV blocks: a window layer's ring of blocks
    pool_kw, kept = {}, {}
    if cfg.cache_spec["window"]:
        ring = cfg.ring_blocks(BS)
        pool_kw["window_blocks"] = BlockAllocator.window_pool_blocks(
            num_blocks, rows, ring)
        kept["window_tables"] = sds((rows, ring), I32)
    pools = jax.tree.map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(
        lambda: model.init_paged_cache(num_blocks, BS, dtype=jnp.bfloat16,
                                       **pool_kw)))
    n_max = -(-cfg.max_seq // BS)
    for name in ("decode", "block") if cfg.generation else ("decode",):
        if name == "decode":
            compiled = jax.jit(
                lambda p, po, t, bt, pos, **kw: model.forward_paged_decode(
                    p, t, po, bt, pos, **kw), donate_argnums=(1,)).lower(
                params, pools, sds((rows, 1), I32), sds((rows, n_max), I32),
                sds((rows,), I32), **kept).compile()
        else:
            # the fused pass of generation by blocks: main and rider entries
            gen = cfg.generation
            entries = rows + blockgen.ride_slots(gen, rows)
            compiled = jax.jit(
                lambda p, po, t, bt, pos: model.forward_paged_block(
                    p, t, po, bt, pos, n_logits=rows),
                donate_argnums=(1,)).lower(
                params, pools, sds((entries, gen.block), I32),
                sds((entries, n_max), I32), sds((entries,), I32)).compile()
        moved = _weight_sized_moves(compiled.as_text(), weights.values())
        assert not moved, (name, moved, weights)


@pytest.mark.parametrize("B,Q,H,KV,Hd,width,terms,form", [
    # opt1b3_serve_*: one block a loop step
    (40, 1, 32, 32, 64, 16, False, "block_diagonal"),
    # olmoe1b7b_serve_decode
    (64, 1, 16, 16, 128, 32, False, "block_diagonal"),
    # GQA group of 4 with ALiBi and pad bias
    (8, 1, 32, 8, 64, 6, True, "block_diagonal"),
    # a 768-lane row: four blocks a loop step
    (8, 1, 12, 12, 64, 6, True, "block_diagonal"),
    # sdar30b_serve_blockgen: a block's 4 positions, 32 query rows a kv head
    (64, 4, 32, 4, 128, 8, False, "per_kv_head"),
    # solaropen2_serve_decode's softmax layer: 8 query rows a kv head
    (128, 1, 64, 8, 128, 16, False, "per_kv_head"),
    # positions x a group of 2 with ALiBi and pad bias, either form
    (8, 4, 8, 4, 128, 6, True, "per_kv_head"),
    (8, 4, 4, 4, 64, 6, True, "block_diagonal"),
])
def test_paged_kernel_compiles_for_v5e_at_real_widths(B, Q, H, KV, Hd, width,
                                                      terms, form, one_v5e,
                                                      monkeypatch):
    """Mosaic takes the streaming kernel at the cells' widths (what interpret
    mode cannot see: the block-diagonal query's half-tile lane offsets at
    head size 64, a kv head's lane slice of a block and its rows of the
    stacked bf16 product, the VMEM the buffers take) in the form its shape
    takes, and the pools reach it where they lie: the program holds no copy
    of one."""
    from deepspeed_tpu.ops import dispatch
    from deepspeed_tpu.ops.pallas.paged_decode_attention import \
        paged_decode_attention
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e)

    blocks = 64
    pool = sds((blocks, BS, KV * Hd), jnp.bfloat16)

    def call(q, kp, vp, bt, pos, bias, slopes):
        return paged_decode_attention(
            q, kp, vp, bt, pos, pad_bias=bias if terms else None,
            alibi_slopes=slopes if terms else None, interpret=False)

    dispatch.reset()
    compiled = jax.jit(call).lower(
        sds((B, H, Hd) if Q == 1 else (B, Q, H, Hd), jnp.bfloat16), pool,
        pool, sds((B, width), jnp.int32), sds((B,), jnp.int32),
        sds((B, width * BS), jnp.float32), sds((H,), jnp.float32)).compile()
    assert dispatch.selected()[f"paged_decode_attention={form}"] == 1
    text = compiled.as_text()
    assert "paged_decode_attention" in text
    assert compiled.memory_analysis().temp_size_in_bytes < blocks * BS * KV * Hd


def test_state_pool_stays_in_one_buffer_for_v5e(one_v5e, monkeypatch):
    """The recurrent state beside the KV pool: the decode program of a
    two-period stateful toy (a KDA state the kernel tiles: 2 heads of 128 x
    128) compiled for a described v5e runs ``kda_decode_update`` on the
    pool where it lies. Every pool is aliased, the temporaries stay under a
    quarter of the state, and no ``copy``, ``dynamic-slice`` or
    ``dynamic-update-slice`` of a layer's state size exists: XLA copies a
    state pool in and out when it cannot prove an update in place (PERF.md
    section 6, PR 31: 1.73 GB of temporaries against 0.21)."""
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.models.presets import get_model
    from deepspeed_tpu.ops import dispatch
    dist.set_mesh(None)
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e)

    rows, num_blocks = 8, 16
    model = get_model("solar_open2", "tiny", n_layer=8, head_size=64,
                      lin_heads=2, lin_head_dim=128)
    assert model.config.n_periods == 2
    params = jax.tree.map(lambda a: sds(a.shape, jnp.bfloat16),
                          jax.eval_shape(model.init_params, jax.random.key(0)))
    pools = jax.tree.map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(
        lambda: model.init_paged_cache(num_blocks, BS, dtype=jnp.bfloat16,
                                       state_slots=rows + 1)))
    n_max = -(-model.config.max_seq // BS)
    dispatch.reset()
    compiled = jax.jit(
        lambda p, po, t, bt, pos, ss: model.forward_paged_decode(
            p, t, po, bt, pos, state_slots=ss), donate_argnums=(1,)).lower(
        params, pools, sds((rows, 1), I32), sds((rows, n_max), I32),
        sds((rows,), I32), sds((rows,), I32)).compile()
    assert dispatch.selected()["kda_decode=kda_kernel"] == 3
    assert dispatch.selected()["kernel/kda_decode_update=compiled"] == 3

    nbytes = lambda tree: sum(  # noqa: E731
        int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
        for a in jax.tree.leaves(tree))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= nbytes(pools), "the pools are not aliased"
    assert mem.temp_size_in_bytes < nbytes(pools["state"]) / 4, (
        mem.temp_size_in_bytes, nbytes(pools["state"]))
    text = compiled.as_text()
    # the period's body: the paged kernel and three of the KDA kernel
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    layer_state = int(np.prod(pools["state"][0].shape[1:]))
    moved = []
    for m in re.finditer(
            r"= \w+\[([\d,]+)\]\S* "
            r"(copy|dynamic-slice|dynamic-update-slice)\(", text):
        if int(np.prod([int(d) for d in m.group(1).split(",")])) >= layer_state:
            moved.append(m.group(0))
    assert not moved, moved


def test_kda_kernel_compiles_for_v5e_at_real_widths(one_v5e, monkeypatch):
    """Mosaic takes the KDA decode kernel at ``solaropen2_serve_decode``'s
    widths (128 rows, 64 heads of a 128 x 128 float32 state, 129 slots of
    one period: what interpret mode cannot see is the turned vectors' lane
    slices, the scalars in SMEM and the VMEM two 16 MB phase buffers take),
    and the pool is its input and its output in one buffer."""
    from deepspeed_tpu.ops import dispatch
    from deepspeed_tpu.ops.pallas.kda_decode_update import kda_decode_update
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    B, H, dk, dv, slots = 128, 64, 128, 128, 129
    f32 = jnp.float32

    def sds(shape, dtype=f32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e)

    compiled = jax.jit(
        lambda S, q, k, v, g, b, ss: kda_decode_update(
            S, q, k, v, g, b, ss, 0, interpret=False),
        donate_argnums=(0,)).lower(
        sds((slots, H, dk, dv)), sds((B, H, dk)), sds((B, H, dk)),
        sds((B, H, dv)), sds((B, H, dk)), sds((B, H)), sds((B,), I32)).compile()
    assert "kda_decode_update" in compiled.as_text()
    pool_bytes = slots * H * dk * dv * 4
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes, "the pool is not aliased"
    assert mem.temp_size_in_bytes < pool_bytes / 64, mem.temp_size_in_bytes


def test_mamba2_state_pools_stay_in_one_buffer_for_v5e(one_v5e, monkeypatch):
    """The second recurrent kind: the decode program of a two-period
    ``granite_hybrid`` toy (a Mamba-2 state the kernel tiles: 2 heads of 64
    x 128) compiled for a described v5e runs ``mamba2_decode_update`` on
    each of its nine state pools where it lies: every pool aliased, the
    temporaries under a quarter of the state, and no ``copy``,
    ``dynamic-slice`` or ``dynamic-update-slice`` of a layer's state size.
    And the prefill keeps the conv pools in the layout they come in (a
    dynamic slice along the time axis made the compiler hold them
    time-minor: 3 padded to 128 lanes, forty times their size in
    temporaries; PERF.md section 6, PR 43)."""
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.models.presets import get_model
    from deepspeed_tpu.ops import dispatch
    dist.set_mesh(None)
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e)

    rows, num_blocks = 8, 16
    model = get_model("granite_hybrid", "tiny", n_layer=20, head_size=64,
                      ssm_heads=2, ssm_head_dim=64, ssm_state=128)
    assert model.config.n_periods == 2
    params = jax.tree.map(lambda a: sds(a.shape, jnp.bfloat16),
                          jax.eval_shape(model.init_params, jax.random.key(0)))
    pools = jax.tree.map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(
        lambda: model.init_paged_cache(num_blocks, BS, dtype=jnp.bfloat16,
                                       state_slots=rows + 1)))
    nbytes = lambda tree: sum(  # noqa: E731
        int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
        for a in jax.tree.leaves(tree))
    n_max = -(-model.config.max_seq // BS)
    dispatch.reset()
    compiled = jax.jit(
        lambda p, po, t, bt, pos, ss: model.forward_paged_decode(
            p, t, po, bt, pos, state_slots=ss), donate_argnums=(1,)).lower(
        params, pools, sds((rows, 1), I32), sds((rows, n_max), I32),
        sds((rows,), I32), sds((rows,), I32)).compile()
    assert dispatch.selected()["ssd_decode=mamba2_kernel"] == 9
    assert dispatch.selected()["kernel/mamba2_decode_update=compiled"] == 9
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= nbytes(pools), "the pools are not aliased"
    assert mem.temp_size_in_bytes < nbytes(pools["state"]) / 4, (
        mem.temp_size_in_bytes, nbytes(pools["state"]))
    text = compiled.as_text()
    # the period's body: the paged kernel and nine of the Mamba-2 kernel
    assert text.count('custom_call_target="tpu_custom_call"') == 10
    layer_state = int(np.prod(pools["state"][0].shape[1:]))
    moved = []
    for m in re.finditer(
            r"= \w+\[([\d,]+)\]\S* "
            r"(copy|dynamic-slice|dynamic-update-slice)\(", text):
        if int(np.prod([int(d) for d in m.group(1).split(",")])) >= layer_state:
            moved.append(m.group(0))
    assert not moved, moved
    prefill = jax.jit(
        lambda p, po, t, sl, li, ss: model.forward_paged_prefill(
            p, t, po, sl, li, state_slot=ss), donate_argnums=(1,)).lower(
        params, pools, sds((1, 256), I32), sds((256,), I32), sds((), I32),
        sds((), I32)).compile()
    mem = prefill.memory_analysis()
    assert mem.alias_size_in_bytes >= nbytes(pools), "the pools are not aliased"
    assert mem.temp_size_in_bytes < 4 * nbytes(pools["conv"]), (
        mem.temp_size_in_bytes, nbytes(pools["conv"]))


def test_mamba2_kernel_compiles_for_v5e_at_real_widths(one_v5e, monkeypatch):
    """Mosaic takes the Mamba-2 decode kernel at
    ``granite4hmicro_serve_chat``'s widths (64 rows, a 128 x 4,096 float32
    state a row: 64 heads of 64 x 128, 4 x 65 slots of one pool: what
    interpret mode cannot see is B and C turned in a tile of eight
    sublanes, the pool's rows copied from and to HBM where it lies and the
    VMEM two phases of eight 2 MB states take), and the pool is its input
    and its output in one buffer."""
    from deepspeed_tpu.ops import dispatch
    from deepspeed_tpu.ops.pallas.mamba2_decode_update import \
        mamba2_decode_update
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    B, H, P, N, slots = 64, 64, 64, 128, 4 * 65
    f32 = jnp.float32

    def sds(shape, dtype=f32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e)

    compiled = jax.jit(
        lambda S, x, dt, A, b, c, ss: mamba2_decode_update(
            S, x, dt, A, b, c, ss, 65, interpret=False),
        donate_argnums=(0,)).lower(
        sds((slots, N, H * P)), sds((B, H, P)), sds((B, H)), sds((H,)),
        sds((B, N)), sds((B, N)), sds((B,), I32)).compile()
    assert "mamba2_decode_update" in compiled.as_text()
    pool_bytes = slots * H * P * N * 4
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes, "the pool is not aliased"
    assert mem.temp_size_in_bytes < pool_bytes / 64, mem.temp_size_in_bytes


def test_block_step_keeps_the_pools_in_place_for_v5e(one_v5e, monkeypatch):
    """The fused step of generation by blocks at ``sdar30b_serve_blockgen``'s
    real widths (64 rows' main entries and the 16 rider entries the
    session derives beside them, 4 positions each, 32 query and 4 key/value
    heads of 128, d 2,048, 16 held experts of 768 of a router's 128; two
    layers of the 48), the decision included over the main entries alone,
    compiled for a described v5e: Mosaic
    takes the paged kernel with the block's 4 positions on its own axis, 32
    query rows a kv head and the products a kv head (one call a layer, not
    one a position), the pools are aliased, the temporaries
    stay under a quarter of them and no ``copy``, ``dynamic-slice`` or
    ``dynamic-update-slice`` of a layer's pool size exists. So does the
    block-causal prefill of 256 tokens through the flash kernel. Both
    programs' experts (320 and 256 rows) are the grouped expert kernel's."""
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.inference import blockgen
    from deepspeed_tpu.models.presets import get_model
    from deepspeed_tpu.ops import dispatch
    dist.set_mesh(None)
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e)

    rows, num_blocks = 64, 160
    model = get_model("sdar", "30b-a3b-ep8", n_layer=2)
    cfg, gen = model.config, model.config.generation
    params = jax.tree.map(lambda a: sds(a.shape, jnp.bfloat16),
                          jax.eval_shape(model.init_params, jax.random.key(0)))
    pools = jax.tree.map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(
        lambda: model.init_paged_cache(num_blocks, BS, dtype=jnp.bfloat16)))
    n_max = -(-cfg.max_seq // BS)
    entries = rows + blockgen.ride_slots(gen, rows)
    assert entries == 80

    def step(p, po, prev, idx, host, bt, pos, n_decide, commit):
        # the engine's ``paged_block``: every entry fed and computed, the
        # head and the decision over the rows' own entries
        state = blockgen.feed(prev, idx, host)
        logits, po, aux = model.forward_paged_block(
            p, blockgen.tokens_of(gen, state), po, bt, pos, n_logits=rows)
        return blockgen.unmask(gen, logits, state[:rows], n_decide,
                               commit), po, aux

    dispatch.reset()
    programs = {
        "block": jax.jit(step, donate_argnums=(1,)).lower(
            params, pools, sds((rows, gen.block), I32), sds((entries,), I32),
            sds((entries, gen.block), I32), sds((entries, n_max), I32),
            sds((entries,), I32), sds((rows,), I32),
            sds((rows,), jnp.bool_)).compile(),
        "prefill": jax.jit(
            lambda p, po, t, s, li: model.forward_paged_prefill(p, t, po, s, li),
            donate_argnums=(1,)).lower(
            params, pools, sds((1, 256), I32), sds((256,), I32),
            sds((), I32)).compile()}
    forms = dispatch.selected()
    assert forms["paged_block=paged_kernel"] == 1
    assert forms["paged_decode_attention=per_kv_head"] == 1
    assert forms["paged_prefill=flash"] == 1
    pool_bytes = sum(int(np.prod(a.shape)) * 2 for a in jax.tree.leaves(pools))
    layer_pool = num_blocks * BS * cfg.kv_heads * cfg.head_dim
    for name, compiled in programs.items():
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes >= pool_bytes, name
        assert mem.temp_size_in_bytes < pool_bytes / 4, (
            name, mem.temp_size_in_bytes, pool_bytes)
        text = compiled.as_text()
        # two kernel calls in the layer scan's body: the attention's and,
        # since PR 53, the experts' (a call of 320 or 256 rows: each expert
        # over its own rows)
        assert text.count('custom_call_target="tpu_custom_call"') == 2, name
        assert text.count("grouped_expert_mlp_own_rows") >= 1, name
        moved = []
        for m in re.finditer(
                r"= \w+\[([\d,]+)\]\S* "
                r"(copy|dynamic-slice|dynamic-update-slice)\(", text):
            dims = [int(d) for d in m.group(1).split(",")]
            # of a pool's own shape (the scan's slices of the 16 stacked
            # experts, 25 M elements a matrix, are weights and fuse into
            # their products)
            if int(np.prod(dims)) >= layer_pool and \
                    dims[-2:] == [BS, cfg.kv_heads * cfg.head_dim]:
                moved.append(m.group(0))
        assert not moved, (name, moved)


def test_window_kernels_compile_for_v5e_at_real_widths(one_v5e, monkeypatch):
    """Mosaic takes both kernels under a WINDOW at the widths of
    ``smallthinker21b_serve_longctx`` (28 query and 4 key/value heads of
    128, a window of 4,096 over blocks of 128): the paged kernel over a ring
    table of 33 entries (a row's loop from its first live block, the table
    index a remainder), and the banded forward flash kernel at a prompt
    bucket of 8,192 on its plain path (two precomputed biases, the key
    blocks' index clamped into the band), under its own name."""
    from deepspeed_tpu.ops import dispatch
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    from deepspeed_tpu.ops.pallas.paged_decode_attention import \
        paged_decode_attention
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e)

    B, H, KV, Hd, W = 16, 28, 4, 128, 4096
    pool = sds((16 * 33 + 1, BS, KV * Hd), jnp.bfloat16)
    dispatch.reset()
    compiled = jax.jit(lambda q, kp, vp, bt, pos: paged_decode_attention(
        q, kp, vp, bt, pos, window=W, interpret=False)).lower(
        sds((B, H, Hd), jnp.bfloat16), pool, pool, sds((B, 33), jnp.int32),
        sds((B,), jnp.int32)).compile()
    assert dispatch.selected()["paged_decode_attention=block_diagonal"] == 1
    assert "paged_decode_attention" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20

    S = 8192
    q, kv = sds((1, S, H, Hd), jnp.bfloat16), sds((1, S, KV, Hd), jnp.bfloat16)
    compiled = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=W, interpret=False)).lower(
        q, kv, kv).compile()
    assert "flash_fwd_band" in compiled.as_text()


@pytest.mark.parametrize("tokens,D,V,tiles", [
    (4 * 2048, 1024, 250880, "bt_fwd=1024 bt=256 bv=512"),   # the BLOOM cell's
    (32 * 1024, 768, 50257, "bt_fwd=1024 bt=256 bv=512"),    # chip_smoke's GPT-2
    (4096, 4096, 32000, "bt_fwd=1024 bt=128 bv=256"),        # a 7B-class head
])
def test_fused_ce_kernels_compile_for_v5e_at_real_widths(tokens, D, V, tiles,
                                                         one_v5e, monkeypatch):
    """Mosaic takes the fused cross-entropy forward with the token tile it
    chooses from the shapes (what interpret mode cannot see: the VMEM a
    (1024, D) tile, two weight blocks and the block's float32 logits take,
    asked for through ``vmem_limit_bytes``) and both backward kernels
    beside it; the program holds no ``[tokens, V]`` temporary."""
    from deepspeed_tpu.ops import dispatch
    from deepspeed_tpu.ops.pallas.fused_cross_entropy import fused_cross_entropy
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e)

    dispatch.reset()
    compiled = jax.jit(jax.value_and_grad(
        lambda h, w, labels: fused_cross_entropy(h, w, labels, interpret=False),
        argnums=(0, 1))).lower(
        sds((tokens, D)), sds((D, V)), sds((tokens,), I32)).compile()
    assert dispatch.details()["fused_ce_fwd=lane_state"] == tiles
    text = compiled.as_text()
    for kernel in ("fused_ce_fwd", "fused_ce_dh", "fused_ce_dw"):
        assert kernel in text, kernel
    # padding W's and dW's columns (GPT-2's 50,257 -> 50,688) is the one
    # large temporary a shape needs: far under the logits' tokens x V x 4
    assert compiled.memory_analysis().temp_size_in_bytes < tokens * V
