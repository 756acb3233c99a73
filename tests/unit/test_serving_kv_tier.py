"""Tiered KV cache: host-RAM spill pool behind the paged block allocator.

Covers the :class:`KvHostPool` LRU/byte/fault invariants, the allocator's
demote-instead-of-reclaim + tiered match walk, scheduler admission that
treats a host hit as a cache hit whose tail needs only H2D, THE
acceptance pin (a fully-cached re-admission whose blocks were demoted to
host runs the whole-prompt prefill jit ZERO times), greedy token identity
with spill forced on across eviction pressure / multi-turn re-hit /
chunked prefill / speculation, injected D2H/H2D fault degradation
(including through the always-on ``AsyncServingEngine`` loop), the
``kv.spill``/``kv.fetch`` flight-recorder + trace surface, and the
``serving_tiered_steady`` compile-budget contract. The conftest
``_no_kv_block_leaks`` fixture additionally asserts every drained
scheduler here left zero live references AND a consistent host tier."""

import errno
import importlib.util
import os
import sys
from pathlib import Path

import numpy as np
import pytest

import deepspeed_tpu
import deepspeed_tpu.comm as dist
from deepspeed_tpu.inference.block_allocator import ROOT_KEY, BlockAllocator
from deepspeed_tpu.inference.kv_host_pool import KvHostPool
from deepspeed_tpu.inference.scheduler import (FINISHED,
                                               ContinuousBatchingScheduler,
                                               ServingTelemetry)
from deepspeed_tpu.models import CausalLM
from deepspeed_tpu.models.transformer import TransformerConfig
from deepspeed_tpu.monitor.metrics import MetricsRegistry
from deepspeed_tpu.utils import fault_injection as fi

_TOOLS = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                      "..", "..", "tools"))
if _TOOLS not in sys.path:
    sys.path.insert(0, _TOOLS)

_VT_PATH = Path(__file__).resolve().parents[2] / "tools" / "validate_trace.py"
_spec = importlib.util.spec_from_file_location("validate_trace", _VT_PATH)
validate_trace = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(validate_trace)


@pytest.fixture(autouse=True)
def clean_state():
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


def tiny_model(**over):
    base = dict(vocab_size=64, n_layer=2, n_head=4, d_model=32, d_ff=64,
                max_seq=64, remat=False)
    base.update(over)
    return CausalLM(TransformerConfig(**base))


def keys_for(alloc, tokens):
    bs = alloc.block_size
    tokens = np.asarray(tokens, np.int32)
    keys, parent = [], ROOT_KEY
    for j in range(tokens.size // bs):
        parent = alloc.chain_key(parent, tokens[j * bs:(j + 1) * bs])
        keys.append(parent)
    return keys


SHAPE = (1, 4, 1)           # [L, bs, KV*Hd] for the host-level tests


def slab(fill):
    return np.full(SHAPE, float(fill), np.float32)


# --------------------------------------------------------------------- #
# KvHostPool: LRU bound, byte accounting, fault degradation


class TestKvHostPool:

    def test_put_get_roundtrip_and_bytes(self):
        hp = KvHostPool(4, SHAPE, "float32")
        assert hp.put(b"a", slab(1), slab(2))
        assert hp.num_blocks == 1
        assert hp.nbytes == 2 * slab(0).nbytes
        k, v = hp.get(b"a")
        np.testing.assert_array_equal(k, slab(1))
        np.testing.assert_array_equal(v, slab(2))
        assert hp.stats["fetches"] == 1
        # duplicate put refreshes recency but is NOT a new spill
        assert not hp.put(b"a", slab(9), slab(9))
        assert hp.num_blocks == 1
        assert hp.get(b"missing") is None

    def test_lru_eviction_at_capacity_and_get_refreshes(self):
        hp = KvHostPool(2, SHAPE, "float32")
        hp.put(b"a", slab(1), slab(1))
        hp.put(b"b", slab(2), slab(2))
        hp.get(b"a")                        # refresh: b is now LRU
        hp.put(b"c", slab(3), slab(3))      # over capacity -> evict b
        assert hp.contains(b"a") and hp.contains(b"c")
        assert not hp.contains(b"b")
        assert hp.stats["evictions"] == 1
        assert hp.num_blocks == 2
        assert hp.nbytes == 2 * 2 * slab(0).nbytes

    def test_remove_and_geometry_guard(self):
        hp = KvHostPool(4, SHAPE, "float32")
        hp.put(b"a", slab(1), slab(1))
        assert hp.remove(b"a") and not hp.remove(b"a")
        assert hp.nbytes == 0
        with pytest.raises(ValueError, match="geometry"):
            hp.put(b"x", np.zeros((1, 8, 1), np.float32),
                   np.zeros((1, 8, 1), np.float32))
        assert not hp.matches_geometry((2, 4, 1), "float32")
        assert hp.matches_geometry(SHAPE, "float32")

    def test_spill_fault_degrades_to_noop(self):
        hp = KvHostPool(4, SHAPE, "float32")
        with fi.inject(fi.FaultInjector().fail_writes(
                errno.EIO, path_substr="kv_host_pool/spill", count=1)):
            assert not hp.put(b"a", slab(1), slab(1))   # faulted: destroy
            assert hp.put(b"b", slab(2), slab(2))       # fault consumed
        assert not hp.contains(b"a") and hp.contains(b"b")
        assert hp.stats["errors"] == 1

    def test_fetch_fault_drops_entry_reports_miss(self):
        hp = KvHostPool(4, SHAPE, "float32")
        hp.put(b"a", slab(1), slab(1))
        with fi.inject(fi.FaultInjector().fail_writes(
                errno.EIO, path_substr="kv_host_pool/fetch", count=1)):
            assert hp.get(b"a") is None
        assert not hp.contains(b"a")        # dropped, not wedged
        assert hp.stats["errors"] == 1
        assert hp.consistency_report() == []


# --------------------------------------------------------------------- #
# allocator: demote-instead-of-reclaim + the tiered match walk


def make_tiered_alloc(num_blocks=5, block_size=4, host_cap=8):
    a = BlockAllocator(num_blocks, block_size, prefix_cache=True)
    hp = KvHostPool(host_cap, SHAPE, "float32")
    a.attach_host_pool(hp)
    spilled = []

    def spill(block, key):
        spilled.append((block, key))
        return hp.put(key, slab(block), slab(block))

    a.set_spill(spill)
    return a, hp, spilled


class TestAllocatorDemotion:

    def test_reclaim_demotes_instead_of_destroying(self):
        a, hp, spilled = make_tiered_alloc()
        prompt = np.arange(8, dtype=np.int32)
        blocks = a.allocate(2)
        k0, k1 = keys_for(a, prompt)
        a.register(blocks[0], k0)
        a.register(blocks[1], k1)
        a.free(list(reversed(blocks)))              # both park cold
        got = a.allocate(4)                         # free 2 + reclaim 2
        assert len(got) == 4 and a.num_cold == 0
        # demoted, not destroyed: both chain keys now live in the host
        # tier (tails reclaimed before parents), device table empty
        assert {k for _, k in spilled} == {k0, k1}
        assert hp.contains(k0) and hp.contains(k1)
        assert a.match_prefix(prompt) == ([], [])
        entries, keys = a.match_prefix_tiered(prompt)
        assert entries == [("host", k0), ("host", k1)] and keys == [k0, k1]
        assert a.host_consistency() == []
        a.free(got)

    def test_tiered_match_mixed_chain_and_break(self):
        a, hp, _ = make_tiered_alloc(num_blocks=8)
        prompt = np.arange(12, dtype=np.int32)      # 3 full blocks
        k0, k1, k2 = keys_for(a, prompt)
        blocks = a.allocate(2)
        a.register(blocks[0], k0)                   # block 0 on device
        hp.put(k1, slab(7), slab(7))                # block 1 demoted
        entries, keys = a.match_prefix_tiered(prompt)
        # dev hit, then host hit, then break at the unknown third key
        assert entries == [("dev", blocks[0]), ("host", k1)]
        assert keys == [k0, k1]
        a.free(blocks)

    def test_device_registration_supersedes_host_copy(self):
        a, hp, _ = make_tiered_alloc()
        k0 = keys_for(a, np.arange(4, dtype=np.int32))[0]
        hp.put(k0, slab(1), slab(1))
        b = a.allocate(1)[0]
        assert a.register(b, k0)                    # recompute re-landed it
        assert not hp.contains(k0)                  # one tier per key
        assert a.host_consistency() == []
        a.free([b])

    def test_spill_off_reclaim_destroys(self):
        a, hp, _ = make_tiered_alloc()
        a.set_spill(None)                           # spill: off
        prompt = np.arange(4, dtype=np.int32)
        b = a.allocate(1)
        a.register(b[0], keys_for(a, prompt)[0])
        a.free(b)
        got = a.allocate(4)                         # reclaims the cold block
        assert hp.num_blocks == 0                   # destroyed, tier empty
        assert a.match_prefix_tiered(prompt) == ([], [])
        a.free(got)

    def test_host_consistency_flags_double_tier_key(self):
        a, hp, _ = make_tiered_alloc()
        k0 = keys_for(a, np.arange(4, dtype=np.int32))[0]
        b = a.allocate(1)[0]
        a.register(b, k0)
        # simulate a dropped promote hand-off behind register's back
        hp._entries[k0] = hp._entries.get(k0) or type(
            "E", (), {"k": slab(1), "v": slab(1), "nbytes": 0,
                      "pending": False})()
        probs = a.host_consistency()
        assert probs and "exactly one tier" in probs[0]
        hp._entries.pop(k0)
        a.free([b])


# --------------------------------------------------------------------- #
# scheduler: host hits admit as cache hits whose tail needs only H2D


def make_sched(num_blocks=9, block_size=4, max_running=2, n_max=8,
               telemetry=None, host_cap=16, **kw):
    a = BlockAllocator(num_blocks, block_size, prefix_cache=True)
    hp = KvHostPool(host_cap, SHAPE, "float32")
    a.attach_host_pool(hp)
    a.set_spill(lambda b, key: hp.put(key, slab(b), slab(b)))
    return ContinuousBatchingScheduler(a, max_running, n_max,
                                       telemetry=telemetry,
                                       prefix_caching=True, **kw)


def drive(sched, max_steps=400, chunk_tokens=0):
    """Run to completion with fake tokens, emulating the engine's fetch +
    chunk bookkeeping (register-on-land + host-entry removal — what
    ``_ServeSession._run_fetches`` does, minus the device copies)."""
    tok = 0
    for _ in range(max_steps):
        action = sched.next_action()
        if action is None:
            return
        kind, payload = action
        if kind in ("prefill", "prefill_chunk"):
            r = payload
            if r.fetch_pending and sched.telemetry is not None:
                # the engine observes the fetch counters at LANDING
                sched.telemetry.kv_fetch_hits.inc(len(r.fetch_pending))
                t = sum(f[4] for f in r.fetch_pending)
                if t:
                    sched.telemetry.kv_fetch_tokens.inc(t)
            for dst, key, _, _, _ in r.fetch_pending:
                if key is not None:
                    sched.allocator.register(dst, key)
                    sched.allocator.host_pool.remove(key)
            r.fetch_pending = []
        if kind == "prefill":
            sched.record_prefill(payload, tok)
            tok += 1
        elif kind == "prefill_chunk":
            r = payload
            r.cow_pending = None
            remaining = r.prefill_target - r.pos
            step = min(chunk_tokens, remaining) if chunk_tokens else remaining
            if r.pos + step == r.prefill_target:
                sched.record_prefill_chunk(r, step, tok)
                tok += 1
            else:
                sched.record_prefill_chunk(r, step)
        else:
            for r in list(payload):
                sched.record_decode(r, tok)
                tok += 1
    raise AssertionError("scheduler did not finish")


class TestSchedulerHostHits:

    def test_host_hit_admits_with_fetch_pending(self):
        reg = MetricsRegistry()
        s = make_sched(telemetry=ServingTelemetry(reg))
        a, hp = s.allocator, s.allocator.host_pool
        prompt = np.arange(10, dtype=np.int32)      # 2 full blocks + tail
        k0, k1 = keys_for(a, prompt)
        hp.put(k0, slab(1), slab(1))                # whole hit demoted
        hp.put(k1, slab(2), slab(2))
        r = s.add_request(prompt, max_new=2)
        kind, req = s.next_action()
        assert (kind, req) == ("prefill_chunk", r)
        assert r.pos == 8 and r.prefill_target == 10
        # two fresh device placements carry the host hits, keys ride along
        assert [f[0] for f in r.fetch_pending] == r.blocks[:2]
        assert [f[1] for f in r.fetch_pending] == [k0, k1]
        assert r.keys == [k0, k1]
        # host entries STAY until the engine lands the copies — and the
        # fetch counters are landing-time too (a preempt-before-fetch
        # re-admission must not double-count)
        assert hp.contains(k0) and hp.contains(k1)
        c = reg.snapshot()["counters"]
        assert c["serving/kv_fetch_hits"] == 0
        assert c["serving/prefix_cache_hit_tokens"] == 8
        drive(s)                 # emulates the engine's fetch landing
        c = reg.snapshot()["counters"]
        assert c["serving/kv_fetch_hits"] == 2
        assert c["serving/kv_fetch_tokens"] == 8
        assert not hp.contains(k0) and not hp.contains(k1)
        assert a.host_consistency() == []

    def test_full_prefix_host_hit_cow_fetches_private_copy(self):
        reg = MetricsRegistry()
        s = make_sched(telemetry=ServingTelemetry(reg))
        a, hp = s.allocator, s.allocator.host_pool
        prompt = np.arange(8, dtype=np.int32)       # exactly 2 full blocks
        k0, k1 = keys_for(a, prompt)
        hp.put(k0, slab(1), slab(1))
        hp.put(k1, slab(2), slab(2))
        r = s.add_request(prompt, max_new=2)
        kind, req = s.next_action()
        assert (kind, req) == ("prefill_chunk", r)
        assert r.pos == 7                            # capped at target-1
        assert r.cow_pending is None                 # host COW = plain fetch
        # last fetch is the COW split: key None -> never registered, and
        # the host entry stays cached for future full hits
        assert r.fetch_pending[-1][0] == r.blocks[-1]
        assert r.fetch_pending[-1][1] is None
        assert r.keys == [k0]
        assert hp.contains(k1)                       # peek, not promote
        cow_block = r.blocks[-1]
        drive(s)
        # once the request fills the private block (its content is k1's
        # content again), decode-time registration lands it on DEVICE
        # under k1 — superseding and discarding the host copy (one tier)
        assert s.allocator._table.get(k1) == cow_block
        assert not hp.contains(k1)
        c = reg.snapshot()["counters"]
        assert c["serving/kv_fetch_hits"] == 2       # promote + COW copy
        assert c["serving/kv_fetch_tokens"] == 7
        assert a.host_consistency() == []

    def test_vanished_host_entry_truncates_chain(self):
        s = make_sched()
        a, hp = s.allocator, s.allocator.host_pool
        prompt = np.arange(10, dtype=np.int32)      # 2 full blocks + tail
        k0, k1 = keys_for(a, prompt)
        hp.put(k0, slab(1), slab(1))
        hp.put(k1, slab(2), slab(2))
        # k0 faults at admission-time get: the chain truncates AT ZERO
        # (k1 alone is not a prefix), so admission recomputes everything
        with fi.inject(fi.FaultInjector().fail_writes(
                errno.EIO, path_substr="kv_host_pool/fetch", count=1)):
            r = s.add_request(prompt, max_new=2)
            kind, req = s.next_action()
        assert r.pos == 0 and r.fetch_pending == []
        assert not hp.contains(k0)                   # dropped by the fault
        assert hp.stats["errors"] == 1
        drive(s)
        assert a.host_consistency() == []

    def test_preempt_before_fetch_loses_nothing(self):
        s = make_sched(num_blocks=5, max_running=1)
        a, hp = s.allocator, s.allocator.host_pool
        k0 = keys_for(a, np.arange(12, dtype=np.int32))[0]
        hp.put(k0, slab(1), slab(1))
        r = s.add_request(np.arange(12, dtype=np.int32), max_new=2)
        s.next_action()
        assert r.fetch_pending and r.pos == 4
        # preemption before the engine landed the fetch: the placement
        # dies, the host entry survives for the re-admission
        s._preempt(r)
        assert r.fetch_pending == [] and r.blocks == []
        assert hp.contains(k0)
        drive(s)
        assert r.state == FINISHED
        assert a.host_consistency() == []

    def test_cow_src_pinned_against_fetch_dst_reclaim(self):
        # full-prefix hit whose chain mixes host hits with a device COW
        # source: the fetch-destination allocation must NOT reclaim the
        # (cold, un-acquired) source — the H2D scatter would overwrite it
        # before the COW copy reads it. The admission pins it with a
        # temporary reference for the allocation.
        s = make_sched(num_blocks=6, max_running=2)
        a, hp = s.allocator, s.allocator.host_pool
        prompt = np.arange(12, dtype=np.int32)      # 3 full blocks
        k0, k1, k2 = keys_for(a, prompt)
        kx = keys_for(a, 63 - prompt[:4])[0]
        blocks = a.allocate(3)
        a.register(blocks[1], k2)                   # the future COW source
        a.register(blocks[2], kx)                   # another cold chain
        hp.put(k0, slab(1), slab(1))
        hp.put(k1, slab(2), slab(2))
        a.free([blocks[1]])                         # src oldest on cold LRU
        a.free([blocks[2]])
        a.free([blocks[0]])
        r = s.add_request(prompt, max_new=1)
        kind, req = s.next_action()
        assert (kind, req) == ("prefill_chunk", r)
        src, dst = r.cow_pending
        assert src == blocks[1]                     # pinned, still the src
        assert src not in r.blocks                  # never handed out
        assert src not in [f[0] for f in r.fetch_pending]
        assert a._table.get(k2) == src              # registration intact
        assert a.ref_count(src) == 0                # pin released: cold
        drive(s)
        assert a.host_consistency() == []

    def test_cow_degrades_to_recompute_when_pool_cannot_pin(self):
        # the pathological pool: placing the host fetches AND preserving
        # the COW source cannot both fit. The admission degrades — drops
        # the COW hit (that block's tokens recompute in the tail chunk)
        # instead of corrupting it or failing the serve.
        s = make_sched(num_blocks=5, max_running=2)
        a, hp = s.allocator, s.allocator.host_pool
        prompt = np.arange(12, dtype=np.int32)
        k0, k1, k2 = keys_for(a, prompt)
        kx = keys_for(a, 63 - prompt[:4])[0]
        blocks = a.allocate(3)                      # hold blocks[0] for now
        a.register(blocks[1], k2)
        a.register(blocks[2], kx)
        hp.put(k0, slab(1), slab(1))
        hp.put(k1, slab(2), slab(2))
        a.free([blocks[1]])
        a.free([blocks[2]])                         # cold: [src, other]
        r = s.add_request(prompt, max_new=1)
        kind, req = s.next_action()
        assert (kind, req) == ("prefill_chunk", r)
        assert r.cow_pending is None                # COW hit dropped
        assert r.pos == 8                           # host hits only
        assert len(r.fetch_pending) == 2
        # the unpinned source was legitimately reclaimed — demoted, so
        # its content survives in the host tier, destroyed for no one
        assert hp.contains(k2)
        a.free([blocks[0]])                         # release the holdout
        drive(s)
        assert a.host_consistency() == []


# --------------------------------------------------------------------- #
# engine: THE acceptance pin + greedy identity with spill forced on


class _CountCalls:
    def __init__(self, fn):
        self.fn, self.calls = fn, 0

    def __call__(self, *a, **k):
        self.calls += 1
        return self.fn(*a, **k)


def _tiered_engine(**serving):
    base = {"block_size": 8, "max_running": 2, "max_num_blocks": 4,
            "kv_host": {"enabled": True}}
    base.update(serving)
    return deepspeed_tpu.init_inference(tiny_model(), dtype="fp32",
                                        telemetry=True, serving=base)


def _pressure(engine, seed=3, n=1, size=17, max_new=4):
    """A scratch burst that floods the (tiny) device pool, reclaiming —
    hence demoting — every cold block the previous serves parked."""
    rng = np.random.default_rng(seed)
    scratch = [rng.integers(0, 64, size=size).astype(np.int32)
               for _ in range(n)]
    engine.generate_batch(scratch, max_new_tokens=max_new)


class TestTieredEngine:

    def test_demoted_rehit_zero_prefill_jit(self):
        # THE acceptance pin: a fully-cached re-admission whose blocks
        # were demoted to host runs the whole-prompt prefill jit ZERO
        # times — the tail chunk is the only prefill work — with
        # serving/kv_fetch_hits > 0 and greedy tokens unchanged
        engine = _tiered_engine()
        prompt = np.arange(16, dtype=np.int32)       # exactly 2 full blocks
        out1 = engine.generate_batch([prompt], max_new_tokens=5)
        _pressure(engine)                            # demote prompt's blocks
        assert engine._kv_host_pool.num_blocks >= 2
        assert engine._paged_alloc.match_prefix(prompt) == ([], [])
        c1 = engine.telemetry_snapshot()["counters"]
        prefill_jit = _CountCalls(engine._paged_jits[0])
        engine._paged_jits = (prefill_jit,) + engine._paged_jits[1:]
        out2 = engine.generate_batch([prompt], max_new_tokens=5)
        c2 = engine.telemetry_snapshot()["counters"]
        assert prefill_jit.calls == 0                # no whole-prompt prefill
        assert c2["serving/kv_fetch_hits"] - c1.get(
            "serving/kv_fetch_hits", 0) == 2         # promote + COW fetch
        assert c2["serving/kv_fetch_tokens"] - c1.get(
            "serving/kv_fetch_tokens", 0) == 15
        assert c2["serving/prefill_chunks"] - c1.get(
            "serving/prefill_chunks", 0) == 1        # tail chunk only
        assert c2["serving/kv_spills"] > 0
        np.testing.assert_array_equal(np.asarray(out1[0]),
                                      np.asarray(out2[0]))
        ref = engine.generate(prompt[None, :], max_new_tokens=5)
        np.testing.assert_array_equal(np.asarray(out2[0]),
                                      np.asarray(ref)[0])

    def test_identity_under_eviction_pressure_with_spill(self):
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 64, size=n).astype(np.int32)
                   for n in (5, 11, 17)]
        engine = _tiered_engine(max_num_blocks=5, prefill_chunk_tokens=8)
        outs = engine.generate_batch(prompts, max_new_tokens=10)
        snap = engine.telemetry_snapshot()["counters"]
        assert snap["serving/preemptions"] > 0
        assert snap["serving/kv_spills"] > 0         # spill actually fired
        for p, o in zip(prompts, outs):
            ref = engine.generate(p[None, :], max_new_tokens=10)
            np.testing.assert_array_equal(np.asarray(o), np.asarray(ref)[0])
        assert engine._paged_alloc.host_consistency() == []

    def test_multiturn_rehit_after_demotion(self):
        engine = _tiered_engine()
        p = np.arange(6, dtype=np.int32)
        out1 = np.asarray(engine.generate_batch([p], max_new_tokens=12)[0])
        _pressure(engine)                            # demote turn 1's blocks
        turn2 = np.concatenate([out1, np.asarray([1, 2, 3], np.int32)])
        c1 = engine.telemetry_snapshot()["counters"]
        out2 = engine.generate_batch([turn2], max_new_tokens=4)
        c2 = engine.telemetry_snapshot()["counters"]
        assert c2["serving/kv_fetch_hits"] > c1.get("serving/kv_fetch_hits",
                                                    0)
        ref = engine.generate(turn2[None, :], max_new_tokens=4)
        np.testing.assert_array_equal(np.asarray(out2[0]),
                                      np.asarray(ref)[0])

    @pytest.mark.slow  # second engine on top of the tier-1 identity pins
    def test_identity_with_speculation_and_spill(self):
        motif = np.asarray([7, 3, 9, 1] * 5, np.int32)
        prompts = [motif, np.arange(11, dtype=np.int32)]
        spec = {"mode": "ngram", "k": 4}
        tiered = _tiered_engine(max_num_blocks=5, speculative=spec)
        outs = tiered.generate_batch(prompts, max_new_tokens=10)
        st = tiered._last_serve_stats
        assert st["spec_accepted"] > 0               # speculation engaged
        plain = deepspeed_tpu.init_inference(
            tiny_model(), dtype="fp32", telemetry=True,
            serving={"block_size": 8, "max_running": 2, "max_num_blocks": 5})
        refs = plain.generate_batch(prompts, max_new_tokens=10)
        for o, r in zip(outs, refs):
            np.testing.assert_array_equal(np.asarray(o), np.asarray(r))
        assert tiered._paged_alloc.host_consistency() == []

    def test_tp2_spill_fetch_identity(self):
        # under serving.tp the per-block D2H/H2D slices land head-sharded
        # like the pools themselves: a tp=2 tiered engine demotes, fetches,
        # and stays token-identical to the tp=1 tiered engine
        import jax
        if jax.device_count() < 2:
            pytest.skip("needs >= 2 devices for tp=2")
        prompt = np.arange(16, dtype=np.int32)
        tp1 = _tiered_engine()
        ref1 = np.asarray(tp1.generate_batch([prompt], max_new_tokens=5)[0])
        dist.set_mesh(None)
        tp2 = deepspeed_tpu.init_inference(
            tiny_model(), dtype="fp32", telemetry=True,
            serving={"block_size": 8, "max_running": 2, "max_num_blocks": 4,
                     "tp": 2, "kv_host": {"enabled": True}})
        out1 = np.asarray(tp2.generate_batch([prompt], max_new_tokens=5)[0])
        _pressure(tp2)                               # demote through tp=2
        c1 = tp2.telemetry_snapshot()["counters"]
        out2 = np.asarray(tp2.generate_batch([prompt], max_new_tokens=5)[0])
        c2 = tp2.telemetry_snapshot()["counters"]
        assert c2["serving/kv_spills"] > 0
        assert c2["serving/kv_fetch_hits"] - c1.get(
            "serving/kv_fetch_hits", 0) > 0          # fetched through tp=2
        np.testing.assert_array_equal(out1, out2)
        np.testing.assert_array_equal(out1, ref1)    # tp=2 == tp=1
        assert tp2._paged_alloc.host_consistency() == []

    def test_spill_mode_off_fetches_but_never_demotes(self):
        engine = _tiered_engine(kv_host={"enabled": True, "spill": "off"})
        prompt = np.arange(16, dtype=np.int32)
        engine.generate_batch([prompt], max_new_tokens=4)
        _pressure(engine)
        assert engine._kv_host_pool.num_blocks == 0  # reclaim destroyed
        snap = engine.telemetry_snapshot()["counters"]
        assert snap.get("serving/kv_spills", 0) == 0


# --------------------------------------------------------------------- #
# fault degradation: the serving loop never wedges


class TestTieredFaults:

    def test_spill_faults_degrade_to_destroy(self):
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 64, size=n).astype(np.int32)
                   for n in (5, 11, 17)]
        engine = _tiered_engine(max_num_blocks=5)
        with fi.inject(fi.FaultInjector().fail_writes(
                errno.EIO, path_substr="kv_host_pool/spill", count=-1)):
            outs = engine.generate_batch(prompts, max_new_tokens=10)
        snap = engine.telemetry_snapshot()["counters"]
        assert snap["serving/kv_host_errors"] > 0    # faults fired
        assert snap.get("serving/kv_spills", 0) == 0  # nothing stored
        assert engine._kv_host_pool.num_blocks == 0
        for p, o in zip(prompts, outs):              # greedy unchanged
            ref = engine.generate(p[None, :], max_new_tokens=10)
            np.testing.assert_array_equal(np.asarray(o), np.asarray(ref)[0])

    def test_fetch_faults_degrade_to_recompute(self):
        engine = _tiered_engine()
        prompt = np.arange(16, dtype=np.int32)
        out1 = engine.generate_batch([prompt], max_new_tokens=5)
        _pressure(engine)
        assert engine._kv_host_pool.num_blocks >= 2
        with fi.inject(fi.FaultInjector().fail_writes(
                errno.EIO, path_substr="kv_host_pool/fetch", count=-1)):
            out2 = engine.generate_batch([prompt], max_new_tokens=5)
        snap = engine.telemetry_snapshot()["counters"]
        assert snap["serving/kv_host_errors"] > 0
        np.testing.assert_array_equal(np.asarray(out1[0]),
                                      np.asarray(out2[0]))
        assert engine._paged_alloc.host_consistency() == []

    def test_async_loop_with_spill_faults_drains_cleanly(self):
        # the always-on loop: tiering on, persistent D2H faults — every
        # handle still terminates with the right greedy tokens and the
        # loop drains without wedging or leaking
        from deepspeed_tpu.inference.serve import AsyncServingEngine
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, 64, size=n).astype(np.int32)
                   for n in (5, 11, 17)]
        engine = _tiered_engine(max_num_blocks=5)
        refs = [np.asarray(engine.generate(p[None, :], max_new_tokens=8))[0]
                for p in prompts]
        with fi.inject(fi.FaultInjector().fail_writes(
                errno.EIO, path_substr="kv_host_pool", count=-1)):
            loop = AsyncServingEngine(engine, max_new_tokens=8)
            handles = [loop.add_request(p) for p in prompts]
            outs = [h.result(timeout=60) for h in handles]
            loop.shutdown(drain=True)
        for o, r in zip(outs, refs):
            np.testing.assert_array_equal(np.asarray(o), r)
        snap = engine.telemetry_snapshot()["counters"]
        assert snap["serving/kv_host_errors"] > 0
        assert engine._paged_alloc.leak_report() == {}


# --------------------------------------------------------------------- #
# surfaces: events + trace, telemetry + health, compile-budget contract


class TestTieredSurfaces:

    def test_spill_fetch_events_and_trace_validate(self, tmp_path):
        engine = deepspeed_tpu.init_inference(
            tiny_model(), dtype="fp32",
            telemetry={"events": True},
            serving={"block_size": 8, "max_running": 2, "max_num_blocks": 4,
                     "kv_host": {"enabled": True}})
        prompt = np.arange(16, dtype=np.int32)
        engine.generate_batch([prompt], max_new_tokens=5)
        _pressure(engine)
        engine.generate_batch([prompt], max_new_tokens=5)
        events = engine._events.snapshot()
        kinds = [e.kind for e in events]
        assert "kv.spill" in kinds and "kv.fetch" in kinds
        sp = next(e for e in events if e.kind == "kv.spill")
        assert sp.data["blocks"] == 1 and sp.data["bytes"] > 0
        assert sp.dur_ns is not None and sp.rid is None
        ft = next(e for e in events if e.kind == "kv.fetch")
        assert ft.rid is not None and ft.dur_ns is not None
        assert ft.data["blocks"] == 2
        assert ft.data["bytes"] > 0
        # events JSONL + rendered chrome trace both pass the validator
        # through the shared EVENT_KINDS import
        jl = str(tmp_path / "events.jsonl")
        engine._events.write_jsonl(jl)
        assert validate_trace.main([jl]) == 0
        tr = str(tmp_path / "trace.json")
        engine.export_serving_trace(tr)
        assert validate_trace.main([tr]) == 0
        import json
        doc = json.load(open(tr))
        names = {e.get("name") for e in doc["traceEvents"]}
        assert "kv_spill" in names and "kv_fetch" in names

    def test_telemetry_gauges_and_health_pane(self):
        from deepspeed_tpu.monitor.health import (health_summary,
                                                  render_health_table)
        engine = _tiered_engine()
        prompt = np.arange(16, dtype=np.int32)
        engine.generate_batch([prompt], max_new_tokens=5)
        _pressure(engine)
        engine.generate_batch([prompt], max_new_tokens=5)
        snap = engine.telemetry_snapshot()
        g, c = snap["gauges"], snap["counters"]
        assert g["serving/kv_host_blocks"] >= 0
        assert "serving/kv_host_bytes" in g
        assert c["serving/kv_spills"] > 0
        assert c["serving/kv_fetch_hits"] > 0
        assert c["serving/kv_fetch_tokens"] > 0
        summary = health_summary(snap)
        sv = summary["serving"]
        assert sv["kv_spills"] == c["serving/kv_spills"]
        assert sv["kv_fetch_hits"] == c["serving/kv_fetch_hits"]
        assert "kv_host_blocks" in sv and "kv_host_bytes" in sv
        table = render_health_table(snap)
        assert "host" in table and "H/" in table    # the KV pane line

    def test_serving_tiered_steady_contract(self):
        """Tiering must not multiply programs: decode==1, verify==1, and
        the spill/fetch copy programs stay within 2 each over a whole
        pressured serve — verified through the CompileWatchdog with
        spill FORCED on (tiny pool, demotion + fetch both fire)."""
        from dslint.contracts import check_compile_budgets

        engine = deepspeed_tpu.init_inference(
            tiny_model(), dtype="fp32", telemetry=True,
            serving={"block_size": 8, "max_running": 2, "max_num_blocks": 4,
                     "kv_host": {"enabled": True},
                     "speculative": {"mode": "ngram", "k": 4}})
        motif = np.asarray([7, 3, 9, 1] * 4, np.int32)
        prompt = np.arange(16, dtype=np.int32)
        engine.generate_batch([prompt], max_new_tokens=5)
        _pressure(engine)
        engine.generate_batch([prompt, motif], max_new_tokens=8)
        _pressure(engine, seed=5)
        engine.generate_batch([prompt], max_new_tokens=5)
        c = engine.telemetry_snapshot()["counters"]
        assert c["serving/kv_spills"] > 0, "scenario never demoted"
        assert c["serving/kv_fetch_hits"] > 0, "scenario never fetched"
        by_fn = engine.telemetry_snapshot()["compile"]["by_fn"]
        assert by_fn.get("inference.paged_decode") == 1
        assert by_fn.get("inference.paged_spill_gather", 0) >= 1
        assert by_fn.get("inference.paged_fetch_scatter", 0) >= 1
        violations = check_compile_budgets(by_fn, "serving_tiered_steady",
                                           strict=True)
        assert violations == [], "\n".join(violations)
