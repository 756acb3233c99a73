"""Continuous-batching serving layer: block allocator, scheduler policy
(FIFO admission, eos retirement + back-fill, deterministic eviction), and
``InferenceEngine.generate_batch`` token parity with the static path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
import deepspeed_tpu.comm as dist
from deepspeed_tpu.inference.block_allocator import (DUMMY_BLOCK,
                                                     BlockAllocator)
from deepspeed_tpu.inference.scheduler import (FINISHED, QUEUED, RUNNING,
                                               ContinuousBatchingScheduler)
from deepspeed_tpu.models import CausalLM
from deepspeed_tpu.models.transformer import TransformerConfig


@pytest.fixture(autouse=True)
def clean_mesh():
    dist.set_mesh(None)
    yield
    dist.set_mesh(None)


def tiny_model(**over):
    base = dict(vocab_size=64, n_layer=2, n_head=4, d_model=32, d_ff=64,
                max_seq=64, remat=False)
    base.update(over)
    return CausalLM(TransformerConfig(**base))


# --------------------------------------------------------------------- #
# block allocator

class TestBlockAllocator:

    def test_dummy_block_reserved(self):
        a = BlockAllocator(4, 8)
        got = a.allocate(3)
        assert got == [1, 2, 3] and DUMMY_BLOCK not in got
        assert a.allocate(1) is None  # dummy never handed out

    def test_all_or_nothing_and_fifo_recycling(self):
        a = BlockAllocator(5, 8)
        first = a.allocate(2)
        assert first == [1, 2]
        assert a.allocate(3) is None        # only 2 free: nothing popped
        assert a.num_free == 2
        a.free(first)
        # freed blocks recycle FIFO: [3, 4] then [1, 2] again
        assert a.allocate(4) == [3, 4, 1, 2]

    def test_blocks_for_tokens(self):
        a = BlockAllocator(4, 8)
        assert [a.blocks_for_tokens(n) for n in (0, 1, 8, 9, 16)] \
            == [0, 1, 1, 2, 2]

    def test_free_validation(self):
        a = BlockAllocator(4, 8)
        a.allocate(1)
        with pytest.raises(ValueError, match="dummy"):
            a.free([DUMMY_BLOCK])
        with pytest.raises(ValueError, match="double free"):
            a.free([2])


# --------------------------------------------------------------------- #
# scheduler policy (no model: drive the state machine by hand)

def make_sched(num_blocks=9, block_size=8, max_running=2, n_max=8):
    return ContinuousBatchingScheduler(BlockAllocator(num_blocks, block_size),
                                       max_running, n_max)


class TestScheduler:

    def test_fifo_admission_order(self):
        s = make_sched(max_running=2)
        reqs = [s.add_request([1] * 4, max_new=4) for _ in range(3)]
        kind, first = s.next_action()
        assert (kind, first) == ("prefill", reqs[0])
        s.record_prefill(first, 7)
        kind, second = s.next_action()
        assert (kind, second) == ("prefill", reqs[1])
        s.record_prefill(second, 7)
        # both slots full: next step decodes; request 2 still queued
        kind, batch = s.next_action()
        assert kind == "decode" and batch == [reqs[0], reqs[1]]
        assert reqs[2].state == QUEUED

    def test_eos_retirement_backfills_from_queue(self):
        s = make_sched(max_running=2)
        r = [s.add_request([1] * 4, max_new=4, eos=9) for _ in range(3)]
        for i in range(2):
            s.next_action()
            s.record_prefill(r[i], 5)
        _, batch = s.next_action()
        s.record_decode(r[0], 9)   # r0 hits eos → retires
        s.record_decode(r[1], 5)
        assert r[0].state == FINISHED and not r[0].blocks
        # the freed slot back-fills with r2 BEFORE the next decode
        kind, nxt = s.next_action()
        assert (kind, nxt) == ("prefill", r[2])
        assert list(np.asarray(r[0].output)) == [1, 1, 1, 1, 5, 9]

    def test_max_new_retirement(self):
        s = make_sched()
        r = s.add_request([1, 2], max_new=2)
        s.next_action()
        s.record_prefill(r, 3)
        _, batch = s.next_action()
        s.record_decode(r, 4)
        assert r.state == FINISHED
        assert list(np.asarray(r.output)) == [1, 2, 3, 4]
        assert s.next_action() is None

    def test_eviction_is_latest_admitted_and_deterministic(self):
        # pool: 4 allocatable blocks of 4 tokens; two requests with 8-token
        # prompts consume all 4 — the first decode block growth must evict
        # the LATEST-admitted request, re-queued at the queue front
        s = make_sched(num_blocks=5, block_size=4, max_running=2, n_max=8)
        r0 = s.add_request([1] * 8, max_new=8)
        r1 = s.add_request([2] * 8, max_new=8)
        for r in (r0, r1):
            s.next_action()
            s.record_prefill(r, 5)
        kind, batch = s.next_action()   # r0 needs block 3 → evicts r1
        assert kind == "decode" and batch == [r0]
        assert r1.state == QUEUED and r1.preemptions == 1 and not r1.blocks
        assert s.waiting[0] is r1
        # r1's re-admission prefills prompt + its generated token
        assert list(np.asarray(r1.prefix())) == [2] * 8 + [5]

    def test_requester_self_eviction_when_latest(self):
        # r1 (latest) crosses a block boundary while the pool is dry → it
        # evicts itself; r0 keeps decoding
        s = make_sched(num_blocks=5, block_size=4, max_running=2, n_max=8)
        r0 = s.add_request([1] * 4, max_new=8)   # 1 block
        r1 = s.add_request([2] * 12, max_new=8)  # 3 blocks, boundary at 12
        for r in (r0, r1):
            s.next_action()
            s.record_prefill(r, 5)
        kind, batch = s.next_action()
        assert kind == "decode" and batch == [r0]
        assert r1.state == QUEUED and r1.preemptions == 1

    def test_single_request_pool_exhaustion_raises(self):
        s = make_sched(num_blocks=2, block_size=4, max_running=2, n_max=8)
        r0 = s.add_request([1] * 4, max_new=8)
        s.next_action()
        s.record_prefill(r0, 5)
        with pytest.raises(RuntimeError, match="max_num_blocks"):
            s.next_action()

    def test_oversized_request_rejected(self):
        s = make_sched(block_size=8, n_max=2)
        with pytest.raises(ValueError, match="block table"):
            s.add_request([1] * 10, max_new=10)


# --------------------------------------------------------------------- #
# the window layers' pool: a second free list, handed out by need

class TestWindowPool:

    @pytest.mark.parametrize("num_blocks,rows,R,want", [
        (1441, 16, 33, 529),        # long rows: a ring a row
        (1825, 64, 33, 1825),       # mostly short rows: the full pool's size
        (24, 4, 3, 13), (10, 4, 3, 10)])
    def test_pool_size_is_the_lesser_of_a_ring_a_row_and_the_full_pool(
            self, num_blocks, rows, R, want):
        assert BlockAllocator.window_pool_blocks(num_blocks, rows, R) == want

    def test_a_request_takes_a_window_block_with_each_block_below_a_ring(self):
        a = BlockAllocator(12, 8, window_blocks=7, ring_blocks=3)
        held, full = [], a.allocate(2)
        a.grow_window(held, len(full))
        assert held == [1, 2] and a.window_used == 2
        full += a.allocate(2)
        a.grow_window(held, len(full))          # 4 blocks, a ring of 3
        assert held == [1, 2, 3] and a.window_used == 3
        a.grow_window(held, len(full) + 1)      # a whole ring: nothing more
        assert held == [1, 2, 3]
        assert a.leak_report() == {1: 1, 2: 1, 3: 1, 4: 1, "window": 3}
        a.free(full)
        a.free_window(held)
        assert held == [] and a.window_used == 0 and not a.leak_report()
        with pytest.raises(ValueError, match="not held"):
            a.free_window([2])

    def test_a_window_block_without_a_full_block_is_refused(self):
        a = BlockAllocator(12, 8, window_blocks=7, ring_blocks=3)
        with pytest.raises(AssertionError, match="without a block"):
            a.grow_window([], 2)                # nothing of the full pool held
        with pytest.raises(ValueError, match="ring_blocks"):
            BlockAllocator(12, 8, window_blocks=7)
        with pytest.raises(ValueError, match="prefix cache"):
            BlockAllocator(12, 8, prefix_cache=True, window_blocks=7,
                           ring_blocks=3)
        # a model without a window: the calls are there and do nothing
        plain, held = BlockAllocator(4, 8), []
        plain.grow_window(held, 3)
        plain.free_window(held)
        assert held == [] and plain.window_used == 0

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_window_held_never_passes_full_held_on_a_random_schedule(self, seed):
        """Requests of random lengths through a pool too small for them all
        (so some are preempted and prefilled again): after every action a
        request holds min(blocks, ring) window blocks, the pool of
        ``window_pool_blocks`` never runs dry, and everything is back at the
        end."""
        rng = np.random.default_rng(seed)
        bs, R, rows, nb = 4, 3, 3, 14
        alloc = BlockAllocator(
            nb, bs, window_blocks=BlockAllocator.window_pool_blocks(nb, rows, R),
            ring_blocks=R)
        assert alloc.window_blocks == 10
        s = ContinuousBatchingScheduler(alloc, rows, 12)
        reqs = [s.add_request([1] * int(rng.integers(1, 24)),
                              max_new=int(rng.integers(2, 20)))
                for _ in range(12)]
        steps = 0
        while (action := s.next_action()) is not None:
            kind, what = action
            if kind == "prefill":
                s.record_prefill(what, 5)
            else:
                for r in list(what):
                    s.record_decode(r, 5)
            for r in s.running:
                assert len(r.window_blocks) == min(len(r.blocks), R)
                assert len(set(r.window_blocks)) == len(r.window_blocks)
            assert alloc.window_used == sum(len(r.window_blocks)
                                            for r in s.running)
            assert alloc.window_used <= alloc.num_used
            for r in reqs:
                if r.state != "running":
                    assert not r.window_blocks and not r.blocks
            steps += 1
            assert steps < 5000
        assert all(r.state == FINISHED for r in reqs)
        assert sum(r.preemptions for r in reqs) > 0
        assert alloc.window_used == 0 and not alloc.leak_report()


# --------------------------------------------------------------------- #
# engine generate_batch

class TestGenerateBatch:

    def _prompts(self, lens=(5, 11, 3, 8)):
        rng = np.random.default_rng(0)
        return [rng.integers(0, 64, size=n).astype(np.int32) for n in lens]

    def test_greedy_token_identity_vs_generate(self):
        engine = deepspeed_tpu.init_inference(
            tiny_model(), dtype="fp32",
            serving={"block_size": 8, "max_running": 2})
        prompts = self._prompts()
        outs = engine.generate_batch(prompts, max_new_tokens=8)
        assert len(outs) == len(prompts)
        for p, o in zip(prompts, outs):
            ref = engine.generate(p[None, :], max_new_tokens=8)
            np.testing.assert_array_equal(np.asarray(o), np.asarray(ref)[0])

    def test_greedy_identity_under_eviction_pressure(self):
        # 5 blocks of 8 tokens for two ~20-token streams: preemption +
        # recompute must reproduce the unconstrained tokens exactly
        engine = deepspeed_tpu.init_inference(
            tiny_model(), dtype="fp32",
            serving={"block_size": 8, "max_running": 2, "max_num_blocks": 5})
        prompts = self._prompts((5, 11))
        outs = engine.generate_batch(prompts, max_new_tokens=10)
        for p, o in zip(prompts, outs):
            ref = engine.generate(p[None, :], max_new_tokens=10)
            np.testing.assert_array_equal(np.asarray(o), np.asarray(ref)[0])

    def test_eos_retirement_matches_generate(self):
        engine = deepspeed_tpu.init_inference(
            tiny_model(), dtype="fp32",
            serving={"block_size": 8, "max_running": 2})
        prompts = self._prompts()
        free = engine.generate_batch(prompts, max_new_tokens=8)
        eos = int(np.asarray(free[0])[len(prompts[0])])  # really emitted
        outs = engine.generate_batch(prompts, max_new_tokens=8,
                                     eos_token_id=eos)
        for p, o in zip(prompts, outs):
            ref = engine.generate(p[None, :], max_new_tokens=8,
                                  eos_token_id=eos)
            np.testing.assert_array_equal(np.asarray(o), np.asarray(ref)[0])

    def test_decode_step_compiles_once(self):
        engine = deepspeed_tpu.init_inference(
            tiny_model(), dtype="fp32",
            serving={"block_size": 8, "max_running": 2})
        engine.generate_batch(self._prompts(), max_new_tokens=6)
        assert engine._paged_jits[1]._cache_size() == 1, (
            "fused decode step recompiled during serving")

    def test_paged_off_falls_back_to_static_path(self):
        engine = deepspeed_tpu.init_inference(
            tiny_model(), dtype="fp32", serving={"paged": "off"})
        prompts = self._prompts((4, 6))
        outs = engine.generate_batch(prompts, max_new_tokens=4)
        assert engine._paged_jits is None  # static path only
        for p, o in zip(prompts, outs):
            ref = engine.generate(p[None, :], max_new_tokens=4)
            np.testing.assert_array_equal(np.asarray(o), np.asarray(ref)[0])

    def test_paged_on_unsupported_raises(self):
        from deepspeed_tpu.models.bert import BertConfig, BertModel
        model = BertModel(BertConfig(vocab_size=64, max_seq=16, n_layer=1,
                                     n_head=2, d_model=16, d_ff=32))
        engine = deepspeed_tpu.init_inference(
            model, params=model.init_params(jax.random.key(0)), dtype="fp32")
        with pytest.raises(ValueError, match="causal LM"):
            engine.generate_batch([np.asarray([1, 2, 3], np.int32)],
                                  max_new_tokens=2)

    def test_sampled_mode_shapes(self):
        engine = deepspeed_tpu.init_inference(
            tiny_model(), dtype="fp32",
            serving={"block_size": 8, "max_running": 3})
        prompts = self._prompts((4, 7))
        outs = engine.generate_batch(prompts, max_new_tokens=5,
                                     temperature=0.8, top_k=10, seed=3)
        for p, o in zip(prompts, outs):
            assert o.shape == (len(p) + 5,)
            assert int(o.min()) >= 0 and int(o.max()) < 64

    def test_length_check(self):
        engine = deepspeed_tpu.init_inference(
            tiny_model(), dtype="fp32", serving={"block_size": 8})
        with pytest.raises(ValueError, match="max_seq"):
            engine.generate_batch([np.ones(60, np.int32)], max_new_tokens=10)


# --------------------------------------------------------------------- #
# the executor's table of action kinds

# what ContinuousBatchingScheduler.next_action can return
ACTION_KINDS = ("wait", "prefill", "prefill_chunk", "verify", "decode",
                "block")


@pytest.mark.parametrize("kind", ACTION_KINDS + ("defragment",))
def test_executor_table_holds_the_schedulers_action_kinds(kind, monkeypatch):
    """The session's executor has an entry for every kind the scheduler
    can hand over and for no other; handed another kind it raises (it
    used to run anything it did not know as a decode step)."""
    import inspect
    import re

    from deepspeed_tpu.inference import engine as engine_mod
    returned = set(re.findall(
        r'return \("(\w+)",', inspect.getsource(ContinuousBatchingScheduler)))
    assert returned == set(ACTION_KINDS)    # the list above is the scheduler's
    assert set(engine_mod._ACTION_KINDS) == returned
    if kind in returned:
        assert isinstance(engine_mod._ACTION_KINDS[kind],
                          engine_mod._ActionKind)
        return
    engine = deepspeed_tpu.init_inference(
        tiny_model(), dtype="fp32",
        serving={"block_size": 8, "max_running": 2})
    with engine._mesh_scope():
        session = engine.open_serve_session(max_new=4)
        try:
            monkeypatch.setattr(session.sched, "next_action",
                                lambda: (kind, []))
            with pytest.raises(ValueError, match=f"unknown kind '{kind}'"):
                session.step()
            assert session.pools_alive()    # nothing was dispatched
            assert session.contain_fault(ValueError(kind)) == "unattributed"
        finally:
            session.close()
