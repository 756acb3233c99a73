"""Continuous-batching scheduler (Orca iteration-level scheduling + vLLM
eviction/prefix-caching + Sarathi-style chunked prefill, host side).

The engine drives one *step* at a time: :meth:`next_action` returns one of

- ``("prefill", request)`` — admit the FIFO queue head into freshly
  allocated blocks and run its whole prompt (the legacy path: no prefix
  hit, chunking off);
- ``("prefill_chunk", request)`` — run the next ``chunk_tokens`` tokens of
  a mid-prefill request against its already-cached blocks (used for the
  tail after a prefix-cache hit and for chunked prefill, which interleaves
  with decode steps instead of stalling every running decode for a whole
  long prompt);
- ``("decode", running)`` — one fused decode step over every running
  request that finished prefilling;
- ``("verify", running)`` — the speculative form of the decode step
  (``spec_k > 0`` with an n-gram proposer): each request carries up to
  ``spec_k`` proposed candidate tokens in ``req.spec_tokens`` and one
  fused verify step checks all of them at once, emitting the accepted
  prefix plus one token — requests with no match ride along with an
  empty window (single-token decode inside the same program), and a
  step where NO request found a match degrades to plain ``decode``.

- ``("block", running)`` — in place of ``decode`` for a model that
  generates by diffusion over blocks (a ``generation`` record on its
  config, see below): one fused pass over every running request's open
  block.

Finished requests retire between steps (their blocks return to the pool)
and queued requests take their slots, so a convoying long request never
stalls the batch the way the static ``generate`` loop does.

**Speculative decoding** (``spec_k``/``spec_proposer``): before a decode
turn, each decode-ready request's prompt + generated history is handed to
the proposer (``inference/spec.py``) and the candidates' KV slots are
secured up front — window growth only draws on the free pool (free list +
reclaimable cold blocks) and TRUNCATES the window when it runs dry, never
preempting: speculation must not evict work plain decode would have kept,
so eviction behavior is identical with speculation on or off. After the
engine's greedy acceptance, :meth:`record_verify` commits the accepted
tokens and ROLLS BACK the rest: ``pos`` rewinds past the rejected
candidates (their k/v stays in the pools beyond ``pos`` — never read,
overwritten as decode advances) and any block that crossed its fill
boundary inside the rejected span is unregistered from the prefix cache
via ``unregister_if_owner`` — unless a first writer already owned the
hash, in which case that owner's (committed) content keeps the mapping.

Request lifecycle::

    QUEUED --admit(probe cache, alloc tail)--> RUNNING[prefilling]
       ^                                           |  chunks until pos==target
       |                                       RUNNING --eos/max_new--> FINISHED
       +--------- preempt (free ALL blocks) -------+

**Automatic prefix caching** (``prefix_caching=True``): admission probes
the allocator's content-addressed cache with the request's token prefix.
Matching FULL blocks are reused with a ref-count bump (zero prefill
compute) and only the tail is allocated + prefilled, with the request's
``pos`` starting past the cached tokens. When the ENTIRE prefix is cached
the hit is capped at ``target - 1`` tokens — logits for the last token
must still be computed to sample the continuation — which lands the
restart mid-block inside a shared block: that block is copied-on-write
(``cow_pending``: the engine device-copies it into a private block before
the tail chunk runs) because partial blocks are never shared. As a
request's blocks fill — during prefill chunks AND as decode crosses block
boundaries — they are registered back into the cache, so repeated system
prompts, multi-turn continuations, and even a preempted request's own
re-admission hit.

**Tiered KV cache** (``serving.kv_host``): with a host pool attached to
the allocator, admission's cache probe walks BOTH tiers
(``match_prefix_tiered``) — device hits acquire as always, host hits
(cold blocks demoted to host RAM instead of destroyed) read their bytes
at admission, take freshly allocated device blocks, and ride
``req.fetch_pending`` to the engine, which lands them H2D before the
request's first prefill work: a host hit is a cache hit whose tail needs
only H2D, not recompute. Promoted blocks register under their chain keys
only once the copy lands, so a preemption between admission and fetch
loses nothing (the host entries survive).

Preemption is recompute-style (vLLM's default): when a running request
needs one more KV block and the pool (free + reclaimable cold blocks) is
dry, the policy-selected victim — LATEST-admitted under the default FIFO
policy; ``inference/policy.py`` plugs in priority-class and SLA-aware
(most-TTFT-slack) victim choice, plus which waiting request admits next —
is evicted: its blocks are dereferenced and it re-queues at the FRONT
with its prompt extended by the tokens it already generated. With prefix caching on, its own still-cold
blocks usually satisfy the re-admission probe, so "recompute" preemption
costs a cache hit instead of a full re-prefill. Victim choice, the FIFO
free list, the LRU cold list, and the prefill/decode interleave toggle are
all deterministic — identical request streams schedule identically.

Bookkeeping invariant: ``req.pos`` is the number of tokens whose k/v sit in
the pools; the newest generated token (``req.last_token``) is NOT yet
cached — it is the next decode step's input, written at slot ``pos`` by
that step. Hence cached = prompt + generated[:-1], pos = len(prompt) +
len(generated) - 1 whenever the request is running (and past prefill).
While prefilling, ``pos < prefill_target == len(prefix())`` counts the
chunked/cache-hit progress.

**Generation by blocks** (``generation``: the model config's
``BlockGeneration`` record; None = one token a step). A step no longer
yields one token a row. ``pos`` is the tokens whose FINAL k/v sit in the
pools, a multiple of the generation block ``Bg``; a (re)admission prefills
the whole generation blocks of ``prefix()`` and samples nothing; what is
left of the prefix (``len % Bg`` tokens) is decided from the start in the
request's first open block, ``[pos, pos + Bg)``. Each ``block`` step is,
for a row, a DENOISE pass (``plan_block``: it decides ``n`` of the block's
undecided positions; their k/v, written to the block's slots, is
overwritten by the next pass) or, once the block is whole, its COMMIT (its
k/v stays; ``pos`` moves on by ``Bg``). A commit RIDES the launch that
opens the next block: the whole block is a second entry of the same row
(one of the step's ``ride_slots`` rider entries) and the row's pass is
denoise pass 0 of the block at ``pos + Bg``, so a block of ``steps``
passes costs a row ``steps`` steps. When more rows have a whole block
than the step has rider slots, the first ``ride_slots`` of them in
admission order ride (``_mark_rides``) and the others commit ALONE, a pass
of their own that decides nothing, as every block did before. A pool
block holds whole generation blocks, so a row's next pass needs the same
one block ahead a decode step needs, from the deepest position it writes:
``pos``, or ``pos + Bg`` for a ride (``_ensure_decode_capacity``). A token is
streamed (``generated``) once it and every token before it is decided;
``max_new`` cuts inside a block. Under the two static rules the host
knows every row's phase without reading a token (``blk_decided``,
``blk_pass`` move at the launch), so the loop runs a pass ahead; under the
confidence-threshold rule the count a pass decides is data and
``plans_ahead`` says no. A preempted request re-prefills whole blocks of
prompt + streamed tokens and re-enters its open block with what that
block had decided (``blk_landed``).

**A launched step** (the engine's loop runs one step ahead): a step's
result has a positional half that is fixed before its token is known —
``pos`` moves on, the filled blocks register, a request that reaches
``max_new`` BY COUNT gives its row and blocks back — and a token half.
``record_*`` does both at once; a loop that holds a step's tokens on the
device calls ``advance_*`` when the step is launched and
:meth:`commit_token` when it lands. In between, ``pos`` is one ahead of the
invariant above for the step's rows (``generated`` lacks the token in
flight) and a request retired by count waits in ``retiring`` for its last
token: :meth:`next_action` plans on exactly what the serial order would see,
because none of it depends on a token's value — save an EOS, which the
engine handles as an overshoot — and everything else (cancel, time-out,
preemption, fault containment) first lands the step.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import List, NamedTuple, Optional, Tuple

import numpy as np

from deepspeed_tpu.inference.block_allocator import ROOT_KEY, BlockAllocator
from deepspeed_tpu.inference.blockgen import open_block, ride_slots
from deepspeed_tpu.utils.logging import logger

QUEUED, RUNNING, FINISHED = "queued", "running", "finished"


class PoolExhausted(RuntimeError):
    """The KV pool cannot supply one more block for ``req`` and there is
    nothing to evict. The closed loop propagates this (a misconfigured
    pool should fail the call loudly); the always-on loop catches it and
    retires ``req`` with an error instead — one oversized request must
    not take the server down for everyone else."""

    def __init__(self, msg: str, req: "Request"):
        super().__init__(msg)
        self.req = req


class ServingTelemetry:
    """Registry adapter for the Orca/vLLM-style iteration-level serving
    stats: the scheduler calls these hooks as its state machine moves and
    the series land in the process-global metrics registry
    (``deepspeed_tpu.monitor.metrics``).

    Invariants the tests pin: TTFT is observed exactly ONCE per request —
    the first token after the ORIGINAL arrival, even when a preemption
    forces a re-prefill later — and ``serving/preemptions`` equals the
    number of eviction events (``serving/recompute_tokens`` the prefix
    tokens those evictions will prefill again). With prefix caching,
    ``serving/prefix_cache_hit_tokens`` counts prompt tokens whose prefill
    was SKIPPED via cache hits (hits / lookups is the admission hit rate),
    and ``serving/cold_blocks`` gauges the freed-but-cached pool blocks."""

    _SERIES = ("ttft", "tpot", "queue_wait", "queue_depth", "running",
               "kv_blocks_used", "window_blocks_used",
               "kv_blocks_free", "kv_block_utilization", "kv_fragmentation",
               "cold_blocks", "prefill_steps", "prefill_chunks",
               "prefill_tokens", "prefill_padded_tokens",
               "prefill_tokens_squared",
               "decode_steps", "decode_steps_ahead", "decode_steps_late",
               "decode_live_kv_tokens", "decode_live_kv_blocks",
               "decode_window_blocks_held", "decode_window_ring_blocks",
               "prefix_cache_lookups", "prefix_cache_hits",
               "prefix_cache_hit_tokens",
               "kv_host_blocks", "kv_host_bytes", "kv_spills",
               "kv_fetch_hits", "kv_fetch_tokens", "kv_host_errors",
               "preemptions", "recompute_tokens", "requests", "finished",
               "rejected_requests",
               "generated_tokens", "spec_verify_steps",
               "spec_proposed_tokens", "spec_accepted_tokens",
               "spec_rollbacks", "spec_acceptance_rate", "tp",
               "step_faults", "engine_restarts", "request_retries",
               "timeouts", "shed_requests", "phase_ms", "wasted_tokens")

    def __init__(self, registry=None, replica: str = "r0"):
        if registry is None:
            from deepspeed_tpu.monitor.metrics import get_registry
            registry = get_registry()
        self.registry = registry
        #: replica label stamped on phase/waste observations — mutable,
        #: the router renames engines after construction (set_replica)
        self.replica = replica
        self.ensure()

    def ensure(self) -> None:
        """Pre-create every serving family so zero-valued series (e.g. a
        run with no preemptions) still appear in snapshots. Re-run by the
        scheduler per serve call — re-creates after a registry reset."""
        for name in self._SERIES:
            getattr(self, name)
        for name, text in self._LOOP.items():
            self.registry.counter("serving/" + name, text)

    # families resolved per access (get-or-create under the registry
    # lock; serving events are host-side per engine step, not a jit hot
    # loop) so a registry reset between bench metrics can't orphan them

    @property
    def ttft(self):
        return self.registry.histogram(
            "serving/ttft_ms", "request arrival -> first generated token")

    @property
    def tpot(self):
        return self.registry.histogram(
            "serving/tpot_ms", "per-output-token latency after the first")

    @property
    def queue_wait(self):
        return self.registry.histogram(
            "serving/queue_wait_ms",
            "request submission -> first admission wait (one observation "
            "per request; preemption re-admissions do not re-observe)")

    @property
    def rejected_requests(self):
        return self.registry.counter(
            "serving/rejected_requests",
            "submissions refused by admission control (queue bound / pool "
            "pressure) before enqueueing")

    @property
    def queue_depth(self):
        return self.registry.gauge(
            "serving/queue_depth", "requests waiting for admission")

    @property
    def running(self):
        return self.registry.gauge(
            "serving/running", "running-batch occupancy (fused decode rows)")

    @property
    def kv_blocks_used(self):
        return self.registry.gauge(
            "serving/kv_blocks_used",
            "pool blocks referenced by live requests (excl. dummy)")

    @property
    def window_blocks_used(self):
        return self.registry.gauge(
            "serving/window_blocks_used",
            "blocks of the window layers' pool that live requests hold "
            "(excl. dummy; 0 for a model without window layers)")

    @property
    def decode_window_blocks_held(self):
        return self.registry.counter(
            "serving/decode_window_blocks_held",
            "per fused decode step, the window-pool blocks its live rows "
            "HOLD (min(blocks of the full pool, a ring) each): over "
            "decode_window_ring_blocks, how full the rings are")

    @property
    def decode_window_ring_blocks(self):
        return self.registry.counter(
            "serving/decode_window_ring_blocks",
            "per fused decode step, its live rows x the blocks of a whole "
            "ring: what the rows would hold with a ring each")

    @property
    def kv_blocks_free(self):
        return self.registry.gauge(
            "serving/kv_blocks_free",
            "allocatable pool blocks: free list + reclaimable cold "
            "(GLOBAL per slice under tensor parallelism — block ids are "
            "shard-invariant, see serving/tp)")

    @property
    def tp(self):
        return self.registry.gauge(
            "serving/tp",
            "tensor-parallel degree of the serving mesh: KV pools are "
            "head-sharded over tp, so block-count gauges are global per "
            "slice while per-shard pool BYTES are 1/tp")

    @property
    def kv_block_utilization(self):
        return self.registry.gauge(
            "serving/kv_block_utilization", "used / allocatable pool blocks")

    @property
    def kv_fragmentation(self):
        return self.registry.gauge(
            "serving/kv_fragmentation",
            "internal fragmentation: unfilled slot fraction of referenced "
            "blocks (capacity minus cached tokens; shared blocks counted "
            "once)")

    @property
    def cold_blocks(self):
        return self.registry.gauge(
            "serving/cold_blocks",
            "freed-but-cached blocks held for prefix reuse (LRU-reclaimed "
            "under allocation pressure)")

    @property
    def prefill_steps(self):
        return self.registry.counter(
            "serving/prefill_steps", "request admissions that scheduled "
            "prefill work (one per admission, however many chunks)")

    @property
    def prefill_chunks(self):
        return self.registry.counter(
            "serving/prefill_chunks",
            "chunked-prefill compute steps (incl. cache-hit tail chunks)")

    @property
    def prefill_tokens(self):
        return self.registry.counter(
            "serving/prefill_tokens",
            "tokens whose k/v a whole-prompt prefill or a prefill chunk "
            "computed (useful work; see prefill_padded_tokens)")

    @property
    def prefill_padded_tokens(self):
        return self.registry.counter(
            "serving/prefill_padded_tokens",
            "the same steps' compile-bucket widths: what the device "
            "computed, padding included")

    @property
    def prefill_tokens_squared(self):
        return self.registry.counter(
            "serving/prefill_tokens_squared",
            "what a causal prefill's attention is counted from: the true "
            "prompt length squared of a whole-prompt prefill; of a chunk "
            "of n tokens behind ``start`` cached ones, (start + n)^2 - "
            "start^2, so that a prompt's chunks add up to its square")

    def count_prefill(self, tokens: int, padded: int, start: int) -> None:
        """One prefill or chunk step of ``tokens`` real tokens behind
        ``start`` cached ones, where its padding was decided."""
        self.prefill_tokens.inc(tokens)
        self.prefill_padded_tokens.inc(padded)
        self.prefill_tokens_squared.inc(tokens * (2 * start + tokens))

    @property
    def decode_steps(self):
        return self.registry.counter(
            "serving/decode_steps", "fused decode steps (all rows at once)")

    @property
    def decode_steps_ahead(self):
        return self.registry.counter(
            "serving/decode_steps_ahead",
            "fused decode steps dispatched while the step before them was "
            "unfetched (their tokens fed on the device): over decode_steps, "
            "how often the loop ran a step ahead")

    @property
    def decode_steps_late(self):
        return self.registry.counter(
            "serving/decode_steps_late",
            "of decode_steps_ahead, those dispatched after the step in "
            "flight had already finished on the device: the device ran dry, "
            "a stall of the host that outlasted a device step")

    def count_gc(self, pause_ms: float, full_pause_ms: float,
                 full: int) -> None:
        """What the interpreter's garbage collector took since the serving
        loop last said (``monitor.trace.gc_totals``' growth); with zeros,
        where the loop starts, so that a window without a collection reads 0.
        Not pre-created: only an always-on loop publishes them."""
        c = self.registry.counter
        c("host/gc_pause_ms",
          "wall clock inside garbage collections of any generation, on any "
          "thread (the interpreter lock is held throughout): over the same "
          "wall clock, the share of time in which no Python thread ran"
          ).inc(pause_ms)
        c("host/gc_full_pause_ms",
          "of gc_pause_ms, inside full (generation 2) collections: over "
          "gc_full_collections, the mean full pause").inc(full_pause_ms)
        c("host/gc_full_collections",
          "full (generation 2) garbage collections").inc(full)

    #: the serving loop's own time (``monitor.trace.LoopTime``), by counter
    #: less ``serving/``: "is my loop host-bound, and is it Python or the
    #: lock". All on the host's one clock, over the loop's whole life.
    _LOOP = {
        "loop_steps":
            "session steps of the serving loop that launched or landed "
            "something: what the loop_*_ms counters are a step of",
        "loop_busy_ms":
            "wall clock of the loop's thread inside serve.step and outside "
            "serve.fetch (where it waits for the device): over loop_steps, "
            "the host's step, which bounds the loop where it is longer than "
            "the device's",
        "loop_cpu_busy_ms":
            "of loop_busy_ms, the turns of the loop on which its thread's "
            "CPU clock was read too (one in 8: that clock is a system call)",
        "loop_cpu_ms":
            "CPU time of the loop's thread over the stretches of "
            "loop_cpu_busy_ms: busy less CPU is time it wanted to run and "
            "did not (the interpreter lock held by the clients it woke, a "
            "collection on another thread, a freeze of the machine)",
        **{f"loop_{name}_ms":
           f"of the loop's thread's wall clock, inside serve.{name} ({what}) "
           "and outside any phase opened within it"
           for name, what in (
               ("schedule", "the scheduler's choice of the next action"),
               ("inputs", "a launch's operands built in numpy"),
               ("dispatch", "the call of a step's program"),
               ("sample", "the sampler's dispatch"),
               ("fetch", "the wait for a launched step's tokens: NOT part "
                         "of loop_busy_ms"),
               ("commit", "record and on_tokens row by row, the "
                          "retirements"),
               ("release", "the drop of what a landed step held"),
               ("intake", "the front-end's commands"))},
        "loop_book_ms":
            "of the loop's thread's wall clock, a step's bookkeeping between "
            "the spans (a phase with no span of its own): the launch's "
            "positional half (advance_* a row), the landing's rows, MoE "
            "counts and recorder events",
        "late_ms":
            "time the device had nothing queued, at least: at each launch "
            "behind an unfetched step, from when that step was first seen "
            "finished (is_ready() at a phase's exit) to the dispatch's "
            "return; the lower end of a bracket late_slack_ms wide",
        "late_slack_ms":
            "at those launches, from when the step was last seen unfinished "
            "to when it was first seen finished: the device was dry for "
            "late_ms at least and late_ms + late_slack_ms at most",
        "commit_sampled_rows":
            "rows of the landed steps whose commit loop was timed row by "
            "row (one landed step in 64)",
        "commit_record_ms":
            "over commit_sampled_rows, inside the scheduler's record (the "
            "token's commit, a retirement)",
        "commit_wake_ms":
            "over commit_sampled_rows, inside on_tokens (the row's client "
            "handed its token and woken)",
    }

    def count_loop(self, grown) -> None:
        """What the serving loop's own time grew by since it last said
        (``LoopTime.take``: ``*_ms`` in ns, the others counts), published
        where ``count_gc`` is. ``serving/loop_kv_fetch_ms`` (the host
        tier's blocks landing ahead of a prefill) exists only once that
        has happened; every other family is pre-created."""
        c = self.registry.counter
        for name, n in grown.items():
            if n:
                c("serving/" + name, self._LOOP.get(name, "")).inc(
                    n / 1e6 if name.endswith("_ms") else n)

    @property
    def decode_live_kv_tokens(self):
        return self.registry.counter(
            "serving/decode_live_kv_tokens",
            "per fused decode step, the sum of its rows' positions: over "
            "decode_steps, the KV tokens a decode step really reads")

    @property
    def decode_live_kv_blocks(self):
        return self.registry.counter(
            "serving/decode_live_kv_blocks",
            "per fused decode step, the sum over its live rows of pos // "
            "block_size + 1: the block copies the paged kernel issues a "
            "layer and pool (it copies nothing for an idle row)")

    def count_state(self, rows: int) -> None:
        """One fused decode step of a model with recurrent state over
        ``rows`` live rows. Not pre-created: a model without state has
        neither this nor ``state_slot_resets``."""
        self.registry.counter(
            "serving/decode_state_rows",
            "per fused decode step, the live rows whose recurrent state it "
            "updates: over decode_steps, the states a step reads and writes"
        ).inc(rows)

    def count_window(self, pos, rows: int, window: int, bs: int,
                     held: int, ring: int) -> None:
        """One fused decode step of a model with window layers: ``pos``
        [W] the step's rows' depths (idle rows 0), the first ``rows`` live,
        which hold ``held`` blocks of the window pool where a ring has
        ``ring``. The live counters are not pre-created: a model without a
        window has none of them."""
        self.decode_window_blocks_held.inc(held)
        self.decode_window_ring_blocks.inc(rows * ring)
        c = self.registry.counter
        first = np.maximum(pos - (window - 1), 0) // bs
        c("serving/decode_live_window_kv_tokens",
          "per fused decode step, the sum over its live rows of min(pos + 1, "
          "window): the KV tokens a WINDOW layer's decode step really reads "
          "(decode_live_kv_tokens is what a full layer reads)"
          ).inc(int(np.minimum(pos[:rows] + 1, window).sum()))
        c("serving/decode_live_window_kv_blocks",
          "per fused decode step, the sum over its live rows of the blocks "
          "from the window's first to the row's newest: the block copies "
          "the paged kernel issues a window layer and pool (it copies "
          "nothing for an idle row)"
          ).inc(int((pos // bs + 1 - first)[:rows].sum()))

    def count_state_reset(self) -> None:
        self.registry.counter(
            "serving/state_slot_resets",
            "prefill pieces that started a state slot from zero: a "
            "request's first, and its first again after each recompute"
        ).inc()

    def count_block(self, rows: int, commits: int, rides: int) -> None:
        """One fused block step (generation by diffusion over blocks) over
        ``rows`` live rows: ``commits`` of them commit alone (their pass
        decides nothing), ``rides`` more commit as rider entries beside
        their next block's first pass. Not pre-created: a model that
        generates a token a step has none of the ``serving/block_*``
        counters."""
        c = self.registry.counter
        c("serving/block_passes",
          "fused block steps: one pass over every running row's open block"
          ).inc()
        c("serving/block_row_passes",
          "live rows summed over the block steps: the passes rows took (a "
          "row whose commit rides took one)").inc(rows)
        c("serving/block_commit_row_passes",
          "of block_row_passes, the lone commits: a pass that wrote a whole "
          "block's final KV, moved the row on and decided nothing, for want "
          "of a rider slot").inc(commits)
        c("serving/block_commit_rides",
          "commits that rode the pass opening the next block, as a second "
          "entry of the same row: with block_commit_row_passes, the blocks "
          "committed").inc(rides)

    def count_block_decided(self, n: int) -> None:
        self.registry.counter(
            "serving/block_decided_tokens",
            "positions the denoise passes decided (a prompt's remainder in "
            "its first block is not one): over block_row_passes, the tokens "
            "a row's pass yields").inc(n)

    def count_moe(self, counts, zero_experts: bool = False,
                  row_tile: int = 0) -> None:
        """One fused decode step of an MoE model. ``counts`` [L, E + 1], the
        program's own: the assignments each expert of each layer computed
        (padding rows excluded) and, in the last column, those the layer
        owed (real rows x k). ``zero_experts`` (a model with zero-compute
        experts): two more columns, the assignments those took and all the
        router made. ``row_tile``: the rows of an expert's own a visit of
        the grouped expert kernel computes at a time in this step's call (0:
        all rows ride every visit, or another form ran). The
        ``serving/moe_*`` counters exist only once
        an MoE model has decoded (they are not pre-created: a reader that
        requires them finds nothing under a dense model); resolved per
        access like every family here (0.4 us each), so a registry reset
        cannot orphan them."""
        c = self.registry.counter
        if zero_experts:
            c("serving/moe_zero_expert_assignments",
              "assignments of real rows that chose a zero-compute (identity) "
              "expert: over moe_router_assignments, the share of the "
              "router's picks that cost no expert's weights"
              ).inc(int(counts[:, -2].sum()))
            c("serving/moe_router_assignments",
              "every assignment the routers made for real rows (rows x k), "
              "those to zero-compute experts and to experts held on other "
              "chips among them").inc(int(counts[:, -1].sum()))
            counts = counts[:, :-2]
        computed, owed = counts[:, :-1], counts[:, -1]
        c("serving/moe_layer_steps",
          "MoE layers run by fused decode steps (steps x layers)"
          ).inc(counts.shape[0])
        c("serving/moe_assignments",
          "(row, expert) pairs the decode steps' MoE layers computed"
          ).inc(int(computed.sum()))
        c("serving/moe_experts_touched",
          "experts with at least one row, summed over layers and decode "
          "steps: over moe_layer_steps, the expert weights a layer reads"
          ).inc(int((computed > 0).sum()))
        if row_tile:
            c("serving/moe_expert_row_tiles",
              "row tiles the grouped expert kernel computed (an expert's "
              "own rows, a tile at a time), summed over layers and decode "
              "steps whose call is past one row tile: over "
              "moe_experts_touched, 1.0 when no expert needed a second"
              ).inc(int((-(-computed // row_tile)).sum()))
        c("serving/moe_max_expert_load",
          "rows of the busiest expert, summed over layers and decode steps"
          ).inc(int(computed.max(axis=1).sum()))
        c("serving/moe_dropped_assignments",
          "assignments of real rows that no expert computed (0 on the "
          "no-drop dispatch; capacity overflow on the capacity dispatch)"
          ).inc(int(owed.sum() - computed.sum()))

    @property
    def prefix_cache_lookups(self):
        return self.registry.counter(
            "serving/prefix_cache_lookups", "admission-time cache probes")

    @property
    def prefix_cache_hits(self):
        return self.registry.counter(
            "serving/prefix_cache_hits",
            "admission probes that matched at least one full block")

    @property
    def prefix_cache_hit_tokens(self):
        return self.registry.counter(
            "serving/prefix_cache_hit_tokens",
            "prompt tokens whose prefill was skipped via cache hits")

    @property
    def kv_host_blocks(self):
        return self.registry.gauge(
            "serving/kv_host_blocks",
            "demoted KV blocks resident in the host-RAM tier (tiered KV "
            "cache; LRU-bounded by serving.kv_host.max_host_blocks)")

    @property
    def kv_host_bytes(self):
        return self.registry.gauge(
            "serving/kv_host_bytes",
            "host RAM held by demoted KV blocks (k+v slices)")

    @property
    def kv_spills(self):
        return self.registry.counter(
            "serving/kv_spills",
            "cold blocks demoted D2H to the host pool instead of being "
            "destroyed under allocation pressure")

    @property
    def kv_fetch_hits(self):
        return self.registry.counter(
            "serving/kv_fetch_hits",
            "admission prefix probes served from the host tier: demoted "
            "blocks re-materialized H2D instead of recomputed (counted "
            "per block)")

    @property
    def kv_fetch_tokens(self):
        return self.registry.counter(
            "serving/kv_fetch_tokens",
            "prompt tokens whose prefill was skipped via host-tier "
            "fetches (subset of prefix_cache_hit_tokens)")

    @property
    def kv_host_errors(self):
        return self.registry.counter(
            "serving/kv_host_errors",
            "D2H/H2D failures degraded to destroy-on-reclaim / recompute "
            "(allocation errors, injected I/O faults)")

    @property
    def preemptions(self):
        return self.registry.counter(
            "serving/preemptions", "recompute-preempt eviction events")

    @property
    def recompute_tokens(self):
        return self.registry.counter(
            "serving/recompute_tokens",
            "prefix tokens re-prefilled by evictions")

    @property
    def requests(self):
        return self.registry.counter("serving/requests")

    @property
    def finished(self):
        return self.registry.counter("serving/finished_requests")

    @property
    def generated_tokens(self):
        return self.registry.counter("serving/generated_tokens")

    @property
    def spec_verify_steps(self):
        return self.registry.counter(
            "serving/spec_verify_steps",
            "fused speculative verify steps (all rows at once)")

    @property
    def spec_proposed_tokens(self):
        return self.registry.counter(
            "serving/spec_proposed_tokens",
            "candidate tokens proposed by the n-gram speculator")

    @property
    def spec_accepted_tokens(self):
        return self.registry.counter(
            "serving/spec_accepted_tokens",
            "proposed candidates greedy verification accepted")

    @property
    def spec_rollbacks(self):
        return self.registry.counter(
            "serving/spec_rollbacks",
            "verify steps that rejected candidates (pos rewound, "
            "uncommitted prefix-cache registrations withdrawn)")

    @property
    def spec_acceptance_rate(self):
        return self.registry.gauge(
            "serving/spec_acceptance_rate",
            "accepted / proposed candidate tokens (cumulative)")

    # ---- serving-plane fault tolerance (inference/serve.py) ---- #

    @property
    def step_faults(self):
        return self.registry.counter(
            "serving/step_faults",
            "engine-step exceptions contained by the serving loop, by "
            "dispatch site (per-request retry/quarantine or engine "
            "restart — the loop survived either way)", labelnames=("kind",))

    @property
    def engine_restarts(self):
        return self.registry.counter(
            "serving/engine_restarts",
            "crash-safe engine recoveries: pool workspace + fused jits "
            "rebuilt, in-flight requests re-admitted from prompt+generated")

    @property
    def request_retries(self):
        return self.registry.counter(
            "serving/request_retries",
            "per-request fault retries: the faulting action's requests "
            "re-queued through recompute-preemption with logical-step "
            "backoff")

    @property
    def timeouts(self):
        return self.registry.counter(
            "serving/timeouts",
            "requests retired for exceeding their deadline (deadline_ms "
            "wall clock / deadline_steps scheduler clock)")

    @property
    def shed_requests(self):
        return self.registry.counter(
            "serving/shed_requests",
            "queued requests dropped by load shedding under queue "
            "pressure (policy select_shed_victim, lowest priority first)")

    # ---- request latency anatomy (phase ledger) ---- #

    @property
    def phase_ms(self):
        return self.registry.histogram(
            "serving/phase_ms",
            "per-request latency anatomy, one histogram per phase and "
            "replica: TTFT = intake + queue + prefill (+ fetch) + "
            "first decode; TPOT = scheduler wait + decode step. Phases "
            "with device work observe at the recorder's sync points, so "
            "they populate when telemetry.events is on",
            labelnames=("phase", "replica"))

    @property
    def wasted_tokens(self):
        return self.registry.counter(
            "serving/wasted_tokens",
            "tokens whose compute produced no delivered output, by cause: "
            "recompute (preemption re-prefill), spec_reject (verify "
            "rollback), timeout / shed (retired unfinished), failover "
            "(sibling re-derived a failed replica's progress) — the "
            "goodput-vs-throughput gap", labelnames=("cause", "replica"))

    def phase(self, phase: str, ms: float, rid=None) -> None:
        """Observe one phase-ledger sample (exemplar = the request id, so
        a p99 bucket links back to the merged trace's request track)."""
        self.phase_ms.labels(phase=phase, replica=self.replica).observe(
            ms, exemplar={"rid": str(rid)} if rid is not None else None)

    def waste(self, cause: str, n) -> None:
        """Count wasted tokens (``n == 0`` still materializes the series,
        so a fleet scrape shows every cause it is tracking)."""
        self.wasted_tokens.labels(cause=cause,
                                  replica=self.replica).inc(int(n))


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray              # [P] int32, immutable
    max_new: int
    eos: Optional[int] = None
    state: str = QUEUED
    blocks: List[int] = dataclasses.field(default_factory=list)
    state_slot: int = 0             # its row of the state pools, held like
    # its blocks from admission to release (0: none, or a model without)
    # its blocks of the window layers' pool in ring order, the window
    # layers' table: one more with each block of ``blocks`` up to a ring
    # (``BlockAllocator.grow_window``); empty for a model without a window
    window_blocks: List[int] = dataclasses.field(default_factory=list)
    pos: int = 0                    # tokens currently cached in the pools
    generated: List[int] = dataclasses.field(default_factory=list)
    admit_seq: int = -1             # admission stamp (eviction order)
    preemptions: int = 0
    t_arrival: float = 0.0          # perf_counter at add_request
    t_submit: float = 0.0           # perf_counter at SUBMISSION (async
    # front-end hand-off; == t_arrival for closed-loop generate_batch) —
    # the serving/queue_wait_ms base
    t_first_token: Optional[float] = None   # TTFT stamp (set once, ever)
    t_last_token: float = 0.0       # previous token's stamp (TPOT base)
    # ---- causal trace context (fleet tracing) ----
    trace: Optional[str] = None     # trace id minted at router intake and
    # carried across the prefill->decode handoff (requests sharing it are
    # one causal chain; the fleet renderer stitches them with flow arrows)
    parent: Optional[int] = None    # parent span = the rid of the
    # upstream hop (the prefill-side warm rid on the decode replica)
    # ---- scheduling-policy inputs (inference/policy.py) ----
    priority: int = 0               # PriorityPolicy class (higher = sooner)
    ttft_budget: Optional[int] = None  # SlaPolicy: scheduler steps past
    # arrival_step before the first token is late (logical clock, not ms)
    arrival_step: int = 0           # sched.step_seq at enqueue
    cancelled: bool = False         # retired by cancellation, not eos/max
    # ---- deadlines / fault containment (serving.fault) ----
    deadline_ms: Optional[float] = None   # wall-clock budget from t_submit;
    # expiry retires the request as timeout (checked at scheduler action
    # boundaries + the async front-end's intake)
    deadline_steps: Optional[int] = None  # logical-step budget on the
    # scheduler clock (like ttft_budget: replay-deterministic)
    timed_out: bool = False         # retired by deadline expiry
    shed: bool = False              # dropped by load shedding while queued
    retry_count: int = 0            # per-request step-fault retries so far
    retry_at_step: int = 0          # backoff hold-down: not admittable
    # before sched.step_seq reaches this (exponential in logical steps)
    # ---- prefix caching / chunked prefill state ----
    prefilling: bool = False        # admitted but pos < prefill_target
    prefill_target: int = 0         # len(prefix()) captured at admission
    keys: List[bytes] = dataclasses.field(default_factory=list)
    # chain keys of this request's REGISTERED-or-matched full blocks
    cow_pending: Optional[Tuple[int, int]] = None  # (src, dst) device copy
    # host-tier fetches the engine must land H2D before this request's
    # next prefill work: (dst_block, chain_key_or_None, k_np, v_np,
    # tokens) per demoted block — key None for the COW split's private
    # (unregistered) copy, tokens the prompt tokens the fetch saves from
    # recompute (the engine's kv_fetch counter base). Bytes in hand, so a
    # host-LRU eviction after admission is safe.
    fetch_pending: List[Tuple] = dataclasses.field(default_factory=list)
    error: Optional[str] = None     # set when retired without completing
    # ---- speculative decoding state ----
    spec_tokens: Tuple[int, ...] = ()  # candidates for the pending verify
    # ---- generation by blocks (a model with a generation record) ----
    blk_start: int = -1             # absolute position of the open block
    blk_decided: int = 0            # its decided positions, launched passes
    # counted in (under the data-dependent rule: as of the last landing)
    blk_pass: int = 0               # denoise passes launched on it
    blk_ride: bool = False          # its next step commits it as a rider
    # and opens the next block (``_mark_rides``, anew before every block step)
    blk_landed: Optional[Tuple] = None  # (start, state [Bg] int32, passes):
    # the open block as the newest landed denoise pass left it, what a
    # re-admission re-enters it with

    def prefix(self) -> np.ndarray:
        """The token prefix a (re)admission must have cached before decode
        resumes: the prompt plus every already-generated token. Prefill
        caches k/v for ALL of them (minus any prefix-cache hit) and samples
        the next (new) token from the last position — so a recomputed
        request continues exactly where it left off (greedy decoding
        reproduces the unpreempted continuation)."""
        if not self.generated:
            return self.prompt
        return np.concatenate([self.prompt,
                               np.asarray(self.generated, np.int32)])

    @property
    def last_token(self) -> Optional[int]:
        return self.generated[-1] if self.generated else None

    @property
    def output(self) -> np.ndarray:
        return np.concatenate([self.prompt,
                               np.asarray(self.generated, np.int32)])


class BlockStep(NamedTuple):
    """What one fused block step is for a row (generation by blocks):
    ``plan_block`` makes it, the session lays its entries out by it, and
    ``advance_block`` / ``record_block`` take its fields in this order."""
    #: the row's open block is whole: this launch writes its final k/v
    commit: bool
    #: positions the row's denoise pass decides at least (a lone commit: 0)
    n: int
    #: that pass's index in its block
    i: int
    #: the commit rides: the whole block is a rider entry and the row's own
    #: entry is pass 0 of the NEXT block; False with ``commit``: the commit
    #: takes the row's pass alone
    ride: bool = False

    @property
    def alone(self) -> bool:
        """A lone commit: the row's pass writes the whole block's final k/v
        and decides nothing."""
        return self.commit and not self.ride


class ContinuousBatchingScheduler:
    """FIFO admission (with optional prefix-cache probe), chunked prefill
    interleaved with fused decode over all running requests, retire on
    eos/max_new, recompute-preempt the latest-admitted request on OOM."""

    def __init__(self, allocator: BlockAllocator, max_running: int,
                 max_blocks_per_seq: int,
                 telemetry: Optional[ServingTelemetry] = None,
                 prefix_caching: bool = False, chunk_tokens: int = 0,
                 events=None, rid_base: int = 0,
                 spec_k: int = 0, spec_proposer=None, policy=None,
                 generation=None):
        if max_running < 1:
            raise ValueError("max_running must be >= 1")
        if chunk_tokens < 0:
            raise ValueError("chunk_tokens must be >= 0 (0 = whole-prompt)")
        if spec_k < 0:
            raise ValueError("spec_k must be >= 0 (0 = speculation off)")
        if generation is not None and (
                spec_k or allocator.block_size % generation.block):
            raise ValueError(
                "generation by blocks takes no speculation (a pass already "
                "carries a block), and a pool block holds whole generation "
                "blocks")
        self.allocator = allocator
        self.max_running = max_running
        self.max_blocks_per_seq = max_blocks_per_seq
        #: the model's BlockGeneration record; None: a token a step
        self.gen = generation
        #: rider entries of a fused block step, behind its ``max_running``
        #: main entries: the one program width, from the rows and the record
        self.ride_slots = 0 if generation is None else ride_slots(
            generation, max_running)
        self.prefix_caching = prefix_caching and allocator.prefix_cache
        # chunk_tokens and spec_k are runtime-mutable by contract: the
        # adaptive controller (monitor/controller.py) lowers them under
        # SLO burn and restores them under headroom, always between steps
        # on the serving thread, and only to values inside the compile
        # buckets the engine already owns (128-multiple chunks; spec k
        # within its fixed pow2 verify window)
        self.chunk_tokens = chunk_tokens
        # speculative decoding: propose up to spec_k candidates per decode-
        # ready request and verify them in one fused step (0/None = off)
        self.spec_k = spec_k if spec_proposer is not None else 0
        self.spec_proposer = spec_proposer
        # plain host counters, always on (the engine/tests read step
        # accounting from here even with the metrics registry disabled):
        # accepted_tokens_per_step = emitted_tokens / (decode + verify)
        self.stats = {"decode_steps": 0, "verify_steps": 0,
                      "emitted_tokens": 0, "spec_proposed": 0,
                      "spec_accepted": 0, "spec_rollbacks": 0,
                      "preemptions": 0, "decode_steps_ahead": 0,
                      "decode_steps_late": 0}
        self.telemetry = telemetry
        # flight recorder (monitor/events.py): None when disabled, so
        # every emit site below gates at one None check
        self.events = events
        if telemetry is not None:
            telemetry.ensure()
        # scheduling policy (inference/policy.py): admission pick, victim
        # pick, submission-time admission control. None = the FIFO rules
        # every decision here used before policies existed.
        if policy is None:
            from deepspeed_tpu.inference.policy import FifoPolicy
            policy = FifoPolicy()
        self.policy = policy
        # logical clock: one tick per compute action handed to the engine.
        # SlaPolicy measures TTFT slack against THIS (replay-deterministic),
        # never against wall time.
        self.step_seq = 0
        self.waiting: deque = deque()
        self.running: List[Request] = []   # admission-ordered
        # retired BY COUNT when their last step was launched (row and
        # blocks handed on), waiting for that step's token to land
        self.retiring: List[Request] = []
        self.finished: List[Request] = []
        self._admit_counter = 0
        # rid_base: the engine threads a per-engine offset through so rids
        # stay unique ACROSS generate_batch calls — the flight recorder's
        # request identity must not collide between serve calls
        self._next_rid = int(rid_base)
        # prefill/decode interleave: after a chunk, give decode a turn (when
        # decodable rows exist) so one long prompt never monopolizes steps
        self._decode_turn = False
        # deadline-free workloads (every closed-loop generate_batch, any
        # serve that never sets a deadline) must not pay the per-action
        # expiry sweep: one integer check, counting LIVE deadline-carrying
        # requests — the sweep cost ends when the last of them retires
        self._deadline_live = 0

    def _tel_gauges(self) -> None:
        """Refresh the occupancy gauges (queue depth, running rows, KV
        pool utilization) from current scheduler/allocator state, and
        emit the flight-recorder occupancy sample (the serving trace's
        counter-track source) at the same transitions."""
        ev = self.events
        if ev is not None:
            a = self.allocator
            ev.emit("sched.gauge", queued=len(self.waiting),
                    running=len(self.running), kv_used=a.num_used,
                    kv_free=a.num_free)
        t = self.telemetry
        if t is None:
            return
        a = self.allocator
        t.queue_depth.set(len(self.waiting))
        t.running.set(len(self.running))
        used = a.num_used
        t.kv_blocks_used.set(used)
        t.window_blocks_used.set(a.window_used)
        t.kv_blocks_free.set(a.num_free)
        t.cold_blocks.set(a.num_cold)
        hp = a.host_pool
        if hp is not None:
            t.kv_host_blocks.set(hp.num_blocks)
            t.kv_host_bytes.set(hp.nbytes)
        t.kv_block_utilization.set(used / max(1, a.capacity))
        # internal fragmentation: slots allocated to requests but not yet
        # holding cached k/v (last-block waste + blocks grown ahead of
        # pos). Shared blocks count ONCE (dedup by block id); a mid-prefill
        # request counts its whole target as cached — its blocks are spoken
        # for, not wasted, and the gauge would otherwise spike at admission
        bs = a.block_size
        if a.prefix_cache:
            fills = {}
            for r in self.running:
                c = r.prefill_target if r.prefilling else r.pos
                for j, b in enumerate(r.blocks):
                    f = min(bs, max(0, c - j * bs))
                    if f > fills.get(b, 0):
                        fills[b] = f
            cached = sum(fills.values())
        else:
            # without the content-addressed cache no block is shared, and a
            # request's blocks fill in order: the same sum a request and
            # not a block (256 rows of 10-20 blocks were 1 ms of this
            # loop at every action, PERF.md section 6, PR 52)
            cached = sum(
                min(max(r.prefill_target if r.prefilling else r.pos, 0),
                    len(r.blocks) * bs) for r in self.running)
        cap = used * bs
        t.kv_fragmentation.set(1.0 - cached / cap if cap > 0 else 0.0)

    # ------------------------------------------------------------------ #

    def add_request(self, prompt, max_new: int,
                    eos: Optional[int] = None, priority: int = 0,
                    ttft_budget: Optional[int] = None,
                    t_submit: Optional[float] = None,
                    deadline_ms: Optional[float] = None,
                    deadline_steps: Optional[int] = None,
                    trace: Optional[str] = None,
                    parent: Optional[int] = None) -> Request:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        total = prompt.size + max_new
        cap = self.max_blocks_per_seq * self.allocator.block_size
        if total > cap:
            raise ValueError(
                f"request needs {total} KV slots but the block table holds "
                f"{cap} ({self.max_blocks_per_seq} blocks of "
                f"{self.allocator.block_size})")
        # admission livelock guard: a prompt that needs more blocks than the
        # pool can EVER supply would sit at the FIFO head forever, starving
        # everything queued behind it — reject it up front instead
        need = self.allocator.blocks_for_tokens(prompt.size)
        if need > self.allocator.capacity:
            raise ValueError(
                f"prompt of {prompt.size} tokens needs {need} KV blocks but "
                f"the pool only has {self.allocator.capacity} allocatable "
                f"blocks in total — it can never be admitted; raise "
                "serving.max_num_blocks or shorten the prompt")
        now = time.perf_counter()
        req = Request(rid=self._next_rid, prompt=prompt, max_new=max_new,
                      eos=eos, t_arrival=now,
                      t_submit=t_submit if t_submit is not None else now,
                      # coerce HERE so a garbage budget fails the one
                      # submission (ValueError/TypeError at add time), not
                      # SlaPolicy's slack math mid-loop for everyone
                      priority=int(priority),
                      ttft_budget=(None if ttft_budget is None
                                   else int(ttft_budget)),
                      deadline_ms=(None if deadline_ms is None
                                   else float(deadline_ms)),
                      deadline_steps=(None if deadline_steps is None
                                      else int(deadline_steps)),
                      arrival_step=self.step_seq,
                      trace=(None if trace is None else str(trace)),
                      parent=(None if parent is None else int(parent)))
        self._next_rid += 1
        if req.deadline_ms is not None or req.deadline_steps is not None:
            self._deadline_live += 1
        self.waiting.append(req)
        if self.events is not None:
            # the trace context rides the enqueue: rids sharing a trace id
            # are one causal chain (the fleet renderer's flow-arrow source)
            ctx = {}
            if req.trace is not None:
                ctx["trace"] = req.trace
            if req.parent is not None:
                ctx["parent"] = req.parent
            self.events.emit("req.enqueue", rid=req.rid,
                             prompt_tokens=int(prompt.size),
                             max_new=max_new, **ctx)
        if self.telemetry is not None:
            self.telemetry.requests.inc()
        self._tel_gauges()
        return req

    def all_done(self) -> bool:
        return not self.waiting and not self.running and not self.retiring

    def _deadline_retired(self, req: Request) -> None:
        """Called at every permanent retirement: the deadline sweep's
        cost ends when the last live deadline-carrying request leaves."""
        if req.deadline_ms is not None or req.deadline_steps is not None:
            self._deadline_live -= 1

    def cancel_request(self, req: Request) -> bool:
        """Retire ``req`` by cancellation at any lifecycle point: a QUEUED
        request leaves the waiting queue, a RUNNING one leaves the batch
        with ALL its blocks dereferenced (prefix-cache registrations stay
        — committed content another request may hit). The request lands in
        ``finished`` with ``cancelled=True`` and whatever it generated so
        far. Returns False when it had already finished (nothing to do).
        The caller owns the engine-step boundary: cancellations must land
        BETWEEN scheduler actions, never between a returned action and its
        ``record_*`` callback."""
        return self._force_retire(req, error=None)

    def fail_request(self, req: Request, error: str) -> bool:
        """Retire ``req`` with ``error`` at any lifecycle point — the
        always-on loop's answer to :class:`PoolExhausted` (and to a
        quarantined poison request): same cleanup as
        :meth:`cancel_request`, but the request's handle terminates with
        status "error" while the loop keeps serving everyone else."""
        return self._force_retire(req, error=str(error))

    def timeout_request(self, req: Request, error: str) -> bool:
        """Retire ``req`` as a deadline expiry (``req.timed_out``): same
        cleanup as :meth:`cancel_request`, emitting ``req.timeout`` and
        counting ``serving/timeouts`` — the handle terminates with status
        "timeout" (HTTP 504 / SSE ``finish_reason: "timeout"``)."""
        return self._force_retire(req, error=str(error), flavor="timeout")

    def shed_request(self, req: Request) -> bool:
        """Drop a QUEUED ``req`` under load-shedding pressure: emits
        ``req.shed`` and counts ``serving/shed_requests``; the handle
        terminates with status "rejected" (HTTP 429 + Retry-After). Only
        waiting requests shed — running work is never abandoned for
        backpressure (preemption owns pool pressure)."""
        if req.state != QUEUED:
            raise ValueError(
                f"request {req.rid} is {req.state}; only QUEUED requests "
                "can be shed")
        return self._force_retire(
            req, error="shed under queue pressure", flavor="shed")

    def _force_retire(self, req: Request, error: Optional[str],
                      flavor: str = "error") -> bool:
        if req.state == FINISHED:
            return False
        if req.state == QUEUED:
            for i, r in enumerate(self.waiting):   # identity, not __eq__
                if r is req:
                    del self.waiting[i]
                    break
            else:
                raise ValueError(f"request {req.rid} is QUEUED but not in "
                                 "this scheduler's waiting queue")
        else:
            self.running.remove(req)
            self._free_blocks(req)
        req.spec_tokens = ()
        req.state = FINISHED
        self._deadline_retired(req)
        self.finished.append(req)
        if error is None:
            req.cancelled = True
            if self.events is not None:
                self.events.emit("req.cancel", rid=req.rid,
                                 generated=len(req.generated))
        else:
            req.error = error
            logger.warning(f"request {req.rid} retired: {error}")
            if flavor == "timeout":
                req.timed_out = True
                if self.telemetry is not None:
                    self.telemetry.timeouts.inc()
                    # everything generated dies with the deadline: the
                    # client gets a 504, not the tokens
                    self.telemetry.waste("timeout", len(req.generated))
                if self.events is not None:
                    self.events.emit("req.timeout", rid=req.rid,
                                     generated=len(req.generated),
                                     error=error)
            elif flavor == "shed":
                req.shed = True
                if self.telemetry is not None:
                    self.telemetry.shed_requests.inc()
                    # shed requests are QUEUED (generated == 0): the zero
                    # inc still materializes the cause series
                    self.telemetry.waste("shed", len(req.generated))
                if self.events is not None:
                    self.events.emit("req.shed", rid=req.rid,
                                     priority=req.priority)
            elif self.events is not None:
                self.events.emit("req.retire", rid=req.rid,
                                 generated=len(req.generated), error=error)
        if self.telemetry is not None:
            self.telemetry.finished.inc()
        self._tel_gauges()
        return True

    def requeue_for_retry(self, req: Request, backoff_steps: int,
                          error: str = "") -> None:
        """Per-request step-fault containment: re-queue a RUNNING request
        through the recompute-preemption machinery (all blocks
        dereferenced, prompt + generated becomes the new prefix — with
        prefix caching its own still-cold blocks usually satisfy the
        re-admission) with an admission hold-down of ``backoff_steps``
        LOGICAL steps (the ``step_seq`` clock, replay-deterministic).
        Greedy decoding reproduces the un-faulted continuation exactly,
        the same guarantee preemption has always carried."""
        if req.state != RUNNING:
            raise ValueError(
                f"request {req.rid} is {req.state}; only RUNNING requests "
                "retry through re-queue")
        if self.events is not None:
            self.events.emit("req.requeue", rid=req.rid,
                             retry=req.retry_count,
                             backoff_steps=int(backoff_steps), error=error)
        if self.telemetry is not None:
            self.telemetry.request_retries.inc()
        # FRONT of the queue like preemption: the backoff hold-down, not
        # queue position, is what delays the retry
        self._demote_to_queue(req)
        req.retry_at_step = self.step_seq + max(int(backoff_steps), 0)
        self._tel_gauges()

    def reset_pool(self, allocator: BlockAllocator) -> None:
        """Crash-safe engine recovery: the device pools died mid-step, so
        every block placement is invalid. Swap in the freshly built
        ``allocator`` and re-queue ALL running requests from prompt +
        generated tokens — exactly the state recompute-preemption already
        proves sufficient to continue greedy-identically. Admission order
        is preserved (earlier-admitted requests re-admit first, ahead of
        anything that was still waiting). The old allocator's refs are
        dereferenced first — pure host bookkeeping (the spill hook was
        already cleared; the buffers its cold cache would describe are
        gone either way) — so an abandoned allocator ends consistent,
        which is what the leak-audit fixtures assert."""
        for req in list(self.running)[::-1]:  # earliest ends at the front
            self._demote_to_queue(req)
        self.allocator = allocator
        self._decode_turn = False
        self._tel_gauges()

    def _demote_to_queue(self, req: Request) -> None:
        """The ONE RUNNING -> QUEUED demotion (preemption, step-fault
        retry, engine restart): every block dereferenced, prefill state
        reset so prompt + generated becomes the re-admission prefix, and
        the request re-queued at the FRONT. A Request field that must
        clear on demotion belongs here (or in ``_free_blocks``), never in
        one caller."""
        self.running.remove(req)
        self._free_blocks(req)
        req.pos = 0
        req.prefilling = False
        req.prefill_target = 0
        req.spec_tokens = ()
        req.state = QUEUED
        self.waiting.appendleft(req)

    # ------------------------------------------------------------------ #
    # admission

    def _try_admit(self) -> Optional[Tuple[str, Request]]:
        """Admit the FIFO queue head when a slot and its (tail) blocks are
        available: probe the prefix cache, acquire the hit, allocate only
        the rest, and start the request's ``pos`` past the cached tokens.
        Returns the prefill action, or None when nothing was admitted."""
        if not self.waiting or len(self.running) >= self.max_running:
            return None
        # the policy picks WHICH waiting request this attempt tries (FIFO:
        # the head); one candidate per attempt keeps admission all-or-
        # nothing and deterministic
        idx = int(self.policy.select_admission(self))
        if not 0 <= idx < len(self.waiting):
            raise ValueError(
                f"policy {self.policy.name!r} selected waiting index {idx} "
                f"out of range (queue depth {len(self.waiting)})")
        req = self.waiting[idx]
        if req.retry_at_step > self.step_seq:
            # the policy's pick is holding down after a step-fault retry
            # (exponential backoff on the logical clock): take the first
            # ELIGIBLE waiting request in FIFO order instead, or admit
            # nothing this step — the backoff must never starve the rest
            # of the queue, and FIFO-among-eligible keeps it deterministic
            for j, r in enumerate(self.waiting):
                if r.retry_at_step <= self.step_seq:
                    idx, req = j, r
                    break
            else:
                return None
        prefix = req.prefix()
        if self.gen is not None:
            # whole generation blocks; the rest enters the open block
            prefix = prefix[:prefix.size // self.gen.block * self.gen.block]
        target = int(prefix.size)
        bs = self.allocator.block_size
        need_total = self.allocator.blocks_for_tokens(target)
        if need_total > self.allocator.capacity:
            # prompt fit at add_request but preemption-appended generated
            # tokens grew the prefix past the whole pool: retire with an
            # error instead of wedging the FIFO head forever
            del self.waiting[idx]
            req.state = FINISHED
            self._deadline_retired(req)
            req.error = (
                f"prefix of {target} tokens (prompt + {len(req.generated)} "
                f"generated) needs {need_total} KV blocks but the pool has "
                f"{self.allocator.capacity}; raise serving.max_num_blocks")
            logger.warning(f"request {req.rid} retired: {req.error}")
            self.finished.append(req)
            if self.events is not None:
                self.events.emit("req.retire", rid=req.rid,
                                 generated=len(req.generated),
                                 error=req.error)
            if self.telemetry is not None:
                self.telemetry.finished.inc()
            self._tel_gauges()
            return self._try_admit()

        entries: List[Tuple] = []       # chain order: ("dev", b) | ("host",
        #                                 key, k_np, v_np) — host bytes in hand
        keys: List[bytes] = []
        cow_src: Optional[int] = None
        cow_fetch = None                # (k_np, v_np): host-resident COW src
        cached = 0
        had_hit = False
        if self.prefix_caching:
            hits, hit_keys = self.allocator.match_prefix_tiered(prefix)
            # resolve host entries NOW — bytes in hand before any
            # allocation below can demote-evict them from the host LRU. A
            # vanished/faulted entry truncates the usable chain at its
            # position (the hit must stay a contiguous prefix).
            resolved: List[Tuple] = []
            for ent, key in zip(hits, hit_keys):
                if ent[0] == "dev":
                    resolved.append((ent, key))
                    continue
                data = self.allocator.host_pool.get(ent[1])
                if data is None:
                    break
                resolved.append((("host", ent[1], data[0], data[1]), key))
            had_hit = bool(resolved)
            if self.telemetry is not None:
                self.telemetry.prefix_cache_lookups.inc()
                if resolved:
                    self.telemetry.prefix_cache_hits.inc()
            cached = len(resolved) * bs
            if cached >= target and self.gen is None:
                # full prefix cached: cap the hit at target-1 (the last
                # token's logits must still be computed to sample the
                # continuation), which restarts mid-block inside the last
                # shared block — copy-on-write it (partial blocks are
                # never shared). A host-resident COW source fetches into
                # the private block directly (no device registration to
                # split; the host entry stays cached for future hits).
                cached = target - 1
                last, _ = resolved[-1]
                resolved = resolved[:-1]
                if last[0] == "dev":
                    cow_src = last[1]
                else:
                    cow_fetch = (last[2], last[3])
            entries = [e for e, _ in resolved]
            keys = [k for _, k in resolved]

        shared = [e[1] for e in entries if e[0] == "dev"]
        # host-hit blocks need fresh device placements, so they come out
        # of the same allocation as the uncached tail
        alloc_needed = need_total - len(shared)
        # acquire the hit FIRST so the tail allocation's cold-list reclaim
        # can't cannibalize the very blocks we are about to share. The COW
        # source is NOT acquired: the only allocation between here and the
        # engine's copy is the COW destination itself, and if LRU reclaim
        # hands back the source as that destination the copy degenerates to
        # the identity (content still intact — nothing writes between
        # admission and the engine processing the returned action).
        self.allocator.acquire(shared)
        # with host hits in the chain, the single-allocation guarantee
        # behind the un-acquired COW source no longer holds: the
        # allocation below also covers fetch destinations, and LRU
        # reclaim could hand the (cold) source out as one of them — the
        # H2D scatter would then overwrite it BEFORE the COW copy reads
        # it. Pin the source with a temporary reference for the
        # allocation (released right after placement); without host hits
        # the degenerate src==dst identity-copy case stays exactly as
        # before.
        protect_cow = cow_src is not None \
            and any(e[0] == "host" for e in entries)
        if protect_cow:
            self.allocator.acquire([cow_src])
        got = self.allocator.allocate(alloc_needed)
        if got is None and protect_cow:
            # the pool can't place the fetches AND preserve the pinned COW
            # source: degrade the full-prefix hit — drop the COW (the last
            # block's tokens recompute in the tail chunk; alloc_needed
            # already covers that block as plain tail) and retry unpinned
            self.allocator.free([cow_src])
            cow_src = None
            protect_cow = False
            cached = bs * len(entries)
            got = self.allocator.allocate(alloc_needed)
        if got is None:
            # roll the probe back — in REVERSE like _free_blocks, so LRU
            # reclaim takes chain tails before parents (a reclaimed parent
            # orphans its still-cached children for every future probe).
            # Host entries were only read (get), never removed: nothing to
            # restore there.
            self.allocator.free(list(reversed(shared)))
            if not self.running:
                raise PoolExhausted(
                    f"prefix of request {req.rid} needs {alloc_needed} more "
                    f"KV blocks but the pool only has "
                    f"{self.allocator.num_free} available and nothing is "
                    "running to evict; raise serving.max_num_blocks or "
                    "shrink the prompt", req)
            return None

        # interleave: chain positions keep their tier order — device hits
        # keep their blocks, host hits take fresh placements that the
        # engine fills H2D (fetch_pending) before this request's first
        # prefill work; the remainder is the uncached tail
        it = iter(got)
        blocks: List[int] = []
        fetches: List[Tuple] = []
        for e in entries:
            if e[0] == "dev":
                blocks.append(e[1])
            else:
                dst = next(it)
                blocks.append(dst)
                # key and token count ride along: the engine registers dst
                # under the key — and observes the fetch counters — only
                # once the copy actually lands (a preemption between
                # admission and fetch must not advertise unwritten content
                # nor count an H2D that never happened)
                fetches.append((dst, e[1], e[2], e[3], bs))
        tail = list(it)
        blocks += tail
        if protect_cow:
            self.allocator.free([cow_src])   # placement done: back cold
        if cow_fetch is not None:
            # the COW split's private copy: fetched, never registered
            fetches.append((tail[0], None, cow_fetch[0], cow_fetch[1],
                            cached - bs * len(entries)))

        del self.waiting[idx]
        first_admit = req.admit_seq == -1
        if self.telemetry is not None and first_admit:
            # first admission only: the submit->admit wait (a preemption
            # re-admission is recompute latency, not queueing delay)
            now = time.perf_counter()
            self.telemetry.queue_wait.observe(
                (now - req.t_submit) * 1e3,
                exemplar={"rid": str(req.rid)})
            # phase ledger: intake = submit->enqueue (front-end hand-off),
            # queue = enqueue->admit (admission wait proper)
            self.telemetry.phase(
                "intake", max(req.t_arrival - req.t_submit, 0.0) * 1e3,
                rid=req.rid)
            self.telemetry.phase(
                "queue", max(now - req.t_arrival, 0.0) * 1e3, rid=req.rid)
        req.blocks = blocks
        self.allocator.grow_window(req.window_blocks, len(blocks))
        # max_running + 1 slots and at most max_running rows: one is free
        req.state_slot = self.allocator.allocate_slot()
        if req.state_slot is None:
            raise RuntimeError("no state slot left for an admitted request")
        req.keys = list(keys)
        req.pos = cached
        req.prefill_target = target
        req.prefilling = True
        req.cow_pending = None if cow_src is None \
            else (cow_src, tail[0])
        req.fetch_pending = fetches
        req.state = RUNNING
        req.admit_seq = self._admit_counter
        self._admit_counter += 1
        self.running.append(req)
        if self.events is not None:
            # probe outcome emitted only on the admission that sticks: a
            # block-short pool retries admission every engine step, and
            # per-attempt instants would flood the bounded ring
            if self.prefix_caching:
                if had_hit:
                    self.events.emit("req.cache_hit", rid=req.rid,
                                     tokens=cached,
                                     host_blocks=len(fetches))
                else:
                    self.events.emit("req.cache_miss", rid=req.rid)
            self.events.emit("req.admit", rid=req.rid,
                             cached_tokens=cached, blocks=len(req.blocks),
                             prefill_target=target)
            if first_admit:
                # phase-ledger spans for the pre-admission phases (the
                # compute phases carry their own timed events); durations
                # are already-elapsed intervals ending here
                now_ns = time.monotonic_ns()
                self.events.emit(
                    "req.phase", rid=req.rid, t_ns=now_ns, phase="intake",
                    dur_ns=int(max(req.t_arrival - req.t_submit, 0.0) * 1e9))
                self.events.emit(
                    "req.phase", rid=req.rid, t_ns=now_ns, phase="queue",
                    dur_ns=int(max(time.perf_counter() - req.t_arrival, 0.0)
                               * 1e9))
        # generation by blocks samples nothing at a prefill, so a prefix
        # that is cached whole (or shorter than a generation block) has
        # none to run: straight into its first open block
        no_prefill = self.gen is not None and cached >= target and not fetches
        if self.telemetry is not None:
            if not no_prefill:
                self.telemetry.prefill_steps.inc()
            if cached:
                self.telemetry.prefix_cache_hit_tokens.inc(cached)
        if no_prefill:
            req.prefilling = False
            self._open_block(req)
        self._tel_gauges()
        if no_prefill:
            return self._try_admit()
        if req.pos > 0 or self.chunk_tokens > 0:
            if self.telemetry is not None:
                self.telemetry.prefill_chunks.inc()
            self._decode_turn = True
            return ("prefill_chunk", req)
        return ("prefill", req)

    # ------------------------------------------------------------------ #

    def next_action(self) -> Optional[Tuple[str, object]]:
        """Pick the next engine step: admit+start the policy-selected
        waiting request when a slot and its tail blocks are available
        (admission has priority — back-fill freed slots immediately), else
        alternate one prefill chunk of the oldest mid-prefill request with
        one fused decode step over the prefill-complete running set. None
        when everything is finished. Every returned action advances the
        logical ``step_seq`` clock (the SLA policies' time base).

        Deadline-carrying requests are swept first: an expired request —
        ``deadline_steps`` on the logical clock, ``deadline_ms`` on wall
        time — retires as ``timeout`` before the next step is chosen.
        ``("wait", None)`` is returned (and the clock ticked) when the
        only waiting requests are holding down in step-fault retry
        backoff — the tick is what moves them toward eligibility."""
        if self._deadline_live:
            self._sweep_deadlines()
        action = self._next_action()
        if action is not None:
            self.step_seq += 1
        return action

    def _expired(self) -> List[Tuple[Request, str]]:
        """The waiting and running requests whose deadline has passed, each
        with what to say of it."""
        now = None
        out = []
        for req in list(self.waiting) + list(self.running):
            expired = None
            if req.deadline_steps is not None and \
                    self.step_seq - req.arrival_step >= req.deadline_steps:
                expired = (f"deadline of {req.deadline_steps} scheduler "
                           f"steps exceeded")
            elif req.deadline_ms is not None:
                if now is None:
                    now = time.perf_counter()
                waited_ms = (now - req.t_submit) * 1e3
                if waited_ms > req.deadline_ms:
                    expired = (f"deadline of {req.deadline_ms:.0f} ms "
                               f"exceeded ({waited_ms:.0f} ms since "
                               "submission)")
            if expired is not None:
                out.append((req, expired))
        return out

    def _sweep_deadlines(self) -> None:
        for req, expired in self._expired():
            self.timeout_request(req, expired)

    def plans_ahead(self, rows: List[Request]) -> bool:
        """Whether :meth:`next_action` can choose while a launched step over
        ``rows`` still holds its tokens on the device, i.e. whether the
        choice reads none of them and undoes none of the rows: not when the
        n-gram proposer would read the tokens, when a deadline sweep would
        retire one of ``rows``, or when growing the decode rows' blocks
        would have to preempt (a victim re-queues prompt + generated).
        Reads only (the rows' ride marks are derived, and made anew by
        :meth:`next_action`); conservative about the last (an admission
        that takes the turn grows nothing)."""
        if self.spec_k > 0 or (self.gen is not None
                               and self.gen.data_dependent):
            return False
        if self._deadline_live and any(
                any(req is r for r in rows) for req, _ in self._expired()):
            return False
        bs = self.allocator.block_size
        ride = self._mark_rides()
        grow = sum(1 for r in self.running if not r.prefilling
                   and r.pos + (ride if r.blk_ride else 0)
                   >= len(r.blocks) * bs)
        return grow <= self.allocator.num_free

    def abandon(self, rows: List[Request]) -> None:
        """The tokens of a launched step over ``rows`` were lost (its fetch
        raised): every row it advanced goes back to the queue, those retired
        by count included, to be recomputed from prompt + generated like a
        preempted request. Earliest-admitted ends at the queue's head."""
        for req in reversed(rows):
            if req.state != RUNNING:
                continue
            if any(req is r for r in self.retiring):
                self.retiring.remove(req)
                self.running.append(req)      # its blocks went at the launch
            self._demote_to_queue(req)
        self._tel_gauges()

    def _next_action(self) -> Optional[Tuple[str, object]]:
        action = self._try_admit()
        if action is not None:
            return action
        prefilling = [r for r in self.running if r.prefilling]
        decodable = [r for r in self.running if not r.prefilling]
        if prefilling and (not decodable or not self._decode_turn):
            if self.telemetry is not None:
                self.telemetry.prefill_chunks.inc()
            self._decode_turn = True
            return ("prefill_chunk", prefilling[0])
        if decodable:
            self._decode_turn = False
            self._ensure_decode_capacity()
            decodable = [r for r in self.running if not r.prefilling]
            if not decodable:
                # capacity growth evicted every decodable row (they went
                # back to the queue); pick again from the new state (the
                # outer next_action ticks step_seq once for whatever comes
                # out)
                return self._next_action()
            if self.spec_k > 0:
                action = self._prepare_verify(decodable)
                if action is not None:
                    self._tel_gauges()   # window growth moved blocks
                    return action
            self.stats["decode_steps"] += 1
            if self.telemetry is not None:
                self.telemetry.decode_steps.inc()
            self._tel_gauges()       # capacity growth/evictions moved blocks
            if self.gen is not None:
                return ("block", decodable)
            return ("decode", decodable)
        if self.waiting:
            if all(r.retry_at_step > self.step_seq for r in self.waiting):
                # everything queued is holding down in retry backoff: a
                # no-op action whose clock tick moves them toward
                # eligibility (bounded — backoff is finite logical steps)
                return ("wait", None)
            # slots full but pool dry would have been handled above; here
            # the running set is empty yet requests wait — impossible unless
            # max_running slots are all mid-preemption; defensive guard
            raise RuntimeError("scheduler stuck: waiting requests but "
                               "nothing runnable")
        return None

    def _mark_rides(self) -> int:
        """Generation by blocks: which rows' next block step is a ride (the
        whole block a rider entry, the row's pass the next block's first),
        ``blk_ride``: the first ``ride_slots`` rows in admission order whose
        block is whole. A row still running has a token left to generate
        past a whole block (one that ends with it was handed on). Returns
        how much deeper a ride writes than ``pos`` (0: a token a step)."""
        if self.gen is None:
            return 0
        left, whole = self.ride_slots, self.gen.block
        for r in self.running:
            r.blk_ride = left > 0 and not r.prefilling \
                and r.blk_decided == whole
            left -= r.blk_ride
        return whole

    def _ensure_decode_capacity(self) -> None:
        """Every decode-ready request writes its next token at slot
        ``pos`` (a row whose commit rides: its next block, from ``pos +
        Bg``); grow its block list when that slot crosses a block
        boundary, evicting the policy's victim (FIFO: latest admitted,
        SLA: most TTFT slack) when the pool — free list AND reclaimable
        cold blocks — is dry."""
        ride = self._mark_rides()
        for req in list(self.running):
            if req.state != RUNNING or req.prefilling:
                continue  # evicted by an earlier iteration, or mid-prefill
            while req.pos + (ride if req.blk_ride else 0) \
                    >= len(req.blocks) * self.allocator.block_size:
                got = self.allocator.allocate(1)
                if got is not None:
                    req.blocks.extend(got)
                    self.allocator.grow_window(req.window_blocks,
                                               len(req.blocks))
                    break
                victim = self.policy.select_victim(self, req)
                # identity scan: Request's dataclass __eq__ compares numpy
                # fields (ambiguous truth value) — never use `in` here
                if not any(victim is r for r in self.running):
                    raise ValueError(
                        f"policy {self.policy.name!r} selected a victim "
                        "that is not running")
                if victim is req and len(self.running) == 1:
                    raise PoolExhausted(
                        f"request {req.rid} needs one more KV block but the "
                        "pool is exhausted and it is the only running "
                        "request; raise serving.max_num_blocks", req)
                self._preempt(victim)
                if victim is req:
                    break  # the requester evicted itself; it re-queued

    def _prepare_verify(self, decodable: List[Request]) \
            -> Optional[Tuple[str, object]]:
        """Propose n-gram candidates for every decode-ready request and
        secure the KV slots their verify windows write (slots ``pos`` ..
        ``pos + len(candidates)``; slot ``pos`` itself is already assured
        by ``_ensure_decode_capacity``). Window growth draws ONLY on the
        free pool and truncates the candidate list when it runs dry —
        speculation never preempts, so eviction behavior is identical to
        plain decode (growth therefore cannot drop rows from
        ``decodable``). Returns ``("verify", decodable)``, or None when no
        request found a match (the caller emits a plain decode step — the
        1-wide program is cheaper than an empty verify window)."""
        ev = self.events
        bs = self.allocator.block_size
        any_cands = False
        for r in decodable:
            # candidates may never push the request past max_new: a verify
            # step emits up to len(candidates)+1 tokens
            headroom = r.max_new - len(r.generated) - 1
            if headroom <= 0:
                r.spec_tokens = ()
                continue
            t0 = time.monotonic_ns() if ev is not None else 0
            cands = self.spec_proposer.propose(
                r.output, min(self.spec_k, headroom))
            found = len(cands)
            if len(cands):
                # clamp to the slots the request owns plus what the PLAIN
                # free list supplies — never evicting AND never reclaiming
                # a cold cached block: speculation is best-effort, so it
                # must not destroy a prefix-cache registration (and the
                # later cache miss + recompute) that spec-off serving
                # would have kept. Highest written slot is pos + len(cands)
                need = self.allocator.blocks_for_tokens(
                    r.pos + 1 + len(cands)) - len(r.blocks)
                if need > 0:
                    got = self.allocator.allocate(
                        min(need, self.allocator.num_free_list))
                    if got:
                        r.blocks.extend(got)
                    cands = cands[:len(r.blocks) * bs - 1 - r.pos]
            r.spec_tokens = tuple(int(c) for c in cands)
            # emitted only when the proposer actually matched: a zero-found
            # probe per request per decode turn would flood the bounded
            # ring and evict the lifecycle tail a post-mortem needs (the
            # same failure mode the per-attempt cache_hit instants had)
            if ev is not None and found:
                ev.emit("req.spec_propose", rid=r.rid, t_ns=t0,
                        dur_ns=time.monotonic_ns() - t0,
                        tokens=len(r.spec_tokens), found=found)
            if r.spec_tokens:
                any_cands = True
                self.stats["spec_proposed"] += len(r.spec_tokens)
                if self.telemetry is not None:
                    self.telemetry.spec_proposed_tokens.inc(
                        len(r.spec_tokens))
        if not any_cands:
            return None
        self.stats["verify_steps"] += 1
        if self.telemetry is not None:
            self.telemetry.spec_verify_steps.inc()
        return ("verify", decodable)

    def _preempt(self, victim: Request) -> None:
        logger.warning(
            f"KV pool exhausted: preempting request {victim.rid} "
            f"({len(victim.blocks)} blocks dereferenced; will recompute "
            f"{len(victim.prefix())} tokens on re-admission"
            + (" minus any prefix-cache hit" if self.prefix_caching else "")
            + ")")
        if self.events is not None:
            self.events.emit("req.preempt", rid=victim.rid,
                             blocks=len(victim.blocks),
                             recompute_tokens=len(victim.prefix()))
        self.stats["preemptions"] += 1
        if self.telemetry is not None:
            self.telemetry.preemptions.inc()
            self.telemetry.recompute_tokens.inc(len(victim.prefix()))
            # wasted-work ledger: the evicted prefix is compute the pool
            # pressure threw away (re-prefilled on re-admission)
            self.telemetry.waste("recompute", len(victim.prefix()))
        # FRONT of the queue: the victim was admitted before anything still
        # waiting, so FIFO fairness re-admits it first
        self._demote_to_queue(victim)
        victim.preemptions += 1

    def _free_blocks(self, req: Request) -> None:
        """Dereference a retiring/preempted request's blocks. Freed in
        REVERSE order when caching so the LRU cold list reclaims chain
        TAILS before their parents — a reclaimed parent orphans its still-
        cached children (match walks front-to-back)."""
        blocks = req.blocks
        if self.prefix_caching:
            blocks = list(reversed(blocks))
        self.allocator.free(blocks)
        self.allocator.free_window(req.window_blocks)
        self.allocator.free_slot(req.state_slot)
        req.state_slot = 0
        req.blocks = []
        req.keys = []
        req.cow_pending = None
        # un-landed host fetches die with the placement: the host pool
        # still holds the entries (removed only when a fetch lands), so a
        # re-admission re-hits them
        req.fetch_pending = []

    def _register_full_blocks(self, req: Request) -> None:
        """Publish every newly-FILLED block (all ``pos`` tokens' k/v are in
        the pools) into the content-addressed cache, extending the
        request's hash chain. First-writer-wins on conflicts (a concurrent
        identical prompt): the chain keys still advance so later blocks
        stay addressable."""
        if not self.prefix_caching:
            return
        bs = self.allocator.block_size
        full = req.pos // bs
        if full <= len(req.keys):
            return
        seq = req.prefix()
        parent = req.keys[-1] if req.keys else ROOT_KEY
        for j in range(len(req.keys), full):
            key = self.allocator.chain_key(parent, seq[j * bs:(j + 1) * bs])
            self.allocator.register(req.blocks[j], key)
            req.keys.append(key)
            parent = key

    # ------------------------------------------------------------------ #
    # engine callbacks after each compute step

    def record_prefill(self, req: Request, token: int) -> None:
        """The engine prefilled ``req.prefix()`` whole and sampled
        ``token`` from the last position."""
        self.advance_prefill(req)
        self.commit_token(req, token)

    def record_prefill_chunk(self, req: Request, n_tokens: int,
                             token: Optional[int] = None) -> None:
        """One prefill chunk of ``n_tokens`` is cached. On the FINAL chunk
        the engine passes the ``token`` it sampled from the prefix's last
        position, completing the prefill exactly like
        :meth:`record_prefill`."""
        self.advance_prefill_chunk(req, n_tokens, last=token is not None)
        if token is not None:
            self.commit_token(req, token)

    def record_decode(self, req: Request, token: int) -> None:
        """One decode step: the previous ``last_token``'s k/v was written at
        slot ``pos`` and ``token`` sampled from the resulting logits."""
        self.advance_decode(req)
        self.commit_token(req, token, fused=True)

    # the two halves of the records above, for a loop that launches a step
    # before it holds the tokens of the one before (see the module docstring)

    def advance_prefill(self, req: Request) -> None:
        """A whole prefill of ``req.prefix()`` was launched."""
        req.pos = req.prefill_target
        req.prefilling = False
        self._register_full_blocks(req)
        self._prefilled(req)

    def advance_prefill_chunk(self, req: Request, n_tokens: int,
                              last: bool) -> None:
        """A prefill chunk of ``n_tokens`` was launched; ``last`` when it
        samples the request's next token."""
        req.pos += int(n_tokens)
        if req.pos > req.prefill_target:
            raise ValueError(
                f"prefill chunk overran request {req.rid}: pos {req.pos} > "
                f"target {req.prefill_target}")
        self._register_full_blocks(req)
        if not last:
            return
        if req.pos != req.prefill_target:
            raise ValueError(
                f"request {req.rid} sampled a token at pos {req.pos} before "
                f"reaching its prefill target {req.prefill_target}")
        req.prefilling = False
        self._prefilled(req)

    def advance_decode(self, req: Request) -> None:
        """A decode step over ``req`` was launched: ``last_token``'s k/v goes
        to slot ``pos``."""
        req.pos += 1
        self._register_full_blocks(req)
        self._retire_by_count(req)

    def _prefilled(self, req: Request) -> None:
        """The last piece of ``req``'s prefill was launched: it samples the
        request's next token, or (generation by blocks) none, and the
        request enters its first open block."""
        if self.gen is None:
            self._retire_by_count(req)
        else:
            self._open_block(req)

    # ---- generation by blocks ---- #

    def block_state(self, req: Request) -> np.ndarray:
        """``req``'s open block as the host knows it, [Bg] int32 (-1:
        undecided): the prefix's tokens that lie inside it, and what the
        newest landed pass over this same block had decided beside them."""
        state = open_block(self.gen, req.prefix(), req.blk_start)
        if req.blk_landed is not None and req.blk_landed[0] == req.blk_start:
            state = np.where(state >= 0, state, req.blk_landed[1])
        return state

    def _open_block(self, req: Request) -> None:
        """``req`` enters the block at ``pos``: fresh, or (a re-admission
        whose recompute ends where its open block began) as it was."""
        req.blk_start = req.pos
        again = req.blk_landed is not None and req.blk_landed[0] == req.pos
        req.blk_pass = req.blk_landed[2] if again else 0
        req.blk_decided = int((self.block_state(req) >= 0).sum())

    def plan_block(self, req: Request) -> BlockStep:
        """What the next block step is for ``req``: a denoise pass, a lone
        commit, or a ride (:class:`BlockStep`)."""
        g = self.gen
        left = g.block - req.blk_decided
        if left:
            return BlockStep(False, min(g.transfers(req.blk_pass), left),
                             req.blk_pass)
        if req.blk_ride:
            return BlockStep(True, g.transfers(0), 0, ride=True)
        return BlockStep(True, 0, req.blk_pass)

    def advance_block(self, req: Request, commit: bool, n: int, i: int,
                      ride: bool = False) -> None:
        """A block step over ``req`` was launched, for it a commit (the
        block's final k/v is in the pools: ``pos`` moves on and the next
        block opens, all undecided), denoise pass ``i`` deciding ``n``
        positions (the data-dependent rule: at least ``n``; the count comes
        with the landing), or (``ride``) both in that order, the pass over
        the block the commit opened."""
        g = self.gen
        if commit:
            req.pos += g.block
            req.blk_start, req.blk_decided, req.blk_pass = req.pos, 0, 0
            req.blk_ride = False
            self._register_full_blocks(req)
            if not ride:
                return
        req.blk_pass = i + 1
        if g.data_dependent:
            return
        req.blk_decided += n
        done = req.blk_start + req.blk_decided - req.prompt.size
        # the pass decides the request's max_new-th token: under the
        # sequential rule by count; under a confidence order only once
        # the block is whole (which positions a pass takes is data)
        if done >= req.max_new and (g.rule == "sequential"
                                    or req.blk_decided == g.block):
            self._hand_on(req)

    def record_block(self, req: Request, commit: bool, n: int, i: int,
                     ride: bool, state) -> List[int]:
        """``req``'s launched block step landed with ``state`` [Bg] (-1:
        undecided), its open block as the pass left it (a lone commit: the
        next block, all undecided; a ride: the next block after its first
        pass). Streams what is newly decided with every token before it:
        returns those tokens."""
        if commit and not ride:
            return []
        state = np.asarray(state, np.int32)
        start = req.blk_start
        req.blk_landed = (start, state, i + 1)
        if self.gen.data_dependent:
            now = int((state >= 0).sum())
            n, req.blk_decided = now - req.blk_decided, now
        if self.telemetry is not None:
            self.telemetry.count_block_decided(n)
        out: List[int] = []
        at = req.prompt.size + len(req.generated) - start
        while at < state.size and state[at] >= 0 \
                and len(req.generated) < req.max_new:
            tok = int(state[at])
            req.generated.append(tok)
            out.append(tok)
            self.stats["emitted_tokens"] += 1
            self._record_token_time(req)
            at += 1
            if req.eos is not None and tok == req.eos:
                break
        if out:
            self._maybe_finish(req)
        return out

    def _retire_by_count(self, req: Request) -> None:
        """The step just launched samples ``req``'s ``max_new``-th token:
        whatever that token is, the request needs no further step, so its
        row and its blocks are handed on now (later programs run after this
        one on the device) and :meth:`commit_token` finishes it."""
        if len(req.generated) + 1 >= req.max_new:
            self._hand_on(req)

    def _hand_on(self, req: Request) -> None:
        """``req`` needs no step after the one just launched: its row and
        blocks go now, and it waits in ``retiring`` for that step to land."""
        self.running.remove(req)
        self._free_blocks(req)
        self.retiring.append(req)

    def commit_token(self, req: Request, token: int,
                     fused: bool = False) -> None:
        """The token of ``req``'s launched step landed."""
        req.generated.append(int(token))
        if fused:
            self.stats["emitted_tokens"] += 1
        self._record_token_time(req)
        self._maybe_finish(req)

    def record_verify(self, req: Request, tokens: List[int]) -> None:
        """One fused verify step for ``req``: the engine scattered k/v for
        the whole window — the pending ``last_token`` plus every candidate
        in ``req.spec_tokens`` at slots ``pos .. pos + m`` — and greedy
        acceptance emitted ``tokens``: the accepted candidate prefix plus
        the first-mismatch (or, on full acceptance, bonus) token.

        Bookkeeping is optimistic-then-rollback, mirroring what the device
        actually did: ``pos`` first advances over every scattered input and
        blocks register into the prefix cache as they fill (their hash
        chains include the candidate tokens — that IS their content right
        now). A rejection then rewinds: the uncommitted candidates leave
        ``generated``, ``pos`` rewinds past them (their k/v stays beyond
        ``pos`` — never read, overwritten as decode advances), and every
        block whose fill boundary sits inside the rejected span is
        unregistered via ``unregister_if_owner`` — its slots WILL be
        overwritten by the real continuation, so a surviving registration
        would advertise content about to be destroyed. When a first writer
        (another request whose identical tokens DID commit) already owned
        the hash, its mapping is preserved untouched."""
        cands = req.spec_tokens
        m = len(cands)
        req.spec_tokens = ()
        tokens = [int(t) for t in tokens]
        if not 1 <= len(tokens) <= m + 1:
            raise ValueError(
                f"verify of request {req.rid} emitted {len(tokens)} tokens "
                f"from a window of {m} candidates")
        # eos can land anywhere in the multi-token window: cut exactly
        # where token-by-token greedy decode would have stopped
        if req.eos is not None and req.eos in tokens:
            tokens = tokens[:tokens.index(req.eos) + 1]
        a = len(tokens) - 1            # candidates that commit

        # ---- optimistic advance over the whole scattered window ----
        req.generated.extend(int(c) for c in cands)
        req.pos += m + 1
        self._register_full_blocks(req)

        # ---- rollback of the rejected tail ----
        drop = m - a
        if drop:
            req.pos -= drop
            del req.generated[-drop:]
            bs = self.allocator.block_size
            unregistered = 0
            while len(req.keys) > req.pos // bs:
                key = req.keys.pop()
                if self.allocator.unregister_if_owner(
                        req.blocks[len(req.keys)], key):
                    unregistered += 1
            # return the window's surplus whole blocks: a rejected
            # speculation holding pool capacity would preempt requests
            # plain decode would have kept (only blocks past the rewound
            # pos's own slot can be surplus — all unregistered, the pop
            # loop above already withdrew any boundary-crossing keys)
            keep = max(self.allocator.blocks_for_tokens(req.pos + 1),
                       len(req.keys))
            if len(req.blocks) > keep:
                tail = req.blocks[keep:]
                del req.blocks[keep:]
                self.allocator.free(list(reversed(tail)))
            self.stats["spec_rollbacks"] += 1
            if self.telemetry is not None:
                self.telemetry.spec_rollbacks.inc()
                # rejected candidates were scattered and verified on the
                # device, then thrown away: speculative wasted work
                self.telemetry.waste("spec_reject", drop)
            if self.events is not None:
                self.events.emit("req.spec_rollback", rid=req.rid,
                                 rejected=drop, unregistered=unregistered)

        # ---- commit: accepted candidates are already in ``generated``;
        # the mismatch/bonus token is the next step's pending input ----
        req.generated.append(tokens[-1])
        self.stats["spec_accepted"] += a
        self.stats["emitted_tokens"] += len(tokens)
        if self.telemetry is not None:
            t = self.telemetry
            t.spec_accepted_tokens.inc(a)
            # the rate gauge derives from the CUMULATIVE registry counters
            # (they outlive this scheduler — one per serve call), so it
            # always equals accepted/proposed as the snapshot reports them
            proposed = t.spec_proposed_tokens.value
            if proposed:
                t.spec_acceptance_rate.set(
                    t.spec_accepted_tokens.value / proposed)
        for _ in tokens:
            self._record_token_time(req)
        self._maybe_finish(req)

    def _record_token_time(self, req: Request) -> None:
        """TTFT once per request (first token after the ORIGINAL arrival —
        a post-preemption re-prefill token counts as a per-output-token
        latency, not a second TTFT), TPOT for every token after it."""
        # an emitted token is real progress: step-fault retries reset, so
        # an innocent request co-batched with a poison one (whose fused
        # steps keep faulting) never accrues its way into quarantine —
        # only a request that cannot progress past its faulting action
        # exhausts serving.fault.max_request_retries
        req.retry_count = 0
        now = time.perf_counter()
        t = self.telemetry
        if t is not None:
            # the exemplar links the histogram's newest observation back
            # to its flight-recorder request track: a scraped p99 spike
            # carries the rid whose trace explains it
            if req.t_first_token is None:
                t.ttft.observe((now - req.t_arrival) * 1e3,
                               exemplar={"rid": str(req.rid)})
            else:
                t.tpot.observe((now - req.t_last_token) * 1e3,
                               exemplar={"rid": str(req.rid)})
            t.generated_tokens.inc()
        if req.t_first_token is None:
            req.t_first_token = now
        req.t_last_token = now

    def _maybe_finish(self, req: Request) -> None:
        done = len(req.generated) >= req.max_new
        if req.eos is not None and req.generated[-1] == req.eos:
            done = True
        if done:
            req.state = FINISHED
            self._deadline_retired(req)
            if any(req is r for r in self.retiring):
                self.retiring.remove(req)   # row and blocks went at launch
            else:
                self.running.remove(req)
                self._free_blocks(req)
            self.finished.append(req)
            if self.events is not None:
                self.events.emit("req.retire", rid=req.rid,
                                 generated=len(req.generated),
                                 preemptions=req.preemptions)
            if self.telemetry is not None:
                self.telemetry.finished.inc()
            self._tel_gauges()
