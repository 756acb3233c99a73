"""Compile-budget contracts for the framework's jitted entry points.

The static rules keep trace hazards out of the code; this registry pins
the *dynamic* compile behavior the code is supposed to have. Each entry
declares, for a named scenario, the maximum number of XLA compilations a
watched jit entry point (the names `CompileWatchdog` records in
``by_fn``) may perform. A tier-1 test drives the real engines through the
scenario and feeds ``telemetry_snapshot()["compile"]["by_fn"]`` to
:func:`check_compile_budgets` — so a shape-stability regression (the
sustained-recompile class PR-3's watchdog could only flag at runtime,
on-device) fails review instead of surfacing as a compile storm.

Budget semantics: ``max_compiles`` bounds the compiles a scenario may
trigger for that entry; entries the scenario never touches are simply
absent from ``by_fn`` (0 compiles always passes). ``by_fn`` names that
have NO budget for the scenario are reported too when ``strict`` — a new
jit entry point must declare its budget before it ships.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional


@dataclass(frozen=True)
class CompileBudget:
    entry: str          # CompileWatchdog name, e.g. "engine.train_batch[gas=1]"
    scenario: str       # scenario key the budget applies to
    max_compiles: int
    note: str           # why this bound holds (shape-stability argument)


#: The registry. Scenarios:
#:   steady_train    — N identical train_batch steps after warmup
#:   serving_steady  — one generate_batch over mixed-length prompts with
#:                     default serving config (prompt lengths within one
#:                     128-token prefill bucket)
#:   serving_chunked — generate_batch with chunked prefill + prefix cache
#:   serving_speculative — generate_batch with serving.speculative
#:                     {mode: ngram} at one fixed k (repetitive prompts,
#:                     verify + fallback decode steps interleaved)
#:   serving_async_steady — the ALWAYS-ON serving loop (AsyncServingEngine)
#:                     fed interleaved arrivals — requests submitted while
#:                     others are mid-decode, mixed priorities, a
#:                     cancellation — with prefix cache + speculation on,
#:                     prompts within two 128-token buckets: THE OPEN LOOP
#:                     MUST REUSE THE CLOSED LOOP'S PROGRAMS — both run
#:                     scheduler actions through the same _ServeSession
#:                     executor, so a generate_batch warm-up followed by
#:                     any amount of open-loop traffic compiles each fused
#:                     entry exactly as often as generate_batch alone
#:   serving_tiered_steady — generate_batch with the tiered KV cache on
#:                     (serving.kv_host.enabled, spill FORCED by a device
#:                     pool small enough that demotion and fetch actually
#:                     fire), prefix cache + speculation on, prompts within
#:                     two 128-token buckets: TIERING MUST NOT MULTIPLY
#:                     PROGRAMS — the fused steps compile exactly as often
#:                     as without the tier, and the spill/fetch copy
#:                     programs are block-index-traced (one program each no
#:                     matter which block moves; budget 2 for the donation/
#:                     layout variants a re-entered workspace can add)
#:   serving_metrics_steady — the telemetry exposition plane beside a warm
#:                     serving loop: a closed-loop warm-up, then open-loop
#:                     traffic with the background MetricsSampler ticking
#:                     (snapshots + SLO burn-rate evaluation) and the
#:                     /metrics exporter being scraped throughout. THE
#:                     SAMPLER/EXPORTER THREADS DO ZERO DEVICE WORK AND
#:                     ADD ZERO COMPILES — a scrape or a snapshot is
#:                     host-side dict work only (dslint DS009 pins the
#:                     no-jax-import half statically; this contract pins
#:                     the dynamic half), so each fused entry compiles
#:                     exactly as often as the unsampled async scenario
#:   serving_faulted_steady — the always-on loop surviving ONE injected
#:                     engine-fatal step fault (the donated pools die
#:                     mid-step): crash-safe recovery rebuilds the pool
#:                     workspace AND the fused-step jits, so each entry may
#:                     recompile AT MOST ONCE PER ENGINE RESTART on top of
#:                     its steady budget (rebuild != recompile storm — the
#:                     recovered loop's shapes are exactly the pre-fault
#:                     shapes, so the post-restart compile set is the warm
#:                     set, once)
#:   serving_sharded_steady — generate_batch under serving.tp > 1 (head-
#:                     sharded KV pools, shard_map'd paged kernel), prefix
#:                     cache + speculation on, prompts within two 128-token
#:                     buckets: SHARDING MUST NOT MULTIPLY PROGRAMS — each
#:                     fused entry compiles exactly as often as its tp=1
#:                     counterpart (the shard_map and the sharding
#:                     constraints are part of the traced program, not a
#:                     per-shard re-trace)
#:   serving_replicated_steady — TWO serving replicas behind the
#:                     deterministic ReplicaRouter (the dp serving axis,
#:                     inference/router.py) with the tiered KV host pool
#:                     shared between them, each engine warmed by one
#:                     closed-loop call, then routed open-loop traffic:
#:                     ROUTING ADDS ZERO NEW COMPILES — the router is pure
#:                     host-side dispatch (hashing, queue-depth compares,
#:                     handle pumping), so the process-wide compile count
#:                     is exactly N x the per-engine serving_tiered_steady
#:                     set (each replica owns its jit wrappers; the
#:                     budgets below are the N=2 totals) and stays frozen
#:                     however much traffic the router spreads
#:   serving_traced_steady — the async serving loop with the FULL request
#:                     latency-anatomy plane on: flight recorder enabled,
#:                     trace context propagated, every phase observed
#:                     into serving/phase_ms (with exemplars) and the
#:                     wasted-token ledger, prefix cache + speculation
#:                     on, prompts within two 128-token buckets: TRACING
#:                     ADDS ZERO STEADY-STATE COMPILES — every emit /
#:                     histogram observe / trace-id stamp is host-side
#:                     dict work AFTER the step's existing sync point
#:                     (dslint DS005 pins the no-new-sync half
#:                     statically; this contract pins the dynamic half),
#:                     so each fused entry compiles exactly as often as
#:                     the untraced serving_async_steady scenario
#:   serving_adaptive_steady — the async serving loop with the adaptive
#:                     controller (monitor/controller.py) driven through a
#:                     FULL tighten-then-revert knob cycle: chunk shrinks,
#:                     spec k drops, admission tightens, then sustained
#:                     headroom steps everything back to the config
#:                     baseline. THE AUTOPILOT ADDS ZERO NEW STEADY-STATE
#:                     PROGRAMS — every knob ladder rung is constructed
#:                     inside an already-compiled bucket (chunk rungs are
#:                     128-multiples at or below the baseline bucket,
#:                     spec-k rungs stay inside the fixed verify window
#:                     with k=0 riding the plain decode program, admission
#:                     / shed / spill knobs are pure host-side scheduler
#:                     state), so each fused entry compiles exactly as
#:                     often as the controller-off serving_async_steady
#:                     scenario — a single extra compile means a knob
#:                     action escaped its compile bucket
BUDGETS: List[CompileBudget] = [
    CompileBudget(
        "engine.train_batch[gas=1]", "steady_train", 1,
        "fixed (B, S) batch: one fused step program, ever; a second "
        "compile means the step fn's input signature is unstable "
        "(python scalars, weak_type flap, donation mismatch)"),
    CompileBudget(
        "engine.accum_batch[gas=1]", "steady_train", 1,
        "accumulation variant of the fused step; same stability bound"),
    CompileBudget(
        "engine.forward", "steady_train", 1,
        "trio forward: one program per fixed micro-batch shape"),
    CompileBudget(
        "engine.backward", "steady_train", 1,
        "trio backward: one program per fixed micro-batch shape"),
    CompileBudget(
        "engine.step", "steady_train", 1,
        "trio apply-update: parameter shapes never change mid-run"),
    CompileBudget(
        "inference.paged_decode", "serving_steady", 1,
        "THE fused decode step: fixed-width over max_running slots, "
        "per-request positions are traced vectors — one program no "
        "matter how many requests/tokens flow through"),
    CompileBudget(
        "inference.paged_sample", "serving_steady", 2,
        "the sampler as one program at two widths (a prefill's one row "
        "of logits, the fused step's rows): see "
        "serving_async_steady"),
    CompileBudget(
        "inference.paged_prefill", "serving_steady", 2,
        "whole-prompt prefill compiles once per 128-token prompt-length "
        "bucket; the steady scenario stays within two buckets"),
    CompileBudget(
        "inference.paged_cow", "serving_steady", 1,
        "copy-on-write block copy: fixed block geometry"),
    CompileBudget(
        "inference.paged_decode", "serving_chunked", 1,
        "chunked prefill interleaves with the SAME fused decode program"),
    CompileBudget(
        "inference.paged_sample", "serving_chunked", 2,
        "the sampler as one program at two widths (a prefill's one row "
        "of logits, the fused step's rows): see "
        "serving_async_steady"),
    CompileBudget(
        "inference.paged_prefill_chunk", "serving_chunked", 4,
        "one program per (chunk bucket, table-width power-of-two) pair; "
        "the acceptance scenario touches at most four"),
    CompileBudget(
        "inference.paged_cow", "serving_chunked", 1,
        "copy-on-write block copy: fixed block geometry"),
    CompileBudget(
        "inference.paged_verify", "serving_speculative", 1,
        "THE fused verify step: fixed max_running rows x a window "
        "bucketed to the next power of two of k+1, per-request position "
        "WINDOWS are traced vectors — one program per k bucket (<= log2 "
        "programs over any k sweep), and the scenario holds k fixed"),
    CompileBudget(
        "inference.paged_decode", "serving_speculative", 1,
        "no-match fallback steps ride the SAME fused decode program "
        "speculation-off serving uses"),
    CompileBudget(
        "inference.paged_sample", "serving_speculative", 2,
        "the sampler as one program at two widths (a prefill's one row "
        "of logits, the fused step's rows): see "
        "serving_async_steady"),
    CompileBudget(
        "inference.paged_prefill", "serving_speculative", 2,
        "admission prefill is untouched by speculation: one compile per "
        "128-token prompt bucket, the scenario stays within two"),
    CompileBudget(
        "inference.paged_prefill_chunk", "serving_speculative", 4,
        "cache-hit tails/chunked prefill interleave unchanged: one "
        "program per (chunk bucket, table-width power-of-two) pair"),
    CompileBudget(
        "inference.paged_cow", "serving_speculative", 1,
        "copy-on-write block copy: fixed block geometry"),
    CompileBudget(
        "inference.paged_decode", "serving_async_steady", 1,
        "THE fused decode step is front-end-independent: the open loop "
        "executes through the same _ServeSession as generate_batch, the "
        "batch stays fixed-width over max_running slots, positions stay "
        "traced vectors — arrivals mid-flight must not retrace"),
    CompileBudget(
        "inference.paged_sample", "serving_async_steady", 2,
        "the sampler's whole dispatch (cast, draw, and the widening of a "
        "prefill's one token to the decode width, where the next decode "
        "step's feed operand gathers from) as ONE program at two widths: "
        "a prefill's one row of logits and the fused step's rows — the "
        "first prefill -> decode compiles both, and a step launched "
        "behind an unfetched decode step adds none (its feed is an "
        "operand of paged_decode, one form whatever step came before)"),
    CompileBudget(
        "inference.paged_verify", "serving_async_steady", 1,
        "fused verify under the open loop: one program per k window "
        "bucket (the scenario holds k fixed), same as closed-loop "
        "speculation"),
    CompileBudget(
        "inference.paged_prefill", "serving_async_steady", 2,
        "admission prefill of open-loop arrivals: one compile per "
        "128-token prompt bucket, the scenario stays within two"),
    CompileBudget(
        "inference.paged_prefill_chunk", "serving_async_steady", 4,
        "cache-hit tails / chunked prefill of open-loop arrivals: one "
        "program per (chunk bucket, table-width power-of-two) pair — "
        "chunk-bucketed exactly like the closed loop"),
    CompileBudget(
        "inference.paged_cow", "serving_async_steady", 1,
        "copy-on-write block copy: fixed block geometry"),
    CompileBudget(
        "inference.paged_decode", "serving_metrics_steady", 1,
        "THE fused decode step is observation-independent: sampler ticks "
        "and /metrics scrapes read host-side registry state under its "
        "lock — they never touch the jit cache, donate a buffer, or "
        "perturb an input signature"),
    CompileBudget(
        "inference.paged_sample", "serving_metrics_steady", 2,
        "the sampler as one program at two widths (a prefill's one row "
        "of logits, the fused step's rows): see "
        "serving_async_steady"),
    CompileBudget(
        "inference.paged_verify", "serving_metrics_steady", 1,
        "fused verify under scrape load: one program per k window "
        "bucket, same as the unobserved loop"),
    CompileBudget(
        "inference.paged_prefill", "serving_metrics_steady", 2,
        "admission prefill: one compile per 128-token prompt bucket, "
        "the scenario stays within two — scrapes add none"),
    CompileBudget(
        "inference.paged_prefill_chunk", "serving_metrics_steady", 4,
        "cache-hit tails / chunked prefill: one program per (chunk "
        "bucket, table-width power-of-two) pair, same as unobserved"),
    CompileBudget(
        "inference.paged_cow", "serving_metrics_steady", 1,
        "copy-on-write block copy: fixed block geometry"),
    CompileBudget(
        "inference.paged_decode", "serving_tiered_steady", 1,
        "THE fused decode step is tier-independent: demotion/fetch are "
        "separate copy programs, the decode signature never changes"),
    CompileBudget(
        "inference.paged_sample", "serving_tiered_steady", 2,
        "the sampler as one program at two widths (a prefill's one row "
        "of logits, the fused step's rows): see "
        "serving_async_steady"),
    CompileBudget(
        "inference.paged_verify", "serving_tiered_steady", 1,
        "THE fused verify step under tiering: one program per k window "
        "bucket (the scenario holds k fixed), same as untied serving"),
    CompileBudget(
        "inference.paged_prefill", "serving_tiered_steady", 2,
        "admission prefill: one compile per 128-token prompt bucket, the "
        "scenario stays within two"),
    CompileBudget(
        "inference.paged_prefill_chunk", "serving_tiered_steady", 4,
        "cache-hit tails (incl. host-hit tails) ride the chunk program: "
        "one per (chunk bucket, table-width power-of-two) pair"),
    CompileBudget(
        "inference.paged_cow", "serving_tiered_steady", 1,
        "copy-on-write block copy: fixed block geometry"),
    CompileBudget(
        "inference.paged_spill_gather", "serving_tiered_steady", 2,
        "per-block D2H gather: the block index is a traced scalar, so "
        "every demotion shares one program (2 covers a donation/layout "
        "variant when the pool workspace is re-entered)"),
    CompileBudget(
        "inference.paged_fetch_scatter", "serving_tiered_steady", 2,
        "per-block H2D scatter: traced block index + fixed slice shape "
        "— one program however many blocks re-materialize"),
    CompileBudget(
        "inference.paged_decode", "serving_faulted_steady", 2,
        "one steady program + at most one post-restart recompile: the "
        "scenario injects exactly one engine-fatal fault, and recovery "
        "rebuilds the jit wrappers once (same shapes, one compile)"),
    CompileBudget(
        "inference.paged_sample", "serving_faulted_steady", 4,
        "the sampler as one program at two widths (a prefill's one row "
        "of logits, the fused step's rows), per engine built: see "
        "serving_async_steady"),
    CompileBudget(
        "inference.paged_prefill", "serving_faulted_steady", 4,
        "two 128-token prompt buckets, each at most twice (steady + one "
        "post-restart recompile)"),
    CompileBudget(
        "inference.paged_prefill_chunk", "serving_faulted_steady", 8,
        "(chunk bucket, table-width power-of-two) pairs at most twice "
        "each across the one restart"),
    CompileBudget(
        "inference.paged_verify", "serving_faulted_steady", 2,
        "one k-window bucket, at most twice across the one restart"),
    CompileBudget(
        "inference.paged_cow", "serving_faulted_steady", 2,
        "fixed block geometry, at most twice across the one restart"),
    CompileBudget(
        "inference.paged_spill_gather", "serving_faulted_steady", 4,
        "block-index-traced copy program (2 donation/layout variants), "
        "at most twice across the one restart"),
    CompileBudget(
        "inference.paged_fetch_scatter", "serving_faulted_steady", 4,
        "block-index-traced copy program (2 donation/layout variants), "
        "at most twice across the one restart"),
    CompileBudget(
        "inference.paged_decode", "serving_sharded_steady", 1,
        "THE fused decode step under tp>1: the head split rides the "
        "traced shard_map, per-request positions stay traced vectors — "
        "one program, same as tp=1 (sharding must not multiply programs)"),
    CompileBudget(
        "inference.paged_sample", "serving_sharded_steady", 2,
        "the sampler as one program at two widths (a prefill's one row "
        "of logits, the fused step's rows): see "
        "serving_async_steady"),
    CompileBudget(
        "inference.paged_prefill", "serving_sharded_steady", 2,
        "whole-prompt prefill under tp>1: one compile per 128-token "
        "prompt bucket exactly as at tp=1; the scenario spans two"),
    CompileBudget(
        "inference.paged_prefill_chunk", "serving_sharded_steady", 4,
        "cache-hit tails / chunked prefill under tp>1: one program per "
        "(chunk bucket, table-width power-of-two) pair, same as tp=1"),
    CompileBudget(
        "inference.paged_verify", "serving_sharded_steady", 1,
        "THE fused verify step under tp>1: one program per k window "
        "bucket (the scenario holds k fixed), same as tp=1"),
    CompileBudget(
        "inference.paged_cow", "serving_sharded_steady", 1,
        "copy-on-write block copy: fixed block geometry, sharding rides "
        "the constrained pool layout"),
    CompileBudget(
        "inference.paged_decode", "serving_replicated_steady", 2,
        "one fused decode program PER REPLICA (N=2): each engine owns "
        "its jit wrappers; the router's host-side dispatch must add "
        "zero — a third compile means routed traffic retraced a step"),
    CompileBudget(
        "inference.paged_sample", "serving_replicated_steady", 4,
        "the sampler as one program at two widths (a prefill's one row "
        "of logits, the fused step's rows), per engine built: see "
        "serving_async_steady"),
    CompileBudget(
        "inference.paged_verify", "serving_replicated_steady", 2,
        "one k-window-bucket verify program per replica (N=2); routed "
        "speculation reuses each engine's own program"),
    CompileBudget(
        "inference.paged_prefill", "serving_replicated_steady", 4,
        "two 128-token prompt buckets x two replicas: routing (incl. "
        "prefill-role warm-ups) must hit existing buckets only"),
    CompileBudget(
        "inference.paged_prefill_chunk", "serving_replicated_steady", 8,
        "(chunk bucket, table-width power-of-two) pairs x two replicas; "
        "host-tier cache-hit tails ride the same chunk programs"),
    CompileBudget(
        "inference.paged_cow", "serving_replicated_steady", 2,
        "fixed block geometry, one program per replica (N=2)"),
    CompileBudget(
        "inference.paged_spill_gather", "serving_replicated_steady", 4,
        "block-index-traced D2H gather (2 donation/layout variants) per "
        "replica: the prefill->decode handoff's push half shares the "
        "tiered-KV spill program, shipping blocks compiles nothing new"),
    CompileBudget(
        "inference.paged_fetch_scatter", "serving_replicated_steady", 4,
        "block-index-traced H2D scatter (2 donation/layout variants) per "
        "replica: the handoff's decode-side fetch IS the PR-12 path — "
        "the host tier as KV transport adds zero programs"),
    CompileBudget(
        "inference.paged_decode", "serving_traced_steady", 1,
        "tracing is host-side emit/observe work after the step's "
        "existing sync: the fused decode program compiles exactly as "
        "often as untraced — a second compile means instrumentation "
        "leaked into the traced program"),
    CompileBudget(
        "inference.paged_sample", "serving_traced_steady", 2,
        "the sampler as one program at two widths (a prefill's one row "
        "of logits, the fused step's rows): see "
        "serving_async_steady"),
    CompileBudget(
        "inference.paged_verify", "serving_traced_steady", 1,
        "one k-window-bucket verify program, same as untraced: the "
        "verify phase observe reuses the step's existing host sync"),
    CompileBudget(
        "inference.paged_prefill", "serving_traced_steady", 2,
        "one program per 128-token prompt bucket (the scenario spans "
        "two), same as untraced: the prefill phase ledger rides the "
        "sample readback that already synced"),
    CompileBudget(
        "inference.paged_prefill_chunk", "serving_traced_steady", 4,
        "one program per (chunk bucket, table-width power-of-two) pair, "
        "same as untraced; phase observes add zero retraces"),
    CompileBudget(
        "inference.paged_cow", "serving_traced_steady", 1,
        "copy-on-write block copy: fixed block geometry; the cow phase "
        "observe happens after its block_until_ready"),
    CompileBudget(
        "inference.paged_decode", "serving_adaptive_steady", 1,
        "THE fused decode step is knob-independent: chunk/admission/shed/"
        "spill actions are host-side scheduler state, spec k=0 rides "
        "this same program — a second compile means a knob action "
        "perturbed the decode signature"),
    CompileBudget(
        "inference.paged_sample", "serving_adaptive_steady", 2,
        "the sampler as one program at two widths (a prefill's one row "
        "of logits, the fused step's rows): see "
        "serving_async_steady"),
    CompileBudget(
        "inference.paged_verify", "serving_adaptive_steady", 1,
        "the verify window is bucketed to the power of two of the "
        "CONFIG k at session open; every spec_k ladder rung stays "
        "inside that window, so tighten->revert reuses one program"),
    CompileBudget(
        "inference.paged_prefill", "serving_adaptive_steady", 2,
        "admission prefill: one program per 128-token prompt bucket "
        "(the scenario spans two); the admission knobs gate arrivals, "
        "they never reshape a prefill"),
    CompileBudget(
        "inference.paged_prefill_chunk", "serving_adaptive_steady", 4,
        "chunk-knob rungs are 128-multiples at or below the baseline, "
        "so every tightened chunk lands in a (chunk bucket, table-width "
        "power-of-two) pair the warm loop already compiled"),
    CompileBudget(
        "inference.paged_cow", "serving_adaptive_steady", 1,
        "copy-on-write block copy: fixed block geometry, untouched by "
        "any knob"),
]


def budgets_for(scenario: str,
                budgets: Optional[Iterable[CompileBudget]] = None
                ) -> Dict[str, CompileBudget]:
    return {b.entry: b for b in (budgets if budgets is not None else BUDGETS)
            if b.scenario == scenario}


def check_compile_budgets(by_fn: Dict[str, int], scenario: str,
                          budgets: Optional[Iterable[CompileBudget]] = None,
                          strict: bool = False) -> List[str]:
    """Violation strings (empty = contract holds) for a watchdog
    ``by_fn`` compile-count map under ``scenario``. ``strict`` also
    reports watched entries that have no declared budget for the
    scenario (new entry points must declare one)."""
    table = budgets_for(scenario, budgets)
    out: List[str] = []
    for entry, count in sorted(by_fn.items()):
        budget = table.get(entry)
        if budget is None:
            if strict:
                out.append(
                    f"{entry}: compiled {count}x in scenario "
                    f"'{scenario}' but declares no compile budget — add a "
                    "CompileBudget entry (tools/dslint/contracts.py)")
            continue
        if count > budget.max_compiles:
            out.append(
                f"{entry}: {count} compiles exceeds the "
                f"'{scenario}' budget of {budget.max_compiles} — "
                f"contract rationale: {budget.note}")
    return out
