"""What the served check of ``sdar30b_serve_blockgen`` refuses, measured: N
pairs of check prompts (129 and 130 tokens, 8 tokens each, as
``perfbench/runners/serve.py`` ``check`` draws them) served by the program at
the cell's sizes and held to ``correctness``'s limit of 4 bf16 steps against
the plain reference, on sound code and under one planted fault at a time.

    python benchmarks/sdar_check_controls.py [--pairs 32] [--seed N]
        [--faults sound no_commit ...] [--toy]

A pair is refused when either of its prompts is. Sound code must read 0
refused; each fault's count says whether the cell's check would catch it
(what it cannot see is held by ``tests/unit/test_sdar.py``'s logits on the
CPU). The faults, each planted in the program and taken out again:

* ``no_commit``: every committing entry of a pass (a rider, a lone
  commit's) writes the dummy block, so the block's KV stays what its last
  denoise pass wrote;
* ``denoise_kv``: a committing entry is fed the last denoise pass's tokens
  (``[MASK]`` at the block's last position) in place of the final ones:
  by construction the same pool state as ``no_commit``, reached another way;
* ``causal_prefill``: the prefill's mask is the plain triangle;
* ``flat_qk_norm``: q and k normed over the whole projection (OLMoE's);
* ``mask_as_0``: an undecided position embedded as id 0;
* ``other_share``: the held experts taken for share 1's (16-31).

The 2 x N requests of a variant are served together (64 rows), the
reference takes the sequences of one length through the stack together.
TPU only unless ``--toy`` (the rehearsal configuration on the CPU, to debug
the script: proves nothing about the chip).
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "perfbench")]

FAULTS = ("sound", "no_commit", "denoise_kv", "causal_prefill",
          "flat_qk_norm", "mask_as_0", "other_share")


def plant(fault, engine, models):
    """Plant ``fault``; returns the function that takes it out again."""
    import jax.numpy as jnp

    from deepspeed_tpu.inference import blockgen, engine as eng
    from deepspeed_tpu.models import transformer as T
    undo = []

    def patch(obj, name, value):
        old = getattr(obj, name)
        setattr(obj, name, value)
        undo.append(lambda: setattr(obj, name, old))

    def kinds(inputs):
        # the executor takes a kind's hooks from the table's record
        old = eng._ACTION_KINDS["block"]
        eng._ACTION_KINDS["block"] = old._replace(inputs=inputs)
        undo.append(lambda: eng._ACTION_KINDS.__setitem__("block", old))

    gen = models[0].config.generation
    if fault == "no_commit":
        real = eng._ServeSession._block_inputs

        def inputs(self, reqs):
            # a lone commit's entry and every rider (the entries past the
            # rows' own) write to the dummy block
            (feed, bt, pos, n, commit, *rest), plan = real(self, reqs)
            bt = bt.copy()
            bt[:commit.size][commit] = 0
            bt[commit.size:] = 0
            return (feed, bt, pos, n, commit, *rest), plan
        kinds(inputs)
    elif fault == "denoise_kv":
        real = eng._ServeSession._block_inputs

        def inputs(self, reqs):
            # the host feeds every entry here (depth zero below), so a
            # committing entry's last position (a lone commit's, a
            # rider's) can be handed over undecided
            (feed, bt, pos, n, commit, *rest), plan = real(self, reqs)
            prev, idx, host = feed
            host = host.copy()
            host[commit[:len(reqs)].nonzero()[0], gen.block - 1] = -1
            host[commit.size:, gen.block - 1] = -1
            assert (idx < 0).all()
            return ((prev, idx, host), bt, pos, n, commit, *rest), plan
        kinds(inputs)
        patch(eng._ServeSession, "_run_ahead", False)
    elif fault == "causal_prefill":
        real = T._paged_prefill_attention

        def prefill(cfg, *a):
            import dataclasses
            return real(dataclasses.replace(cfg, generation=None), *a)
        patch(T, "_paged_prefill_attention", prefill)
    elif fault == "flat_qk_norm":
        def flat(cfg, q, k, lp):
            import jax

            def rms(x, p):
                x32 = x.astype(jnp.float32)
                var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
                g = jnp.tile(p["scale"].astype(jnp.float32),
                             x.shape[-1] // cfg.head_dim)
                return (x32 * jax.lax.rsqrt(var + cfg.norm_eps) * g).astype(x.dtype)
            return rms(q, lp["q_norm"]), rms(k, lp["k_norm"])
        patch(T, "_qk_norm", flat)
    elif fault == "mask_as_0":
        patch(blockgen, "tokens_of", lambda gen, state: jnp.maximum(state, 0))
    elif fault == "other_share":
        patch(engine, "module", models[1])
    elif fault != "sound":
        raise ValueError(fault)
    engine._paged_jits = None            # the programs are traced again
    return lambda: [u() for u in reversed(undo)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pairs", type=int, default=32)
    ap.add_argument("--seed", type=int, default=3300000101)
    ap.add_argument("--faults", nargs="+", default=list(FAULTS))
    ap.add_argument("--toy", action="store_true")
    ap.add_argument("--embed-std", type=float, default=None,
                    help="draw the token embedding at this std (a trial of "
                         "the preset's init, not the cell's)")
    args = ap.parse_args()
    if args.toy:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    import correctness
    import deepspeed_tpu
    from build_model import build_model
    from deepspeed_tpu.inference.serve import AsyncServingEngine
    from weights import make_params

    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.toy:
        sys.exit(f"sdar_check_controls: the default device is {platform!r}, "
                 "not a TPU")
    name = "rehearsal-sdar-tiny" if args.toy else "sdar-30b-a3b-chat"
    with open(os.path.join(ROOT, "perfbench", "configs", name + ".json")) as f:
        config = json.load(f)
    name_map = correctness.load_map(name)
    over = {} if args.embed_std is None else {"embed_init_std": args.embed_std}
    models = [build_model(config["preset"], **over),
              build_model(config["preset"], share=1, **over)]
    mcfg = models[0].config
    serve = config["assumed"]["serve"]
    dtype = jnp.float32 if args.toy else jnp.bfloat16
    t0 = time.perf_counter()
    params = make_params(models[0], args.seed, dtype, jax.devices()[:1])
    jax.block_until_ready(params)
    engine = deepspeed_tpu.init_inference(
        models[0], params=params, dtype="fp32" if args.toy else "bf16",
        serving={"block_size": int(serve["block_size"]),
                 "max_running": int(serve["max_running"]),
                 "max_num_blocks": int(serve["max_num_blocks"]),
                 # every variant serves the same prompts: a block one
                 # variant registered must not be another's prefix hit
                 "prefix_caching": "off"})
    del params
    print(f"[controls] {name}: weights and engine {time.perf_counter() - t0:.1f}s"
          f"; embedding std {mcfg.embed_init_std or mcfg.init_std}", flush=True)
    cfg = correctness.reference_config(config, name_map)
    ref = correctness.load_reference(name_map)
    weights = ref.Weights(engine.params, name_map)
    lens = (17, 18) if args.toy else (129, 130)
    prompts = [np.random.default_rng([args.seed, 11, i]).integers(
        0, mcfg.vocab_size, size=n).astype(np.int32)
        for i in range(args.pairs) for n in lens]
    want = 8

    for fault in args.faults:
        t0 = time.perf_counter()
        unplant = plant(fault, engine, models)
        try:
            serving = AsyncServingEngine(engine, max_new_tokens=mcfg.max_seq)
            handles = [serving.add_request(p, max_new_tokens=want)
                       for p in prompts]
            served = [[t for burst in h.stream(timeout=900) for t in burst]
                      for h in handles]
            serving.shutdown(drain=False, timeout=120)
        finally:
            unplant()
        t_served = time.perf_counter() - t0
        assert all(len(s) == want for s in served), [len(s) for s in served]
        gaps = np.zeros(len(prompts))
        for n, lo in [(n, lo) for n in lens for lo in range(0, args.pairs, 16)]:
            # sixteen sequences of one length a pass of the stack
            which = [i for i, p in enumerate(prompts) if p.size == n][lo:lo + 16]
            seqs = np.stack([np.concatenate([prompts[i], served[i]])
                             for i in which])
            h = ref.final_hidden(cfg, weights, jnp.asarray(seqs))
            for i, hi in zip(which, h):
                logits = np.asarray(ref.logits_rows(
                    cfg, weights, hi[n - 1:n - 1 + want]), np.float32)
                top = logits.max(axis=-1)
                got = logits[np.arange(want), np.asarray(served[i])]
                step = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(top), 1e-30))) - 7)
                gaps[i] = ((top - got) / step).max()
        pair = gaps.reshape(args.pairs, len(lens)).max(axis=1)
        print(json.dumps({
            "fault": fault, "pairs": args.pairs,
            "pairs_refused": int((pair > correctness.SERVE_ULPS).sum()),
            "prompts_refused": int((gaps > correctness.SERVE_ULPS).sum()),
            "worst_gap_bf16_steps": round(float(gaps.max()), 3),
            "median_gap_bf16_steps": round(float(np.median(gaps)), 3),
            "argmax_share": round(float((gaps == 0).mean()), 3),
            "served_s": round(t_served, 1),
            "reference_s": round(time.perf_counter() - t0 - t_served, 1)}),
            flush=True)
    engine._paged_jits = None


if __name__ == "__main__":
    main()
