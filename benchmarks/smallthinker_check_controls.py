"""What the served check of ``smallthinker21b_serve_longctx`` refuses, measured:
N pairs of check prompts (6,146 and 6,147 tokens, 8 tokens each, as
``perfbench/runners/serve.py`` ``check`` draws them) served by the program at
the cell's sizes, and each held by ``correctness.check_served`` (the
comparison ``run.py`` makes: 4 bf16 steps) to the plain reference as it is
and to the reference with ONE thing changed on its side.

    python benchmarks/smallthinker_check_controls.py [--pairs 3] [--seed N]
        [--controls sound window_left_out ...] [--toy] [--init K=V,K=V ...]

A pair is refused when either of its prompts is. ``sound`` must read 0
refused; every control must refuse every pair, or the cell's ``correct``
cannot see that mechanism. The controls, each a key of the reference's
configuration and none of the program's (the served tokens are the sound
program's in every column; the program WITHOUT a mechanism against the
reference with it is the same comparison from the other side):

* ``window_left_out``: ``window_layout`` all 0, every layer sees every key;
* ``rope_on_full_layers``: ``rope_layout`` all 1, the NoPE layers turned;
* ``float8``: the reference computed in the nearest precision below the
  configuration's bf16: its matrices (but the router's) and its activations
  (the residual stream a layer reads, both norms' outputs, q, k, v, the
  heads' outputs, an expert's hidden vector) rounded through
  ``float8_e4m3fn`` (``round_to``);
* ``router_after_attention`` and ``silu_gate`` are the program's to plant
  (its MoE config) and are held by ``tests/unit/test_smallthinker.py``.

``--init`` tries another seeded init than the preset's (``init_std=0.03``;
several: one engine after the other in this process): a trial, not the
cell's. TPU only unless ``--toy`` (the rehearsal configuration on the CPU,
bf16 as served: to debug the script and to see a regime, proves nothing
about the chip).
"""

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "perfbench")]

CONTROLS = ("sound", "window_left_out", "rope_on_full_layers", "float8")


def variant(cfg, control):
    """The reference's configuration under ``control``."""
    depth = len(cfg["window_layout"])
    return {"sound": cfg,
            "window_left_out": {**cfg, "window_layout": [0] * depth},
            "rope_on_full_layers": {**cfg, "rope_layout": [1] * depth},
            "float8": {**cfg, "round_to": "float8_e4m3fn"}}[control]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pairs", type=int, default=3)
    ap.add_argument("--seed", type=int, default=3700000101)
    ap.add_argument("--controls", nargs="+", default=list(CONTROLS))
    ap.add_argument("--control-pairs", type=int, default=None,
                    help="pairs held to each control (default: all; sound "
                         "code is held to every pair)")
    ap.add_argument("--toy", action="store_true")
    ap.add_argument("--lengths", type=int, nargs=2, default=None,
                    help="the pair's prompt lengths (default: the check's)")
    ap.add_argument("--init", nargs="+", default=[""],
                    help="K=V,K=V over the preset's TransformerConfig, one "
                         "trial each; '' is the preset as it is")
    args = ap.parse_args()
    if args.toy:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    import correctness
    import deepspeed_tpu
    import traffic as traffic_mod
    from build_model import build_model
    from deepspeed_tpu.inference.serve import AsyncServingEngine
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    from weights import make_params

    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.toy:
        sys.exit(f"smallthinker_check_controls: the default device is "
                 f"{platform!r}, not a TPU")
    if not args.toy:
        enable_compile_cache()
    name = "rehearsal-smallthinker-tiny" if args.toy \
        else "smallthinker-21ba3b-instruct"
    with open(os.path.join(ROOT, "perfbench", "configs", name + ".json")) as f:
        config = json.load(f)
    name_map = correctness.load_map(name)
    cfg = correctness.reference_config(config, name_map)
    ref = correctness.load_reference(name_map)
    serve = config["assumed"]["serve"]
    spec = traffic_mod.load("closed_longctx_8k")
    want = int(spec["check"]["tokens"])

    for trial in args.init:
        over = {k: float(v) for k, v in
                (kv.split("=") for kv in trial.split(",") if kv)}
        model = build_model(config["preset"], **over)
        mcfg = model.config
        lo = traffic_mod.ServeTraffic(
            spec, mcfg.vocab_size, args.seed,
            config.get("length_scale", 1.0)).prompt_bounds()[0][0]
        lens = tuple(args.lengths or (lo + 1, lo + 2))
        t0 = time.perf_counter()
        params = make_params(model, args.seed, jnp.bfloat16, jax.devices()[:1])
        jax.block_until_ready(params)
        engine = deepspeed_tpu.init_inference(
            model, params=params, dtype="bf16",
            serving={"block_size": int(serve["block_size"]),
                     "max_running": int(serve["max_running"]),
                     "max_num_blocks": int(serve["max_num_blocks"])})
        del params
        prompts = [np.random.default_rng([args.seed, 11, i]).integers(
            0, mcfg.vocab_size, size=n).astype(np.int32)
            for i in range(args.pairs) for n in lens]
        serving = AsyncServingEngine(engine, max_new_tokens=mcfg.max_seq)
        handles = [serving.add_request(p, max_new_tokens=want) for p in prompts]
        served = [[t for burst in h.stream(timeout=1100) for t in burst]
                  for h in handles]
        serving.shutdown(drain=False, timeout=120)
        assert all(len(s) == want for s in served), [len(s) for s in served]
        print(f"[controls] {name} init {over or 'preset'} (init_std "
              f"{mcfg.init_std}, embedding {mcfg.embed_init_std or mcfg.init_std}"
              f"): weights, engine and {len(prompts)} requests of {lens} "
              f"tokens {time.perf_counter() - t0:.1f}s", flush=True)
        weights = ref.Weights(engine.params, name_map)
        for control in args.controls:
            t0 = time.perf_counter()
            pairs = args.pairs if control == "sound" \
                else args.control_pairs or args.pairs
            got = [correctness.check_served(variant(cfg, control), weights, p, s)
                   for p, s in list(zip(prompts, served))[:pairs * len(lens)]]
            gaps = np.array([g["worst_gap_bf16_steps"] for g in got])
            pair = gaps.reshape(pairs, len(lens)).max(axis=1)
            print(json.dumps({
                "init": over or "preset", "control": control,
                "pairs": pairs,
                "pairs_refused": int((pair > correctness.SERVE_ULPS).sum()),
                "prompts_refused": int(sum(not g["ok"] for g in got)),
                "gaps_bf16_steps": [round(float(g), 3) for g in gaps],
                "argmax_share": round(float(np.mean(
                    [g["argmax_matches"] / want for g in got])), 3),
                "reference_s": round(time.perf_counter() - t0, 1)}),
                flush=True)
        del weights, engine, serving, handles, model
        gc.collect()
        jax.clear_caches()


if __name__ == "__main__":
    main()
