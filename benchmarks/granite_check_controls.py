"""What the served check of ``granite4hmicro_serve_chat`` refuses, measured,
and how close the paged programs come to the plain reference in LOGITS.

    python benchmarks/granite_check_controls.py [--pairs 16] [--seed N]
        [--controls sound lost_state ...] [--init K=V,K=V ...] [--lengths A B]
        [--logits [--rows 64 --compare 8 --steps 64] [--float32]] [--toy]

**The controls** (default). N pairs of check prompts (33 and 34 tokens, 8
tokens each, as ``perfbench/runners/serve.py`` ``check`` draws them; 16 pairs
are 32 prompts) are served by the program at the cell's sizes and held by
``correctness.check_served`` (the comparison ``run.py`` makes: 4 bf16 steps)
to the plain reference: once served by the sound program, and once by the
program with ONE fault planted (a new engine each; the reference is never
touched). ``sound`` must read 0 refused; a control that is not refused on
most prompts is a mechanism the cell's ``correct`` cannot see. The faults:

* ``lost_state``: a decode step reads a zero state (the dummy slot's) and
  writes nothing back: what a wrong slot index or a pool copied and dropped
  would do;
* ``conv_dropped``: a decode step's conv sees no inputs before its own;
* ``no_dt_bias``, ``no_D``: ``dt_bias`` / ``D`` are zero in the weights the
  program serves (the reference keeps the true ones);
* ``residual_1``: ``residual_multiplier`` 1.0; ``attn_scale_8``: the scores
  times 1/sqrt(64) and not ``attention_multiplier`` 1/64;
* ``bf16_state``: the state rounded to bf16 wherever it is written (the
  nearest precision below the configuration's float32 state).

Each line also gives ``echo_share``: the share of served tokens equal to the
token they were computed from (a tied head over a x12 embedding can serve its
input back whatever the layers do; a check under which it does sees nothing).
``--init`` tries another seeded init than the preset's, one engine after
the other: a trial, not the cell's.

**The logits** (``--logits``). ``--rows`` requests drawn from the cell's
traffic are prefilled into one pool at the cell's sizes and decoded together
for ``--steps`` steps through ``forward_paged_prefill`` / ``forward_paged_decode``
(greedy, the program's own picks); the first ``--compare`` rows' logits at
the prefill's last position and at every decode step are held to the
reference's full forward over the same tokens. Then the same tokens again
through the program with ``bf16_state`` planted: the limit has to lie between
the two readings.

``--logits --float32`` runs the same comparison at the published widths ON
THE CPU with float32 weights, activations and pools (the programs' plain-XLA
forms): no device number, but the one place where the published widths'
arithmetic is free of bf16's rounding, so that what a bf16 STATE alone costs
can be read; on the chip the bf16 weights and activations leave ten times
more than the state's rounding and the two readings cannot be told apart.

TPU only unless ``--toy`` (the rehearsal configuration on the CPU, bf16 as
served: to debug the script, proves nothing about the chip) or ``--float32``.
"""

import argparse
import contextlib
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "perfbench")]

CONTROLS = ("sound", "lost_state", "conv_dropped", "no_dt_bias", "no_D",
            "residual_1", "attn_scale_8", "bf16_state")


def _faults():
    """control -> (config override, parameter leaf to zero, patches of
    ``models/state_mixers.py``'s functions): planted here, so that the
    program has no option that selects them."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import state_mixers as SM

    step, chunked, project = SM.ssd_recurrent_step, SM.ssd_chunked, SM._mamba2_project

    def lost_state(cfg, state, x, dt, A, Bm, Cm, D, slots, base):
        zero = jnp.zeros((*x.shape[1:], Bm.shape[-1]), jnp.float32)
        y, _ = jax.vmap(lambda xb, dtb, bb, cb: step(zero, xb, dtb, A, bb, cb, D))(
            x, dt, Bm, Cm)
        return y, state

    def conv_dropped(cfg, x, lp, conv_ctx):
        if x.shape[1] == 1:
            conv_ctx = jnp.zeros_like(conv_ctx)
        return project(cfg, x, lp, conv_ctx)

    def rounded(S):
        # not a pair of casts: XLA may keep the excess precision of those
        # (`xla_allow_excess_precision`), and did: both readings were equal
        return jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)

    def step_bf16(S, *a):
        y, S = step(S, *a)
        return y, rounded(S)

    def chunked_bf16(*a, **kw):
        y, S = chunked(*a, **kw)
        return y, rounded(S)

    return {
        "sound": ({}, None, {}),
        "lost_state": ({}, None, {"_ssd_state_update": lost_state}),
        "conv_dropped": ({}, None, {"_mamba2_project": conv_dropped}),
        "no_dt_bias": ({}, "dt_bias", {}),
        "no_D": ({}, "D", {}),
        "residual_1": ({"residual_multiplier": 1.0}, None, {}),
        "attn_scale_8": ({"attn_scale": 0.125}, None, {}),
        # through the plain-XLA form, whose step is the patched one
        "bf16_state": ({}, None, {
            "ssd_recurrent_step": step_bf16, "ssd_chunked": chunked_bf16,
            "_ssd_state_update": lambda cfg, *a: SM._ssd_decode_update(*a)}),
    }


@contextlib.contextmanager
def planted(patches):
    from deepspeed_tpu.models import state_mixers as SM
    kept = {name: getattr(SM, name) for name in patches}
    try:
        for name, fn in patches.items():
            setattr(SM, name, fn)
        yield
    finally:
        for name, fn in kept.items():
            setattr(SM, name, fn)


def without(params, leaf):
    """``params`` with every Mamba-2 layer's ``leaf`` zero."""
    if leaf is None:
        return params
    import jax.numpy as jnp
    return {**params, "layers": tuple(
        {**g, "ssm": {**g["ssm"], leaf: jnp.zeros_like(g["ssm"][leaf])}}
        if "ssm" in g else g for g in params["layers"])}


def echo_share(prompts, served):
    import numpy as np
    same = total = 0
    for p, s in zip(prompts, served):
        fed = np.concatenate([p[-1:], np.asarray(s[:-1], np.int64)])
        same += int((fed == np.asarray(s)).sum())
        total += len(s)
    return same / max(total, 1)


def controls(args, config, name_map, name):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import correctness
    import deepspeed_tpu
    import traffic as traffic_mod
    from build_model import build_model
    from deepspeed_tpu.inference.serve import AsyncServingEngine
    from weights import make_params

    cfg = correctness.reference_config(config, name_map)
    ref = correctness.load_reference(name_map)
    serve = config["assumed"]["serve"]
    spec = traffic_mod.load("closed_chat_short")
    want = int(spec["check"]["tokens"])
    faults = _faults()
    for trial in args.init:
        over = {k: float(v) for k, v in
                (kv.split("=") for kv in trial.split(",") if kv)}
        sound = build_model(config["preset"], **over)
        mcfg = sound.config
        lo = traffic_mod.ServeTraffic(
            spec, mcfg.vocab_size, args.seed,
            config.get("length_scale", 1.0)).prompt_bounds()[0][0]
        lens = tuple(args.lengths or (lo + 1, lo + 2))
        prompts = [np.random.default_rng([args.seed, 11, i]).integers(
            0, mcfg.vocab_size, size=n).astype(np.int32)
            for i in range(args.pairs) for n in lens]
        true_params = make_params(sound, args.seed, jnp.bfloat16,
                                  jax.devices()[:1])
        weights = ref.Weights(true_params, name_map)
        for control in args.controls:
            t0 = time.perf_counter()
            model_over, leaf, patches = faults[control]
            pairs = args.pairs if control == "sound" \
                else args.control_pairs or args.pairs
            mine = prompts[:pairs * len(lens)]
            with planted(patches):
                engine = deepspeed_tpu.init_inference(
                    build_model(config["preset"], **over, **model_over),
                    params=without(true_params, leaf), dtype="bf16",
                    serving={"block_size": int(serve["block_size"]),
                             "max_running": int(serve["max_running"]),
                             "max_num_blocks": int(serve["max_num_blocks"])})
                serving = AsyncServingEngine(engine, max_new_tokens=mcfg.max_seq)
                handles = [serving.add_request(p, max_new_tokens=want)
                           for p in mine]
                served = [[t for burst in h.stream(timeout=1100) for t in burst]
                          for h in handles]
                serving.shutdown(drain=False, timeout=120)
            assert all(len(s) == want for s in served), [len(s) for s in served]
            served_s = time.perf_counter() - t0
            del engine, serving, handles
            gc.collect()
            jax.clear_caches()
            got = [correctness.check_served(cfg, weights, p, s)
                   for p, s in zip(mine, served)]
            gaps = np.array([g["worst_gap_bf16_steps"] for g in got])
            refused = int(sum(not g["ok"] for g in got))
            print(json.dumps({
                "config": name, "init": over or "preset",
                "init_std": mcfg.init_std,
                "embed_init_std": mcfg.embed_init_std or mcfg.init_std,
                "control": control, "prompts": len(mine), "lengths": lens,
                "prompts_refused": refused,
                "share_refused": round(refused / len(mine), 3),
                "worst_gap_bf16_steps": round(float(gaps.max()), 3),
                "median_gap_bf16_steps": round(float(np.median(gaps)), 3),
                "argmax_share": round(float(np.mean(
                    [g["argmax_matches"] / want for g in got])), 3),
                "echo_share": round(echo_share(mine, served), 3),
                # what a reading is made of: tokens that are not the
                # reference's pick, and every prompt's worst reading over 0
                "tokens": want * len(mine),
                "tokens_not_the_argmax": int(sum(
                    want - g["argmax_matches"] for g in got)),
                "readings_over_0": sorted(round(float(g), 2)
                                          for g in gaps if g > 0),
                "served_s": round(served_s, 1),
                "reference_s": round(time.perf_counter() - t0 - served_s, 1)}),
                flush=True)
        del weights, true_params
        gc.collect()


def logits(args, config, name_map, name):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import correctness
    import traffic as traffic_mod
    from build_model import build_model
    from weights import make_params

    cfg = correctness.reference_config(config, name_map)
    ref = correctness.load_reference(name_map)
    serve = config["assumed"]["serve"]
    bs, nb = int(serve["block_size"]), int(serve["max_num_blocks"])
    rows = min(args.rows, int(serve["max_running"]))
    spec = traffic_mod.load("closed_chat_short")
    model = build_model(config["preset"])
    mcfg = model.config
    mix = traffic_mod.ServeTraffic(spec, mcfg.vocab_size, args.seed,
                                   config.get("length_scale", 1.0))
    prompts = [mix.request(i)["prompt"] for i in range(rows)]
    per_row = (nb - 1) // int(serve["max_running"])
    tables = np.stack([1 + r * per_row + np.arange(per_row)
                       for r in range(rows)]).astype(np.int32)
    dtype = jnp.float32 if args.float32 else jnp.bfloat16
    params = make_params(model, args.seed, dtype, jax.devices()[:1])
    weights = ref.Weights(params, name_map)
    from deepspeed_tpu.inference.engine import InferenceEngine

    def run(forced=None):
        """(tokens [rows, steps + 1], logits [compare, steps + 1, V]) of the
        prefills' last positions and ``steps`` decode steps, greedy, or
        teacher-forced on ``forced``."""
        pools = model.init_paged_cache(nb, bs, dtype,
                                       state_slots=int(serve["max_running"]) + 1)
        # a jit of its own a run: what is planted is traced, not a cached
        # program of the sound functions
        prefill = jax.jit(lambda *a: model.forward_paged_prefill(*a),
                          donate_argnums=(2,))
        decode = jax.jit(lambda *a: model.forward_paged_decode(*a),
                         donate_argnums=(2,))
        toks = np.zeros((rows, args.steps + 1), np.int32)
        kept = np.zeros((args.compare, args.steps + 1, mcfg.vocab_size),
                        np.float32)
        for r, p in enumerate(prompts):
            Tb = InferenceEngine._bucket(len(p), mcfg.max_seq)
            padded = np.zeros((1, Tb), np.int32)
            padded[0, :len(p)] = p
            at = np.arange(Tb)
            slots = np.where(at < len(p), tables[r][np.minimum(at // bs, per_row - 1)]
                             * bs + at % bs, at % bs).astype(np.int32)
            lg, pools = prefill(params, padded, pools, slots,
                                np.int32(len(p) - 1), np.int32(r + 1))
            lg = np.asarray(lg[0], np.float32)
            toks[r, 0] = lg.argmax() if forced is None else forced[r, 0]
            if r < args.compare:
                kept[r, 0] = lg
        pos = np.array([len(p) for p in prompts], np.int32)
        slots = np.arange(1, rows + 1, dtype=np.int32)
        for s in range(args.steps):
            lg, pools = decode(params, toks[:, s:s + 1], pools, tables, pos + s,
                               None, slots)
            lg = np.asarray(lg, np.float32)
            toks[:, s + 1] = lg.argmax(-1) if forced is None else forced[:, s + 1]
            kept[:, s + 1] = lg[:args.compare]
        del pools
        return toks, kept

    t0 = time.perf_counter()
    toks, got = run()
    print(f"[logits] {name}: {rows} rows (prompts "
          f"{sorted(len(p) for p in prompts)[::max(rows // 8, 1)]}), "
          f"{args.steps} decode steps {time.perf_counter() - t0:.1f}s", flush=True)
    want = []
    for r in range(args.compare):
        seq = np.concatenate([prompts[r], toks[r, :-1]])[None]
        h = ref.final_hidden(cfg, weights, jnp.asarray(seq))
        want.append(np.asarray(ref.logits_rows(
            cfg, weights, h[0, len(prompts[r]) - 1:]), np.float32))
    want = np.stack(want)

    def report(tag, got):
        diff = np.abs(got - want)
        top = np.abs(want).max(-1)
        # a bf16 step at the magnitude of the reference's largest logit
        step = 2.0 ** (np.floor(np.log2(top)) - 7)
        print(json.dumps({
            "config": name, "program": tag, "rows_compared": args.compare,
            "prompt_tokens": [len(p) for p in prompts[:args.compare]],
            "largest_logit": round(float(top.max()), 4),
            "logit_std": round(float(want.std()), 5),
            "prefill_max_abs": float(diff[:, 0].max()),
            "prefill_max_over_top": float((diff[:, 0].max(-1) / top[:, 0]).max()),
            "decode_max_abs": float(diff[:, 1:].max()),
            "decode_max_over_top": float((diff[:, 1:].max(-1) / top[:, 1:]).max()),
            "decode_max_bf16_steps": float((diff[:, 1:].max(-1) / step[:, 1:]).max()),
            "decode_rms_over_std": float(np.sqrt((diff[:, 1:] ** 2).mean())
                                         / want.std()),
            "argmax_share": float((got.argmax(-1) == want.argmax(-1)).mean()),
        }), flush=True)

    report("sound", got)
    with planted(_faults()["bf16_state"][2]):
        _, faulted = run(forced=toks)
    report("bf16_state", faulted)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pairs", type=int, default=16)
    ap.add_argument("--seed", type=int, default=4300000101)
    ap.add_argument("--controls", nargs="+", default=list(CONTROLS),
                    choices=CONTROLS)
    ap.add_argument("--control-pairs", type=int, default=None,
                    help="pairs served under each fault (default: all)")
    ap.add_argument("--toy", action="store_true")
    ap.add_argument("--lengths", type=int, nargs=2, default=None,
                    help="the pair's prompt lengths (default: the check's)")
    ap.add_argument("--init", nargs="+", default=[""],
                    help="K=V,K=V over the preset's TransformerConfig, one "
                         "trial each; '' is the preset as it is")
    ap.add_argument("--logits", action="store_true")
    ap.add_argument("--rows", type=int, default=64)
    ap.add_argument("--compare", type=int, default=8)
    ap.add_argument("--steps", type=int, default=64)
    ap.add_argument("--float32", action="store_true",
                    help="with --logits: the published widths on the CPU in "
                         "float32")
    args = ap.parse_args()
    if args.float32 and not args.logits:
        ap.error("--float32 goes with --logits")
    if args.toy or args.float32:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    import correctness
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    platform = jax.devices()[0].platform
    if platform != "tpu" and not (args.toy or args.float32):
        sys.exit(f"granite_check_controls: the default device is "
                 f"{platform!r}, not a TPU")
    if not (args.toy or args.float32):
        enable_compile_cache()
    name = "rehearsal-granite-hybrid-tiny" if args.toy else "granite-4.0-h-micro"
    with open(os.path.join(ROOT, "perfbench", "configs", name + ".json")) as f:
        config = json.load(f)
    name_map = correctness.load_map(name)
    (logits if args.logits else controls)(args, config, name_map, name)


if __name__ == "__main__":
    main()
