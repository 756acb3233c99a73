"""What the served check of ``longcatflashomni_serve_ctx3k`` refuses, measured,
and how close the paged programs come to the plain reference in LOGITS.

    python benchmarks/longcat_check_controls.py [--pairs 4] [--seed N]
        [--controls sound no_shortcut ...] [--init K=V,K=V ...]
        [--lengths A B] [--logits [--rows 4 --steps 16]] [--toy]

**The controls** (default). N pairs of check prompts (2,049 and 2,050
tokens, 8 tokens each, as ``perfbench/runners/serve.py`` ``check`` draws
them) are served by the program at the cell's sizes and held by
``correctness.check_served`` (the comparison ``run.py`` makes: 4 bf16 steps)
to the plain reference: once served by the sound program, and once by the
program with ONE fault planted (a new engine each; the reference is never
touched). ``sound`` must read 0 refused; a control that is not refused on
most prompts is a mechanism the cell's ``correct`` cannot see. The faults:

* ``no_shortcut``: the shortcut MoE adds nothing (its held experts' and its
  zero-compute experts' parts both dropped);
* ``zero_experts_nothing``: an assignment to a zero-compute expert returns
  nothing (they are scored and chosen as published, then left out);
* ``no_lora_scale``: the query and the normed latent without their
  ``sqrt(6144 / rank)``; ``half_split_rope``: the rope's pairs ``(i, i +
  32)`` in place of the published ``(2i, 2i + 1)``;
* ``other_share``: the experts of share 1 (16-31 of 512) read from this
  share's weights;
* ``bf16_router``, ``bf16_norms``: the nearest precision below the one the
  configuration states (``assumed.dtype``: norms, softmaxes and the router
  in float32), where it can be planted outside a kernel: the router's logits,
  scores and weights rounded to bf16; every RMSNorm (the four of a layer,
  the final one, the two of the latent bottlenecks) computed in bf16.
  Whether the served check SEES a precision is a reading to write down, not
  a fault it has to refuse.

``--init`` tries another seeded init than the preset's (``init_std``,
``embed_init_std``, ``router_init_scale``; ``expert_bias_std`` scales what
the harness drew for a sigmoid router's selection bias), one engine after
the other: a trial, not the cell's.

**The logits** (``--logits``). ``--rows`` requests drawn from the cell's
traffic are prefilled into one pool at the cell's sizes and decoded together
for ``--steps`` steps through ``forward_paged_prefill`` / ``forward_paged_decode``
(greedy, the program's own picks: the flash prefill over the expanded
latent, then the absorbed kernel); their logits at the prefill's last
position and at every decode step are held to the reference's full forward
over the same tokens, as a share of the largest logit and in bf16 steps.

TPU only unless ``--toy`` (the rehearsal configuration on the CPU, bf16 as
served: to debug the script, proves nothing about the chip).
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

CONTROLS = ("sound", "no_shortcut", "zero_experts_nothing", "no_lora_scale",
            "half_split_rope", "other_share", "bf16_router", "bf16_norms")
TRAFFIC = "closed_ctx3k_1k"


#: control -> overrides of the preset that plant it (``no_shortcut`` and
#: ``zero_experts_nothing`` need the built model: ``build``)
FAULTS = {
    "sound": {}, "no_shortcut": {}, "zero_experts_nothing": {},
    "no_lora_scale": {"mla_lora_scale": False},
    "half_split_rope": {"rope_interleaved": False},
    "other_share": {"share": 1},
    "bf16_router": {}, "bf16_norms": {},
}


def _rounded(a):
    # not a pair of casts: XLA may keep the excess precision of those
    import jax
    import jax.numpy as jnp
    return jax.lax.reduce_precision(a.astype(jnp.float32), exponent_bits=8,
                                    mantissa_bits=7)


@contextlib.contextmanager
def planted(control):
    """The precision controls: functions of the program's modules replaced
    while an engine traces, so that the program has no option for them."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import latent_attention as LA
    from deepspeed_tpu.models import moe_lm
    from deepspeed_tpu.models import transformer as T

    routing = moe_lm.topk_routing

    def routing_bf16(logits, *a, **kw):
        weights, experts, scores = routing(_rounded(logits), *a, **kw)
        return _rounded(weights), experts, _rounded(scores)

    def rms_bf16(x, scale, eps):
        r = _rounded
        var = r(jnp.mean(r(jnp.square(r(x))), axis=-1, keepdims=True))
        return r(r(r(x) * r(jax.lax.rsqrt(var + eps))) * r(scale)).astype(x.dtype)

    patches = {
        "bf16_router": [(moe_lm, "topk_routing", routing_bf16)],
        "bf16_norms": [
            (T, "_norm", lambda cfg, x, p: rms_bf16(x, p["scale"], cfg.norm_eps)),
            (LA, "_rms", lambda x, p, eps: rms_bf16(x, p["scale"], eps))],
    }.get(control, [])
    kept = [(mod, name, getattr(mod, name)) for mod, name, _ in patches]
    try:
        for mod, name, fn in patches:
            setattr(mod, name, fn)
        yield
    finally:
        for mod, name, fn in kept:
            setattr(mod, name, fn)


#: the deviation ``perfbench/weights.py`` draws every bias at
HARNESS_BIAS_STD = 0.02


def trial_init(init):
    """``K=V,K=V`` -> (overrides of the preset, those of them its ``moe``
    takes). ``expert_bias_std`` is no key of either: ``trial_params``."""
    over = {k: float(v) for k, v in
            (kv.split("=") for kv in init.split(",") if kv)}
    over.pop("expert_bias_std", None)
    moe = {k: over.pop(k) for k in ("router_init_scale",) if k in over}
    return over, moe


def trial_params(init, params):
    """``expert_bias_std=S``: the sigmoid router's selection bias (leaves
    ``b_select``, which the harness draws at 0.02) scaled to deviation S."""
    import jax
    std = dict(kv.split("=") for kv in init.split(",") if kv).get(
        "expert_bias_std")
    if std is None:
        return params
    k = float(std) / HARNESS_BIAS_STD
    return jax.tree_util.tree_map_with_path(
        lambda path, a: (a * k).astype(a.dtype)
        if getattr(path[-1], "key", None) == "b_select" else a, params)


def build(config, init, control):
    """(the sound model under the trial's init, the model with ``control``
    planted)."""
    from build_model import build_model
    over, moe = trial_init(init)
    sound = build_model(config["preset"], **over, **({"moe": moe} if moe else {}))
    if control == "zero_experts_nothing":
        # the router keeps every output; those past the real ones are no
        # expert of anybody's
        moe = {**moe, "zero_experts": 0, "router_experts": sound.router_width}
    model = build_model(config["preset"], **over, **FAULTS[control],
                        **({"moe": moe} if moe else {}))
    if control == "no_shortcut":
        import jax.numpy as jnp
        inner = model._nodrop_mlp

        def nothing(*a, **kw):
            out, *rest = inner(*a, **kw)
            return (jnp.zeros_like(out), *rest)
        model._nodrop_mlp = nothing
    return sound, model


def controls(args, config, name_map, name, traffic=TRAFFIC, build=None,
             planted=None, sound_extra=None, cfg_of=None):
    """The loop of every trial and control. Another configuration's tool
    (``lfm2_check_controls.py``) hands its own ``traffic``, ``build`` and
    ``planted``, ``sound_extra(ref, cfg, weights, prompts, served)``: more
    keys for the sound control's line, and ``cfg_of(control, cfg)``: the
    reference's configuration the served tokens are CHECKED under (a control
    planted in the reference and not in the program)."""
    build = build or globals()["build"]
    planted = planted or globals()["planted"]
    cfg_of = cfg_of or (lambda control, cfg: cfg)
    import jax
    import jax.numpy as jnp
    import numpy as np

    import correctness
    import deepspeed_tpu
    import traffic as traffic_mod
    from deepspeed_tpu.inference.serve import AsyncServingEngine
    from weights import make_params

    cfg = correctness.reference_config(config, name_map)
    ref = correctness.load_reference(name_map)
    serve = config["assumed"]["serve"]
    spec = traffic_mod.load(traffic)
    want = int(spec["check"]["tokens"])
    for trial in args.init:
        sound, _ = build(config, trial, "sound")
        mcfg = sound.config
        lo = traffic_mod.ServeTraffic(
            spec, mcfg.vocab_size, args.seed,
            config.get("length_scale", 1.0)).prompt_bounds()[0][0]
        lens = tuple(args.lengths or (lo + 1, lo + 2))
        prompts = [np.random.default_rng([args.seed, 11, i]).integers(
            0, mcfg.vocab_size, size=n).astype(np.int32)
            for i in range(args.pairs) for n in lens]
        true_params = trial_params(trial, make_params(
            sound, args.seed, jnp.bfloat16, jax.devices()[:1]))
        weights = ref.Weights(true_params, name_map)
        for control in args.controls:
            t0 = time.perf_counter()
            _, model = build(config, trial, control)
            # a control may serve a stack without one of the tree's groups
            own = set(jax.eval_shape(model.init_params, jax.random.key(0)))
            with planted(control):
                engine = deepspeed_tpu.init_inference(
                    model, dtype="bf16",
                    params={k: v for k, v in true_params.items() if k in own},
                    serving={"block_size": int(serve["block_size"]),
                             "max_running": int(serve["max_running"]),
                             "max_num_blocks": int(serve["max_num_blocks"])})
                serving = AsyncServingEngine(engine, max_new_tokens=mcfg.max_seq)
                handles = [serving.add_request(p, max_new_tokens=want)
                           for p in prompts]
                served = [[t for burst in h.stream(timeout=1100) for t in burst]
                          for h in handles]
                serving.shutdown(drain=False, timeout=120)
                assert all(len(s) == want for s in served), [len(s) for s in served]
            served_s = time.perf_counter() - t0
            del engine, serving, handles
            gc.collect()
            jax.clear_caches()
            got = [correctness.check_served(cfg_of(control, cfg), weights, p, s)
                   for p, s in zip(prompts, served)]
            gaps = np.array([g["worst_gap_bf16_steps"] for g in got])
            refused = int(sum(not g["ok"] for g in got))
            extra = (sound_extra(ref, cfg, weights, prompts, served)
                     if control == "sound" and sound_extra else {})
            print(json.dumps({
                **extra, "config": name, "init": trial or "preset",
                "init_std": mcfg.init_std, "embed_init_std": mcfg.embed_init_std,
                "router_init_scale": sound.moe.router_init_scale,
                "control": control, "prompts": len(prompts), "lengths": lens,
                "prompts_refused": refused,
                "worst_gap_bf16_steps": round(float(gaps.max()), 3),
                "median_gap_bf16_steps": round(float(np.median(gaps)), 3),
                "argmax_share": round(float(np.mean(
                    [g["argmax_matches"] / want for g in got])), 3),
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
    from deepspeed_tpu.inference.engine import InferenceEngine
    from weights import make_params

    cfg = correctness.reference_config(config, name_map)
    ref = correctness.load_reference(name_map)
    serve = config["assumed"]["serve"]
    bs, nb = int(serve["block_size"]), int(serve["max_num_blocks"])
    W = int(serve["max_running"])
    rows = min(args.rows, W)
    spec = traffic_mod.load(TRAFFIC)
    model, _ = build(config, args.init[0], "sound")
    mcfg = model.config
    mix = traffic_mod.ServeTraffic(spec, mcfg.vocab_size, args.seed,
                                   config.get("length_scale", 1.0))
    prompts = [mix.request(i)["prompt"] for i in range(rows)]
    per_row = (nb - 1) // W
    tables = np.zeros((W, per_row), np.int32)
    tables[:rows] = np.stack([1 + r * per_row + np.arange(per_row)
                              for r in range(rows)])
    params = make_params(model, args.seed, jnp.bfloat16, jax.devices()[:1])
    weights = ref.Weights(params, name_map)
    pools = model.init_paged_cache(nb, bs, jnp.bfloat16)
    prefill = jax.jit(model.forward_paged_prefill, donate_argnums=(2,))
    decode = jax.jit(model.forward_paged_decode, donate_argnums=(2,))
    toks = np.zeros((W, args.steps + 1), np.int32)
    kept = np.zeros((rows, args.steps + 1, mcfg.vocab_size), np.float32)
    t0 = time.perf_counter()
    for r, p in enumerate(prompts):
        Tb = InferenceEngine._bucket(len(p), mcfg.max_seq)
        padded = np.zeros((1, Tb), np.int32)
        padded[0, :len(p)] = p
        at = np.arange(Tb)
        slots = np.where(at < len(p), tables[r][np.minimum(at // bs, per_row - 1)]
                         * bs + at % bs, at % bs).astype(np.int32)
        lg, pools = prefill(params, padded, pools, slots, np.int32(len(p) - 1))
        kept[r, 0] = np.asarray(lg[0], np.float32)
        toks[r, 0] = kept[r, 0].argmax()
    pos = np.zeros((W,), np.int32)
    pos[:rows] = [len(p) for p in prompts]
    live = (np.arange(W) < rows).astype(np.int32)
    for s in range(args.steps):
        lg, pools, _ = decode(params, toks[:, s:s + 1], pools, tables,
                              pos + s * live)
        lg = np.asarray(lg, np.float32)
        toks[:, s + 1] = lg.argmax(-1)
        kept[:, s + 1] = lg[:rows]
    del pools
    print(f"[logits] {name}: {rows} rows (prompts {[len(p) for p in prompts]}), "
          f"{args.steps} decode steps {time.perf_counter() - t0:.1f}s", flush=True)
    want = []
    for r in range(rows):
        seq = np.concatenate([prompts[r], toks[r, :-1]])[None]
        h = ref.final_hidden(cfg, weights, jnp.asarray(seq))
        want.append(np.asarray(ref.logits_rows(
            cfg, weights, h[0, len(prompts[r]) - 1:]), np.float32))
    want = np.stack(want)
    diff = np.abs(kept - want)
    top = np.abs(want).max(-1)
    step = 2.0 ** (np.floor(np.log2(top)) - 7)
    print(json.dumps({
        "config": name, "rows_compared": rows,
        "largest_logit": round(float(top.max()), 4),
        "logit_std": round(float(want.std()), 5),
        "prefill_max_abs": float(diff[:, 0].max()),
        "prefill_max_over_top": float((diff[:, 0].max(-1) / top[:, 0]).max()),
        "decode_max_abs": float(diff[:, 1:].max()),
        "decode_max_over_top": float((diff[:, 1:].max(-1) / top[:, 1:]).max()),
        "decode_max_bf16_steps": float((diff[:, 1:].max(-1) / step[:, 1:]).max()),
        "decode_rms_over_std": float(np.sqrt((diff[:, 1:] ** 2).mean())
                                     / want.std()),
        "argmax_share": float((kept.argmax(-1) == want.argmax(-1)).mean()),
    }), flush=True)


def main(tool="longcat_check_controls", configs=("longcat-flash-omni",
                                                 "rehearsal-longcat-flash-tiny"),
         names=CONTROLS, seed=4800000101, run_controls=controls,
         run_logits=logits, more_args=None):
    """``configs``: (the cell's configuration, its rehearsal's).
    ``more_args(ap)``: another tool's own options. Returns what the chosen
    run returned (a tool's exit code, or None)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--pairs", type=int, default=4)
    ap.add_argument("--seed", type=int, default=seed)
    ap.add_argument("--controls", nargs="+", default=list(names), choices=names)
    ap.add_argument("--toy", action="store_true")
    ap.add_argument("--lengths", type=int, nargs=2, default=None)
    ap.add_argument("--init", nargs="+", default=[""],
                    help="K=V,K=V over the preset: init_std, embed_init_std, "
                         "router_init_scale, expert_bias_std")
    ap.add_argument("--logits", action="store_true")
    ap.add_argument("--rows", type=int, default=4)
    if more_args:
        more_args(ap)
    else:
        ap.add_argument("--steps", type=int, default=16)
    args = ap.parse_args()

    import correctness
    name = configs[1] if args.toy else configs[0]
    if not args.toy:
        from deepspeed_tpu.accelerator import require_tpu
        try:
            require_tpu()
        except Exception as e:  # noqa: BLE001
            sys.exit(f"{tool}: {e}")
    with open(os.path.join(ROOT, "perfbench", "configs", name + ".json")) as f:
        config = json.load(f)
    name_map = correctness.load_map(name)
    return (run_logits if args.logits else run_controls)(
        args, config, name_map, name)


if __name__ == "__main__":
    main()
