"""What the served check of ``lfm2_24b_serve_rollout`` refuses, measured, and
how close the paged programs come to the plain reference in LOGITS.

    python benchmarks/lfm2_check_controls.py [--pairs 4] [--seed N]
        [--controls sound lost_conv_state ...] [--init K=V,K=V ...]
        [--lengths A B] [--margins] [--logits [--rows 4 --context 2560 --every 64]]
        [--toy]

**The controls** (default). N pairs of check prompts (129 and 130 tokens, 8
tokens each, as ``perfbench/runners/serve.py`` ``check`` draws them) are
served TOGETHER by the program at the cell's sizes (so that neighbouring
slots are held) and held by ``correctness.check_served`` (the comparison
``run.py`` makes: 4 bf16 steps) to the plain reference: once served by the
sound program, and once by the program with ONE fault planted (a new engine
each; the reference is never touched). ``sound`` must read 0 refused; a
control that is not refused on most prompts is a mechanism the cell's
``correct`` cannot see. The faults:

* ``lost_conv_state``: a decode step's conv reads zeros where the slot holds
  the row's last two inputs (what it writes is sound);
  ``shifted_conv_state``: it reads them a position late (the two swapped);
  ``neighbours_slot``: it reads the NEXT slot's;
* ``no_expert_bias``: the router chooses without ``expert_bias``;
  ``not_normalised``: the four weights as the sigmoid gives them;
* ``lead_skipped``: the stack without its leading layer;
* ``rope_theta_1e4``: rope of theta 1e4 where the configuration says 1e6;
* ``bf16_router``, ``bf16_conv``: the nearest precision below the one the
  configuration states (``assumed.dtype``: the router and the conv's taps in
  float32): the router's logits, scores and weights rounded to bf16; the
  conv's taps from bf16 weights with a bf16 sum. Whether the served check
  SEES a precision is a reading to write down, not a fault it has to refuse.

``--init`` tries another seeded init than the preset's (``init_std``,
``embed_init_std``, ``router_init_scale``, and ``expert_bias_std``: the
harness's draw of ``expert_bias`` scaled to another deviation), one engine
after the other: a trial, not the cell's.

**The logits** (``--logits``). ``--rows`` requests drawn from the cell's
traffic are prefilled into one pool at the cell's sizes (256 rows wide) and
decoded together through ``forward_paged_prefill`` / ``forward_paged_decode``
(greedy, the program's own picks) until the longest holds ``--context``
tokens (2,560: the cell's longest request); their logits at the prefill's
last position, at every ``--every``-th decode step and at the last 8 are
held to the reference's full forward over the same tokens (computed a row
at a time, the head on the kept positions alone), as a share of the largest
logit and in bf16 steps.

TPU only unless ``--toy`` (the rehearsal configuration on the CPU, bf16 as
served: to debug the script, proves nothing about the chip).
"""

import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "perfbench"),
                os.path.join(ROOT, "benchmarks")]

# the loop over trials and controls, ``--init`` and the command line are
# the LongCat tool's
import longcat_check_controls as shared  # noqa: E402

CONTROLS = ("sound", "lost_conv_state", "shifted_conv_state",
            "neighbours_slot", "no_expert_bias", "not_normalised",
            "lead_skipped", "rope_theta_1e4", "bf16_router", "bf16_conv")
TRAFFIC = "closed_rollout_2k"

#: control -> overrides of the preset that plant it (the others are planted
#: while an engine traces: ``planted``)
FAULTS = {"rope_theta_1e4": {"rope_theta": 1e4},
          "not_normalised": {"moe": {"norm_topk_prob": False}}}


_rounded = shared._rounded


@contextlib.contextmanager
def planted(control, slots: int = 0):
    """Functions of the program's modules replaced while an engine traces,
    so that the program has no option for a fault. ``slots``: the slots a
    layer of the conv pools (the engine's ``max_running + 1``), which
    ``neighbours_slot`` wraps within."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import moe_lm
    from deepspeed_tpu.models import state_mixers as SM
    from deepspeed_tpu.models import transformer as T

    record = SM.STATE_MIXERS[T.SHORT_CONV]
    routing, taps = moe_lm.topk_routing, SM._short_conv_taps

    def reading(change):
        """The sound decode step, its conv READING ``change(conv, base,
        slots)`` of the pool; what it writes back is the sound step's."""
        def decode(cfg, x, lp, state, conv, base, slots):
            y, state, _ = record.decode(cfg, x, lp, state,
                                        *change(conv, base, slots))
            return y, state, record.decode(cfg, x, lp, state, conv, base,
                                           slots)[2]
        return decode

    def neighbour(conv, base, rows):
        # the next slot of a live row, wrapped past the last onto slot 1
        return conv, base, jnp.where(rows > 0, rows % (slots - 1) + 1, 0)

    def routing_bf16(logits, *a, **kw):
        weights, experts, scores = routing(_rounded(logits), *a, **kw)
        return _rounded(weights), experts, _rounded(scores)

    def no_bias(logits, *a, select_bias=None, **kw):
        return routing(logits, *a, select_bias=jnp.zeros_like(select_bias), **kw)

    decodes = {
        "lost_conv_state": reading(lambda c, b, s: (jnp.zeros_like(c), b, s)),
        "shifted_conv_state": reading(lambda c, b, s: (jnp.roll(c, 1, axis=1), b, s)),
        "neighbours_slot": reading(neighbour)}
    patches = {
        "bf16_router": [(moe_lm, "topk_routing", routing_bf16)],
        "no_expert_bias": [(moe_lm, "topk_routing", no_bias)],
        "bf16_conv": [(SM, "_short_conv_taps", lambda win, lp, Tn: _rounded(taps(
            _rounded(win), {**lp, "conv_w": _rounded(lp["conv_w"])}, Tn)))],
    }.get(control, [])
    kept = [(mod, name, getattr(mod, name)) for mod, name, _ in patches]
    try:
        for mod, name, fn in patches:
            setattr(mod, name, fn)
        if control in decodes:
            SM.STATE_MIXERS[T.SHORT_CONV] = record._replace(decode=decodes[control])
        yield
    finally:
        SM.STATE_MIXERS[T.SHORT_CONV] = record
        for mod, name, fn in kept:
            setattr(mod, name, fn)


def build(config, init, control):
    """(the sound model under the trial's init, the model with ``control``
    planted)."""
    import dataclasses

    from build_model import build_model
    over, moe = shared.trial_init(init)
    sound = build_model(config["preset"], **over, **({"moe": moe} if moe else {}))
    fault = dict(FAULTS.get(control, {}))
    moe = {**moe, **fault.pop("moe", {})}
    model = build_model(config["preset"], **over, **fault,
                        **({"moe": moe} if moe else {}))
    if control == "lead_skipped":
        # a stack without a lead; the loop hands it the tree without the
        # group
        cfg = model.config
        model = type(model)(dataclasses.replace(
            cfg, lead_kinds=(), n_layer=cfg.n_layer - len(cfg.lead_kinds)),
            model.moe, param_dtype=model.param_dtype)
    return sound, model


def controls(args, config, name_map, name):
    import numpy as np

    def room(ref, cfg, weights, prompts, served):
        """How far the reference's pick stands above its runner-up at the
        served positions: what the program's own noise has to cross before
        a served token reads over 0; and the share of served tokens that
        are their input token (the tied head's echo)."""
        if not args.margins:
            return {}
        m = np.concatenate([_margins(ref, cfg, weights, p, s)
                            for p, s in zip(prompts, served)])
        return {"margin_min_bf16_steps": round(float(m.min()), 2),
                "margin_p10_bf16_steps": round(float(np.percentile(m, 10)), 2),
                "margin_median_bf16_steps": round(float(np.median(m)), 2),
                "echo_share": round(float(np.mean(np.concatenate(
                    [np.asarray(s) == np.concatenate([p[-1:], s[:-1]])
                     for p, s in zip(prompts, served)]))), 3)}

    slots = int(config["assumed"]["serve"]["max_running"]) + 1
    shared.controls(args, config, name_map, name, traffic=TRAFFIC, build=build,
                    planted=lambda control: planted(control, slots),
                    sound_extra=room)


def _margins(ref, cfg, weights, prompt, served):
    """The reference's largest logit over its second largest at each served
    position, teacher-forced on the served tokens, in bf16 steps of the
    largest (the unit of ``correctness.check_served``)."""
    import jax.numpy as jnp
    import numpy as np
    seq = np.concatenate([prompt, np.asarray(served, np.int32)])[None, :]
    h = ref.final_hidden(cfg, weights, jnp.asarray(seq))
    rows = h[0, len(prompt) - 1: len(prompt) - 1 + len(served)]
    top = np.sort(np.asarray(ref.logits_rows(cfg, weights, rows), np.float32),
                  axis=-1)[:, -2:]
    step = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(top[:, 1]), 1e-30))) - 7)
    return (top[:, 1] - top[:, 0]) / step


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
    context = min(args.context, mcfg.max_seq)
    steps = context - max(len(p) for p in prompts)
    keep = sorted({s for s in range(steps) if s % args.every == 0}
                  | set(range(max(steps - 8, 0), steps)))
    per_row = (nb - 1) // W
    tables = np.zeros((W, per_row), np.int32)
    tables[:rows] = np.stack([1 + r * per_row + np.arange(per_row)
                              for r in range(rows)])
    # rows take slots from the far end, so that a row is not its own slot
    slot_of = np.zeros((W,), np.int32)
    slot_of[:rows] = W - np.arange(rows)
    params = shared.trial_params(args.init[0], make_params(
        model, args.seed, jnp.bfloat16, jax.devices()[:1]))
    weights = ref.Weights(params, name_map)
    pools = model.init_paged_cache(nb, bs, jnp.bfloat16, state_slots=W + 1)
    prefill = jax.jit(model.forward_paged_prefill, donate_argnums=(2,))
    decode = jax.jit(model.forward_paged_decode, donate_argnums=(2,))
    toks = np.zeros((W, steps + 1), np.int32)
    kept = np.zeros((rows, 1 + len(keep), mcfg.vocab_size), np.float32)
    t0 = time.perf_counter()
    for r, p in enumerate(prompts):
        Tb = InferenceEngine._bucket(len(p), mcfg.max_seq)
        padded = np.zeros((1, Tb), np.int32)
        padded[0, :len(p)] = p
        at = np.arange(Tb)
        slots = np.where(at < len(p), tables[r][np.minimum(at // bs, per_row - 1)]
                         * bs + at % bs, at % bs).astype(np.int32)
        lg, pools = prefill(params, padded, pools, slots, np.int32(len(p) - 1),
                            np.int32(slot_of[r]))
        kept[r, 0] = np.asarray(lg[0], np.float32)
        toks[r, 0] = kept[r, 0].argmax()
    pos = np.zeros((W,), np.int32)
    pos[:rows] = [len(p) for p in prompts]
    live = (np.arange(W) < rows).astype(np.int32)
    for s in range(steps):
        lg, pools, _ = decode(params, toks[:, s:s + 1], pools, tables,
                              pos + s * live, None, slot_of)
        toks[:, s + 1] = np.asarray(jnp.argmax(lg, axis=-1))
        if s in keep:
            kept[:, 1 + keep.index(s)] = np.asarray(lg[:rows], np.float32)
    del pools
    print(f"[logits] {name}: {rows} rows (prompts {[len(p) for p in prompts]}), "
          f"{steps} decode steps to a context of {context}, {len(keep)} kept: "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    want = []
    for r in range(rows):
        n = len(prompts[r])
        seq = np.concatenate([prompts[r], toks[r, :-1]])[None]
        h = ref.final_hidden(cfg, weights, jnp.asarray(seq))
        at = np.array([n - 1] + [n + s for s in keep])
        want.append(np.asarray(ref.logits_rows(cfg, weights, h[0, at]), np.float32))
    want = np.stack(want)
    diff = np.abs(kept - want)
    top = np.abs(want).max(-1)
    step = 2.0 ** (np.floor(np.log2(top)) - 7)
    late = 1 + len(keep) - min(8, len(keep))          # the last 8: longest context
    print(json.dumps({
        "config": name, "rows_compared": rows, "context": context,
        "kept_decode_steps": len(keep),
        "largest_logit": round(float(top.max()), 4),
        "logit_std": round(float(want.std()), 5),
        "prefill_max_abs": float(diff[:, 0].max()),
        "prefill_max_over_top": float((diff[:, 0].max(-1) / top[:, 0]).max()),
        "decode_max_abs": float(diff[:, 1:].max()),
        "decode_max_over_top": float((diff[:, 1:].max(-1) / top[:, 1:]).max()),
        "decode_max_bf16_steps": float((diff[:, 1:].max(-1) / step[:, 1:]).max()),
        "last8_max_over_top": float((diff[:, late:].max(-1) / top[:, late:]).max()),
        "decode_rms_over_std": float(np.sqrt((diff[:, 1:] ** 2).mean())
                                     / want.std()),
        "argmax_share": float((kept.argmax(-1) == want.argmax(-1)).mean()),
        "reference_s": round(time.perf_counter() - t0, 1),
    }), flush=True)


def _more_args(ap):
    ap.add_argument("--margins", action="store_true",
                    help="with the sound control: the reference's room "
                         "between its pick and its runner-up")
    ap.add_argument("--context", type=int, default=2560)
    ap.add_argument("--every", type=int, default=64)


if __name__ == "__main__":
    shared.main("lfm2_check_controls",
                ("lfm2-24b-a2b", "rehearsal-lfm2-moe-tiny"), CONTROLS,
                5200000101, controls, logits, _more_args)
