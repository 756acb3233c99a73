"""What the served check of ``kanana2_30b_serve_longdoc`` refuses, measured,
and how close the paged programs come to the plain reference in LOGITS, at
the low AND the high end of the cell's prompts.

    python benchmarks/kanana_check_controls.py [--pairs 2] [--seed N]
        [--controls sound no_routed_scaling ...] [--init K=V,K=V ...]
        [--logits [--steps 767 --every 64]] [--toy]

**The controls** (default). N pairs of check prompts (12,290 and 12,291
tokens, 8 tokens each, as ``perfbench/runners/serve.py`` ``check`` draws
them) are served by the program at the cell's sizes and held by
``correctness.check_served`` (the comparison ``run.py`` makes: 4 bf16 steps)
to the plain reference: once served by the sound program, and once by the
program with ONE fault planted (a new engine each; ``float8_reference``
alone is planted in the reference). The loop, ``--init`` and the command line are
``longcat_check_controls.py``'s. The faults:

* ``no_routed_scaling``: the six weights without ``routed_scaling_factor``;
  ``no_norm_topk``: not divided by their sum; ``bias_weighs``: the selection
  bias weighs as well as chooses (``(s + b) / sum(s + b)``);
* ``no_shared_expert``: the MoE branch without the shared experts;
  ``lead_skipped``: the stack without its leading dense layer;
* ``no_kv_norm``: the latent without ``kv_a_layernorm``;
  ``half_split_rope``: the rope's pairs ``(i, i + 32)`` in place of the
  published de-interleaved ones; ``scale_of_nope``: scores over
  ``sqrt(128)`` in place of ``sqrt(192)``;
* ``bf16_router``, ``bf16_norms``: the nearest precision below the one the
  configuration states for the router and the norms (float32), planted
  where it can be outside a kernel. Whether the served check SEES them is a
  reading to write down; the logits control below has to refuse them;
* ``float8_reference``: the nearest precision below the stated bf16,
  planted in the reference: the sound program's tokens checked against the
  reference COMPUTED IN float8 (e4m3: its matrices but the router's, and its
  activations, rounded through it; the reference's ``round_to``). This is
  the reading ``correct`` has to come out false on.

**The logits** (``--logits``). Three requests of the cell's own traffic, the
two the check takes at the LOW end (12,290 and 12,291 tokens) and ONE AT THE
HIGH END (20,480 tokens: the widest prefill bucket), are prefilled into a
pool of the cell's sizes and decoded together through
``forward_paged_prefill`` / ``forward_paged_decode`` (greedy, the program's
own picks) for ``--steps`` steps (767: the high row ends in the 166th block
of its table); their logits at the prefill's last position, at every
``--every``-th decode step and at the last 8 are held to the reference's
full forward over the same tokens, POSITION BY POSITION, to
``trinity_check_controls.LOGIT_TOL`` (0.02 of the largest logit by the
largest difference). Under bf16 activations one of seven routers over 128
experts takes another sixth expert than the reference's at a quarter of the
positions, and one such choice moves the logits by 3-14%, so the reference
runs a second time with
THE PROGRAM'S OWN six experts at the kept positions of every MoE layer (the
weights stay the reference's float32 scores of them): that one forward a row
decides every position, with no excuse left. The program's choices, and
what shows a precision, come from WITNESSES traced into the paged programs
(ordered host callbacks; the products, kernels and pools are the served
ones, though XLA may fuse round a witnessed value otherwise): at the kept
rows each router's input, choice and scores, and each RMSNorm's input, scale
and output. The router's scores are held to ``sigmoid(m Wr)`` recomputed in
float64 from what the router itself read (``ROUTER_TOL``), the norms'
outputs to their float64 value in bf16 steps of it (``NORM_TOL_STEPS``): a
float32 computation rounded once passes both, a bf16 one neither.
``isfinite`` is asked of every kept logit. With ``--controls`` naming a
precision control the PROGRAM runs under it (``bf16_router``,
``bf16_norms``) and the same comparison has to come out NOT ok;
``float8_reference`` holds the sound program's logits to the reference
computed in float8.

TPU only unless ``--toy`` (the rehearsal configuration on the CPU, bf16 as
served: to debug the script, proves nothing about the chip).
"""

import contextlib
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "perfbench"),
                os.path.join(ROOT, "benchmarks")]

# the loop, ``--init`` and the command line are the LongCat tool's; the
# limit the Trinity tool's
import longcat_check_controls as shared  # noqa: E402
import trinity_check_controls as trinity  # noqa: E402

PRECISIONS = ("bf16_router", "bf16_norms", "float8_reference")
CONTROLS = ("sound", "no_routed_scaling", "no_norm_topk", "bias_weighs",
            "no_shared_expert", "lead_skipped", "no_kv_norm",
            "half_split_rope", "scale_of_nope", *PRECISIONS)
TRAFFIC = "closed_longdoc_16k"
#: the program's router scores against sigmoid(m Wr) in float64 of the input
#: the router read. Between its two readings on the chip (PERF.md section 6,
#: PR 61): the float32 router and the same router rounded to bf16
ROUTER_TOL = 1e-4
#: an RMSNorm's output against its float64 value, in bf16 steps of that
#: value: float32 arithmetic rounded once is within half a step, bf16
#: arithmetic rounds six times on the way
NORM_TOL_STEPS = 0.75

#: control -> (overrides of the sound TransformerConfig ``c``, of its
#: MoEConfig)
FAULTS = {
    "no_routed_scaling": lambda c: ({}, {"routed_scaling_factor": 1.0}),
    "no_norm_topk": lambda c: ({}, {"norm_topk_prob": False}),
    "half_split_rope": lambda c: ({"rope_interleaved": False}, {}),
    "scale_of_nope": lambda c: (
        {"attn_scale": c.qk_nope_head_dim ** -0.5}, {}),
    "lead_skipped": lambda c: (
        {"lead_kinds": (), "n_layer": c.n_layer - len(c.lead_kinds)}, {}),
}


def build(config, init, control):
    """(the sound model under the trial's init, the model with ``control``
    planted)."""
    import jax.numpy as jnp

    from build_model import build_model
    from deepspeed_tpu.models.moe_lm import MoECausalLM
    over, moe = shared.trial_init(init)
    sound = build_model(config["preset"], **over, **({"moe": moe} if moe else {}))
    cfg, mcfg = sound.config, sound.moe
    c_over, m_over = FAULTS.get(control, lambda c: ({}, {}))(cfg)
    model = MoECausalLM(dataclasses.replace(cfg, **c_over),
                        dataclasses.replace(mcfg, **m_over))
    if control == "no_shared_expert":
        # the tree keeps its ``shared`` leaves; the MLP does not read them
        model._nodrop_mlp = MoECausalLM(cfg, dataclasses.replace(
            mcfg, shared_expert_d_ff=0))._nodrop_mlp
    if control == "bias_weighs":
        route = model._route

        def weighed(lp, tokens):
            _, e, probs, zero = route(lp, tokens)
            sb = jnp.take_along_axis(probs, e, axis=1) \
                + lp["b_select"].astype(jnp.float32)[e]
            w = sb / (jnp.sum(sb, -1, keepdims=True) + 1e-20)
            return w * mcfg.routed_scaling_factor, e, probs, zero
        model._route = weighed
    return sound, model


@contextlib.contextmanager
def planted(control):
    """``no_kv_norm`` (the latent module's one RMSNorm, ``kv_a_layernorm``
    where the query is direct) and the LongCat tool's precision controls:
    functions of the program's modules replaced while an engine traces."""
    from deepspeed_tpu.models import latent_attention as LA
    if control != "no_kv_norm":
        with shared.planted(control):
            yield
        return
    kept = LA._rms
    LA._rms = lambda x, p, eps: x
    try:
        yield
    finally:
        LA._rms = kept


def float8(control, cfg):
    """The reference's configuration a control is checked under:
    ``float8_reference`` computes it in float8 (e4m3), matrices and
    activations (the reference's ``round_to``), the nearest precision below
    the stated bf16; the router stays float32."""
    return {**cfg, "round_to": "float8_e4m3fn"} \
        if control == "float8_reference" else cfg


def controls(args, config, name_map, name):
    shared.controls(args, config, name_map, name, traffic=TRAFFIC,
                    build=build, planted=planted, cfg_of=float8)


@contextlib.contextmanager
def witnessed(model, rows, live, prefill_row, seen):
    """The program's routers and RMSNorms while the block traces and runs:
    of every call, the kept rows of what it read and gave appended to
    ``seen`` in program order (ordered host callbacks), as ``("router", m,
    experts, scores)`` or ``("norm", x, scale, eps, out)`` in float64. The
    kept rows: the first ``live`` of a decode step's ``rows``; of a prefill's
    padded prompt the row ``prefill_row[0]`` (static: one trace a prompt); a
    call on one row (the prefill's final norm), that row. Entered INSIDE
    ``planted``: what is witnessed is the function as planted."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models import latent_attention as LA
    from deepspeed_tpu.models import transformer as T

    def kept(a):
        flat = a.reshape(-1, a.shape[-1])
        n = flat.shape[0]
        return flat[:live] if n == rows else flat if n == 1 \
            else flat[prefill_row[0]:prefill_row[0] + 1]

    def note(kind):
        return lambda *a: seen.append(
            (kind, *(np.asarray(x).astype(np.float64) for x in a)))

    route, norm, rms = model._route, T._norm, LA._rms

    def route_w(lp, tokens):
        out = route(lp, tokens)
        jax.debug.callback(note("router"), kept(tokens), kept(out[1]),
                           kept(out[2]), ordered=True)
        return out

    def norm_w(cfg, x, p):
        out = norm(cfg, x, p)
        jax.debug.callback(note("norm"), kept(x.astype(jnp.float32)),
                           p["scale"], cfg.norm_eps, kept(out), ordered=True)
        return out

    def rms_w(x, p, eps):
        out = rms(x, p, eps)
        jax.debug.callback(note("norm"), kept(x.astype(jnp.float32)),
                           p["scale"], eps, kept(out), ordered=True)
        return out

    model._route, T._norm, LA._rms = route_w, norm_w, rms_w
    try:
        yield
    finally:
        del model._route                # the instance's: the class's is back
        T._norm, LA._rms = norm, rms


def _digest(seen, gates):
    """One program run's witnesses (``seen`` is emptied) -> (the routers'
    choices [MoE layers, kept rows, K], their scores [.., E], the scores'
    largest distance from ``sigmoid(m Wr)`` in float64 of the input the
    router read, the norms' largest distance from their float64 value in
    bf16 steps of it)."""
    import jax
    import numpy as np
    jax.effects_barrier()
    routers = [r[1:] for r in seen if r[0] == "router"]
    norms = [r[1:] for r in seen if r[0] == "norm"]
    seen.clear()
    assert len(routers) == len(gates), (len(routers), len(gates))
    score_err = max(
        float(np.abs(s - 1.0 / (1.0 + np.exp(-(m @ g)))).max())
        for (m, _, s), g in zip(routers, gates))
    norm_steps = 0.0
    for x, scale, eps, out in norms:
        want = x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * scale
        step = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(want), 1e-30))) - 7)
        norm_steps = max(norm_steps, float((np.abs(out - want) / step).max()))
    return (np.stack([e for _, e, _ in routers]).astype(np.int32),
            np.stack([s for _, _, s in routers]), score_err, norm_steps)


def _paged_logits(model, params, serve, prompts, steps, keep, gates):
    """The paged programs on ``prompts`` together, witnessed: the kept
    logits [rows, 1 + len(keep), V], the tokens picked [rows, steps + 1],
    the routers' choices [rows, MoE layers, 1 + len(keep), K] and scores
    [.., E] at the kept positions, and ``_digest``'s two distances over all
    of them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.engine import InferenceEngine
    mcfg = model.config
    bs, nb = int(serve["block_size"]), int(serve["max_num_blocks"])
    W, rows = int(serve["max_running"]), len(prompts)
    per_row = (nb - 1) // W
    tables = np.zeros((W, per_row), np.int32)
    tables[:rows] = np.stack([1 + r * per_row + np.arange(per_row)
                              for r in range(rows)])
    pools = model.init_paged_cache(nb, bs, jnp.bfloat16)
    toks = np.zeros((W, steps + 1), np.int32)
    kept = np.zeros((rows, 1 + len(keep), mcfg.vocab_size), np.float32)
    took, scores = [[] for _ in prompts], [[] for _ in prompts]
    worst = np.zeros(2)
    seen, prefill_row = [], [0]

    def digest():
        e, sc, *errs = _digest(seen, gates)
        worst[:] = np.maximum(worst, errs)
        return e, sc

    with witnessed(model, W, rows, prefill_row, seen):
        for r, p in enumerate(prompts):
            Tb = InferenceEngine._bucket(len(p), mcfg.max_seq)
            padded = np.zeros((1, Tb), np.int32)
            padded[0, :len(p)] = p
            slots = InferenceEngine._flat_slots(tables[r], 0, len(p), Tb,
                                                bs).astype(np.int32)
            prefill_row[0] = len(p) - 1
            # a trace a prompt, the witnessed row static in it: a function
            # of its own, since jit finds an equal bound method's trace again
            lg, pools = jax.jit(lambda *a: model.forward_paged_prefill(*a),
                                donate_argnums=(2,))(
                params, padded, pools, slots, np.int32(len(p) - 1))
            kept[r, 0] = np.asarray(lg[0], np.float32)
            toks[r, 0] = kept[r, 0].argmax()
            e, sc = digest()
            took[r].append(e[:, 0])
            scores[r].append(sc[:, 0])
        decode = jax.jit(lambda *a: model.forward_paged_decode(*a),
                         donate_argnums=(2,))
        pos = np.zeros((W,), np.int32)
        pos[:rows] = [len(p) for p in prompts]
        live = (np.arange(W) < rows).astype(np.int32)
        for s in range(steps):
            lg, pools, _ = decode(params, toks[:, s:s + 1], pools, tables,
                                  pos + s * live)
            toks[:, s + 1] = np.asarray(jnp.argmax(lg, axis=-1))
            if s not in keep:
                jax.effects_barrier()
                seen.clear()
                continue
            kept[:, 1 + keep.index(s)] = np.asarray(lg[:rows], np.float32)
            e, sc = digest()
            for r in range(rows):
                took[r].append(e[:, r])
                scores[r].append(sc[:, r])
    del pools
    return (kept, toks[:rows], np.stack([np.stack(t, 1) for t in took]),
            np.stack([np.stack(t, 1) for t in scores]), *worst)


@contextlib.contextmanager
def watched_router(ref, at, seen, took=None):
    """The reference's router while the block runs: its scores [len(at), E]
    and its own choice [len(at), K] at the sequence positions ``at``
    appended to ``seen`` a MoE layer, and with ``took`` [MoE layers,
    len(at), K] THOSE experts taken there in place of its own (weighed by
    the reference's scores of them)."""
    import numpy as np
    route_ = ref._route

    def route(cfg, w, m):
        sc, biased = ref.scores(w, m)
        top = ref.choose(cfg, biased)
        call = len(seen)
        seen.append((np.asarray(sc[0, at]), np.asarray(top[0, at])))
        if took is not None:
            top = top.at[0, at].set(took[call])
        return ref.weigh(cfg, sc, top)
    ref._route = route
    try:
        yield
    finally:
        ref._route = route_


def logits(args, config, name_map, name):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import correctness
    import traffic as traffic_mod
    from weights import make_params

    tol = trinity.LOGIT_TOL
    cfg = correctness.reference_config(config, name_map)
    ref = correctness.load_reference(name_map)
    serve = config["assumed"]["serve"]
    sound, _ = build(config, args.init[0], "sound")
    mcfg = sound.config
    (lo, hi), = traffic_mod.ServeTraffic(
        traffic_mod.load(TRAFFIC), mcfg.vocab_size, args.seed,
        config.get("length_scale", 1.0)).prompt_bounds()
    lens = [lo + 1, lo + 2, hi]
    steps = min(args.steps, mcfg.max_seq - hi - 1)
    rng = np.random.default_rng([args.seed, 13])
    prompts = [rng.integers(0, mcfg.vocab_size, size=n).astype(np.int32)
               for n in lens]
    keep = sorted({s for s in range(steps) if s % args.every == 0}
                  | set(range(max(steps - 8, 0), steps)))
    params = shared.trial_params(args.init[0], make_params(
        sound, args.seed, jnp.bfloat16, jax.devices()[:1]))
    weights = ref.Weights(params, name_map)
    gates = [np.asarray(weights.layer(l)["router"], np.float64)
             for l in range(cfg["n_dense_layer"], cfg["n_layer"])]
    names = [c for c in args.controls if c == "sound" or c in PRECISIONS] \
        if set(args.controls) != set(CONTROLS) else ["sound"]
    verdicts = {}

    def reference(seqs, took=None, cfg=cfg):
        """The reference's logits at the kept positions [rows, kept, V], and
        there its routers' scores [rows, MoE layers, kept, E] and own
        choices [.., K]; ``took``: the choices it is made to take there."""
        out, sc, own = [], [], []
        for r, (n, seq) in enumerate(zip(lens, seqs)):
            at = np.array([n - 1] + [n + s for s in keep])
            seen = []
            with watched_router(ref, at, seen,
                                None if took is None else took[r]):
                h = ref.final_hidden(cfg, weights, jnp.asarray(seq[None]))
            out.append(np.asarray(
                ref.logits_rows(cfg, weights, h[0, at]), np.float32))
            sc.append(np.stack([s for s, _ in seen]))
            own.append(np.stack([t for _, t in seen]))
        return np.stack(out), np.stack(sc), np.stack(own)

    def over_top(got, want):
        return np.abs(got - want).max(-1) / np.abs(want).max(-1)

    for control in names:
        if control == "float8_reference":
            continue                    # read beside the sound program, below
        t0 = time.perf_counter()
        _, model = build(config, args.init[0], control)
        with planted(control):
            kept, toks, took, scores, score_err, norm_steps = _paged_logits(
                model, params, serve, prompts, steps, keep, gates)
        jax.clear_caches()
        print(f"[logits] {name} {control}: prompts {lens}, {steps} decode "
              f"steps, {len(keep)} kept, last position "
              f"{[n + steps for n in lens]} of {mcfg.max_seq}: "
              f"{time.perf_counter() - t0:.1f}s", flush=True)
        finite = bool(np.isfinite(kept).all())
        if not finite:
            bad = {r: [int(i) for i in
                       np.flatnonzero(~np.isfinite(kept[r]).all(-1))]
                   for r in range(len(lens))}
            print(f"[logits] NOT FINITE, by row, kept positions (0: the "
                  f"prefill): {bad}", flush=True)
        seqs = [np.concatenate([p, t[:-1]]) for p, t in zip(prompts, toks)]
        own_logits, own_scores, own_took = reference(seqs)
        forced_logits, _, _ = reference(seqs, took)
        own = over_top(kept, own_logits)                        # [rows, kept]
        err = over_top(kept, forced_logits)
        # MoE layers of a kept position whose six are not the reference's
        differ = (np.sort(took, -1) != np.sort(own_took, -1)).any(-1).sum(1)
        at = [[n - 1] + [n + s for s in keep] for n in lens]
        for r, i in np.argwhere(~(err <= tol)):
            print("[logits] over the limit under the program's own choices: "
                  + json.dumps({"row": int(r), "position": int(at[r][i]),
                                "over_top": float(err[r, i]),
                                "over_top_under_the_references": float(own[r, i]),
                                "layers_that_differ": int(differ[r, i])}),
                  flush=True)
        ok = bool(finite and (err <= tol).all() and score_err <= ROUTER_TOL
                  and norm_steps <= NORM_TOL_STEPS)
        verdicts[control] = ok
        print(json.dumps({
            "config": name, "control": control, "prompts": lens,
            "decode_steps": steps, "kept_decode_steps": len(keep),
            "all_finite": finite,
            "largest_logit": round(float(np.abs(own_logits).max()), 4),
            "logit_std": round(float(own_logits.std()), 5), "tol": tol,
            "positions": int(err.size),
            "under_the_programs_own_choices": {
                "positions_over_tol": int((~(err <= tol)).sum()),
                "largest_over_top": float(err.max()),
                "by_row_prefill_over_top": [float(x) for x in err[:, 0]],
                "by_row_decode_max_over_top": [float(x)
                                               for x in err[:, 1:].max(-1)],
                "by_row_decode_median_over_top": [
                    float(x) for x in np.median(err[:, 1:], axis=-1)]},
            "under_the_references_choices": {
                "positions_over_tol": int((~(own <= tol)).sum()),
                "largest_over_top": float(own.max()),
                "positions_where_a_choice_differs": int((differ > 0).sum()),
                "of_them_over_tol": int(((differ > 0) & ~(own <= tol)).sum()),
                "choices_that_differ": int(differ.sum()),
                "router_scores_from_the_references_max": float(
                    np.abs(scores - own_scores).max())},
            "router_scores_from_float64_of_their_input_max": score_err,
            "router_tol": ROUTER_TOL,
            "norm_outputs_from_float64_max_bf16_steps": norm_steps,
            "norm_tol_steps": NORM_TOL_STEPS, "ok": ok,
            "argmax_share": float(
                (kept.argmax(-1) == forced_logits.argmax(-1)).mean()),
            "seconds": round(time.perf_counter() - t0, 1)}), flush=True)
        if control == "sound" and "float8_reference" in names:
            # the sound program's logits against the reference in float8
            # (its own choices: the least over the positions says how far
            # the limit is from that precision, flips or none)
            t0 = time.perf_counter()
            low, _, _ = reference(seqs, cfg=float8("float8_reference", cfg))
            low_err = over_top(kept, low)
            verdicts["float8_reference"] = bool((low_err <= tol).all())
            print(json.dumps({
                "config": name, "control": "float8_reference", "tol": tol,
                "least_over_top": float(low_err.min()),
                "median_over_top": float(np.median(low_err)),
                "largest_over_top": float(low_err.max()),
                "positions_within_tol": int((low_err <= tol).sum()),
                "positions": int(low_err.size),
                "ok": verdicts["float8_reference"],
                "seconds": round(time.perf_counter() - t0, 1)}), flush=True)
    # the sound program passes; a precision below the stated one does not
    bad = [c for c, ok in verdicts.items() if ok != (c == "sound")]
    if bad:
        print(f"[logits] not as they have to be: {bad}", flush=True)
    return 1 if bad else 0


def _more_args(ap):
    ap.add_argument("--steps", type=int, default=767)
    ap.add_argument("--every", type=int, default=64)


if __name__ == "__main__":
    sys.exit(shared.main(
        "kanana_check_controls",
        ("kanana-2-30b-a3b-instruct-2601", "rehearsal-deepseek-v3-tiny"),
        CONTROLS, 6100000101, controls, logits, _more_args))
