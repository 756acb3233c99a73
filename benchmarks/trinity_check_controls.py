"""What the served check of ``trinitylarge_serve_shortlong`` refuses, measured,
and how close the paged programs come to the plain reference in LOGITS.

    python benchmarks/trinity_check_controls.py [--pairs 6] [--seed N]
        [--controls sound no_post_norm ...] [--init K=V,K=V ...]
        [--logits [--steps 136 --every 16]] [--toy]

**The controls** (default). N pairs of check prompts (257 and 4,609 tokens, 8
tokens each, as ``perfbench/runners/serve.py`` ``check`` draws them: one row
under a ring, one whose ring has wrapped) are served TOGETHER by the program
at the cell's sizes and held by ``correctness.check_served`` (the comparison
``run.py`` makes: 4 bf16 steps) to the plain reference as it is (``sound``:
must read 0 refused) and to the reference with ONE fault planted in it (the
program's tokens are then those of a program that differs from the reference
by that fault; the tool patches the reference's module while it runs, the
file is never touched):

* ``no_post_norm``: both norms on a branch's way out left out;
* ``no_gate``: attention without its sigmoid gate (the gate's matrix zero: a
  constant gate is a scale, which the post-norm takes out);
* ``rope_on_full``: rope on the full layer too;
* ``no_window``: every window layer sees every key;
* ``no_shared_expert``: the MoE branch without the shared expert;
* ``no_route_scale``: the four weights without ``route_scale``;
* ``float8``: every matrix rounded through float8 (e4m3), the nearest
  precision below the stated bf16 (a reading, not a fault to refuse).

One fault is planted in the PROGRAM, a second engine: ``stale_table``, a
decode step whose window tables lack their newest entry (the host's table
one block behind: the step writes to and reads the dummy block there).

``--init`` tries another seeded init than the preset's (``init_std``,
``embed_init_std``, ``router_init_scale``, ``expert_bias_std``), one engine
after the other: a trial, not the cell's.

**The logits** (``--logits``). Four requests, two of each class of the cell's
traffic, are prefilled into pools of the cell's sizes and decoded together
through ``forward_paged_prefill`` / ``forward_paged_decode`` (greedy, the
program's own picks) for ``--steps`` steps, their blocks handed out by a
``BlockAllocator`` as the engine's are (window tables from the host: every
row takes a block in the window, the short rows below a whole ring); their
logits at the prefill's last position, at every ``--every``-th decode step
and at the last 8 are held to the reference's full forward over the same
tokens, POSITION BY POSITION, and, as the reading that has to FAIL, to the
same reference with its matrices in float8. A position over ``LOGIT_TOL``
passes only where the tool SHOWS a flipped boundary choice of a router
there: the reference run again with that position's K-th expert of ONE MoE
layer given up for its K+1-th (the layers tried by their margin, the
narrowest first) has to hold the program's logits inside the same limit;
the layer, the margin and both experts are printed. ``LOGIT_TOL`` is written
below with its readings.

TPU only unless ``--toy`` (the rehearsal configuration on the CPU, bf16 as
served: to debug the script, proves nothing about the chip).
"""

import contextlib
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "perfbench"),
                os.path.join(ROOT, "benchmarks")]

# ``--init`` and the command line are the LongCat tool's
import longcat_check_controls as shared  # noqa: E402

REFERENCE_FAULTS = ("no_post_norm", "no_gate", "rope_on_full", "no_window",
                    "no_shared_expert", "no_route_scale", "float8")
CONTROLS = ("sound", *REFERENCE_FAULTS, "stale_table")
TRAFFIC = "closed_shortlong_6k"
#: what ``--logits`` accepts, a kept position: the largest difference of
#: program - reference over the position's logits, over the largest of the
#: reference's there. bf16 weights, activations and KV against float32; the
#: limit lies between the two readings the tool prints (the program's largest
#: over the positions without a shown flip, the float8 reference's LEAST over
#: all positions: PERF.md section 6, PR 56). A position over it has to be a
#: router's flipped boundary choice and is held, under the same limit, to the
#: reference with that one choice flipped (``_flips``); an average over
#: positions is printed and not judged (it hides a one-position fault, which
#: is the shape a stale window table has)
LOGIT_TOL = 0.02
#: positions over the limit examined for a flip (each is up to one forward of
#: the reference a MoE layer); more than that of 68 is no handful of flips
FLIPS_EXAMINED = 8


def _float8(a):
    import jax.numpy as jnp
    return a.astype(jnp.float8_e4m3fn).astype(a.dtype) if a.ndim >= 2 else a


@contextlib.contextmanager
def faulty_reference(ref, fault):
    """``ref`` (the reference's module) with ``fault`` planted while the
    block runs (None: as it is); yields what turns the sound configuration
    into the fault's."""
    import jax
    import jax.numpy as jnp

    mixer_, layer_, top_, take_ = (ref._mixer, ref.Weights.layer,
                                   ref.Weights.top, ref._take)
    patches, cfg_of = [], (lambda c: c)

    def layers(edit):
        return (ref.Weights, "layer", lambda self, l: edit(layer_(self, l)))

    if fault == "no_post_norm":
        def mixer(cfg, w, x, window, rope):
            y = ref.gated_attention(cfg, w, ref._rms(x, w["ln1_g"], cfg["eps"]),
                                    window, rope)
            return x + y, ref._rms(x + y, w["ln2_g"], cfg["eps"])
        patches = [(ref, "_mixer", jax.jit(mixer, static_argnums=0)),
                   (ref, "_join", jax.jit(lambda cfg, h, f, g: h + f,
                                          static_argnums=0))]
    elif fault == "rope_on_full":
        patches = [(ref, "_mixer", lambda cfg, w, x, window, rope: mixer_(
            cfg, w, x, window, True))]
    elif fault == "no_window":
        patches = [(ref, "_mixer", lambda cfg, w, x, window, rope: mixer_(
            cfg, w, x, 0, rope))]
    elif fault == "no_gate":
        patches = [layers(lambda w: {
            **w, "w_gate_attn": jnp.zeros_like(w["w_gate_attn"])})]
    elif fault == "no_shared_expert":
        patches = [layers(lambda w: {
            **w, **({"shared_down": jnp.zeros_like(w["shared_down"])}
                    if "shared_down" in w else {})})]
    elif fault == "no_route_scale":
        cfg_of = lambda c: {**c, "route_scale": 1.0}  # noqa: E731
    elif fault == "float8":
        patches = [
            (ref, "_take", lambda stack, row, e: _float8(take_(stack, row, e))),
            layers(lambda w: {k: v if isinstance(v, ref._Experts)
                              else _float8(v) for k, v in w.items()}),
            (ref.Weights, "top", lambda self: {
                k: _float8(v) for k, v in top_(self).items()})]
    kept = [(obj, name, getattr(obj, name)) for obj, name, _ in patches]
    try:
        for obj, name, fn in patches:
            setattr(obj, name, fn)
        yield cfg_of
    finally:
        for obj, name, fn in kept:
            setattr(obj, name, fn)


@contextlib.contextmanager
def planted(control):
    """``stale_table`` in the program: a decode step's window tables lack
    the entry of each row's newest block."""
    from deepspeed_tpu.models import transformer as T
    decode = T.forward_paged_decode
    if control == "stale_table":
        import jax.numpy as jnp

        def stale(cfg, params, tokens, pools, block_tables, pos, *a,
                  window_tables=None, **kw):
            R, bs = window_tables.shape[1], pools["wk"].shape[2]
            newest = jnp.arange(R)[None, :] == ((pos // bs) % R)[:, None]
            return decode(cfg, params, tokens, pools, block_tables, pos, *a,
                          window_tables=jnp.where(newest, 0, window_tables),
                          **kw)
        T.forward_paged_decode = stale
    try:
        yield
    finally:
        T.forward_paged_decode = decode


def _check_lengths(spec, vocab, seed, scale):
    import traffic as traffic_mod
    bounds = traffic_mod.ServeTraffic(spec, vocab, seed, scale).prompt_bounds()
    return (min(bounds)[0] + 1, max(bounds)[0] + 1)


def _serve(model, params, serve, prompts, want, control):
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.inference.serve import AsyncServingEngine
    with planted(control):
        engine = deepspeed_tpu.init_inference(
            model, dtype="bf16", params=params,
            serving={"block_size": int(serve["block_size"]),
                     "max_running": int(serve["max_running"]),
                     "max_num_blocks": int(serve["max_num_blocks"])})
        serving = AsyncServingEngine(engine, max_new_tokens=model.config.max_seq)
        handles = [serving.add_request(p, max_new_tokens=want) for p in prompts]
        served = [[t for burst in h.stream(timeout=1100) for t in burst]
                  for h in handles]
        serving.shutdown(drain=False, timeout=120)
    assert all(len(s) == want for s in served), [len(s) for s in served]
    del engine, serving, handles
    gc.collect()
    jax.clear_caches()
    return served


def controls(args, config, name_map, name):
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
    spec = traffic_mod.load(TRAFFIC)
    want = int(spec["check"]["tokens"])
    for trial in args.init:
        over, moe = shared.trial_init(trial)
        model = build_model(config["preset"], **over,
                            **({"moe": moe} if moe else {}))
        mcfg = model.config
        lens = tuple(args.lengths or _check_lengths(
            spec, mcfg.vocab_size, args.seed, config.get("length_scale", 1.0)))
        prompts = [np.random.default_rng([args.seed, 11, i]).integers(
            0, mcfg.vocab_size, size=n).astype(np.int32)
            for i in range(args.pairs) for n in lens]
        params = shared.trial_params(trial, make_params(
            model, args.seed, jnp.bfloat16, jax.devices()[:1]))
        weights = ref.Weights(params, name_map)
        served = {}
        for control in args.controls:
            t0 = time.perf_counter()
            in_program = control not in REFERENCE_FAULTS
            key = control if in_program else "sound"
            if key not in served:
                served[key] = _serve(model, params, serve, prompts, want, key)
            served_s = time.perf_counter() - t0
            with faulty_reference(ref, None if in_program else control) \
                    as cfg_of:
                got = [correctness.check_served(cfg_of(cfg), weights, p, s)
                       for p, s in zip(prompts, served[key])]
            gaps = np.array([g["worst_gap_bf16_steps"] for g in got])
            by_len = {int(n): int(sum(not g["ok"] for g in got
                                      if g["prompt_tokens"] == n))
                      for n in lens}
            print(json.dumps({
                "config": name, "init": trial or "preset",
                "init_std": mcfg.init_std, "embed_init_std": mcfg.embed_init_std,
                "control": control,
                "planted_in": "program" if in_program else "reference",
                "prompts": len(prompts), "lengths": lens,
                "prompts_refused": int(sum(not g["ok"] for g in got)),
                "refused_by_length": by_len,
                "worst_gap_bf16_steps": round(float(gaps.max()), 3),
                "median_gap_bf16_steps": round(float(np.median(gaps)), 3),
                "argmax_share": round(float(np.mean(
                    [g["argmax_matches"] / want for g in got])), 3),
                "readings_over_0": sorted(round(float(g), 2)
                                          for g in gaps if g > 0),
                "served_s": round(served_s, 1),
                "reference_s": round(time.perf_counter() - t0 - served_s, 1)}),
                flush=True)
        del weights, params
        gc.collect()


@contextlib.contextmanager
def watched_router(ref, at, seen, flip=None):
    """The reference's router while the block runs, with what it chose at
    sequence position ``at`` appended to ``seen`` a MoE layer ((margin
    between the K-th and the K+1-th of s + b, the K-th expert, the
    K+1-th)) and, in the MoE layer number ``flip``, the K+1-th taken there
    for the K-th."""
    import jax

    route_ = ref._route

    def route(cfg, w, m):
        K = cfg["experts_per_token"]
        sc, biased = ref.scores(w, m)
        val, ranked = jax.lax.top_k(biased, K + 1)
        call = len(seen)
        seen.append((float(val[0, at, K - 1] - val[0, at, K]),
                     int(ranked[0, at, K - 1]), int(ranked[0, at, K])))
        top = ranked[..., :K]
        if call == flip:
            top = top.at[0, at, K - 1].set(ranked[0, at, K])
        return ref.weigh(cfg, sc, top)
    ref._route = route
    try:
        yield
    finally:
        ref._route = route_


def _flips(ref, cfg, weights, seq, at, got):
    """Whether the program's logits ``got`` [V] at position ``at`` of ``seq``
    are the reference's under ONE flipped boundary choice there: the MoE
    layers by their margin, the narrowest first; returns what was tried, the
    last of it the one that held (``over_top`` <= ``LOGIT_TOL``) if any
    did."""
    import jax.numpy as jnp
    import numpy as np

    def forward(flip):
        seen = []
        with watched_router(ref, at, seen, flip):
            h = ref.final_hidden(cfg, weights, jnp.asarray(seq[None]))
        return seen, np.asarray(ref.logits_rows(cfg, weights, h[0, at:at + 1]),
                                np.float32)[0]

    seen, _ = forward(None)
    tried = []
    for call in np.argsort([m for m, _, _ in seen]):
        _, want = forward(int(call))
        margin, kth, next_ = seen[call]
        tried.append({
            "layer": int(cfg["n_dense_layer"] + call),
            "margin": float(margin), "reference_took": kth,
            "flipped_to": next_,
            "over_top": float(np.abs(got - want).max() / np.abs(want).max())})
        if tried[-1]["over_top"] <= LOGIT_TOL:
            break
    return tried


def logits(args, config, name_map, name):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import correctness
    import traffic as traffic_mod
    from build_model import build_model
    from deepspeed_tpu.inference.block_allocator import BlockAllocator
    from deepspeed_tpu.inference.engine import InferenceEngine
    from weights import make_params

    cfg = correctness.reference_config(config, name_map)
    ref = correctness.load_reference(name_map)
    serve = config["assumed"]["serve"]
    bs, nb = int(serve["block_size"]), int(serve["max_num_blocks"])
    W = int(serve["max_running"])
    over, moe = shared.trial_init(args.init[0])
    model = build_model(config["preset"], **over, **({"moe": moe} if moe else {}))
    mcfg = model.config
    scale = config.get("length_scale", 1.0)
    spec = traffic_mod.load(TRAFFIC)
    rng = np.random.default_rng([args.seed, 13])
    # two requests a class, at the ends of the class's prompt range
    lens = [n for lo, hi in traffic_mod.ServeTraffic(
        spec, mcfg.vocab_size, args.seed, scale).prompt_bounds()
        for n in (lo + 3, hi - 5)]
    steps = min(args.steps, mcfg.max_seq - max(lens) - 1)
    rows = len(lens)
    prompts = [rng.integers(0, mcfg.vocab_size, size=n).astype(np.int32)
               for n in lens]
    keep = sorted({s for s in range(steps) if s % args.every == 0}
                  | set(range(max(steps - 8, 0), steps)))
    R = mcfg.ring_blocks(bs)
    alloc = BlockAllocator(
        nb, bs, window_blocks=BlockAllocator.window_pool_blocks(nb, W, R),
        ring_blocks=R)
    params = shared.trial_params(args.init[0], make_params(
        model, args.seed, jnp.bfloat16, jax.devices()[:1]))
    weights = ref.Weights(params, name_map)
    pools = model.init_paged_cache(nb, bs, jnp.bfloat16,
                                   window_blocks=alloc.window_blocks)
    prefill = jax.jit(model.forward_paged_prefill, donate_argnums=(2,))
    decode = jax.jit(model.forward_paged_decode, donate_argnums=(2,))
    n_max = mcfg.max_seq // bs
    held = [alloc.allocate(alloc.blocks_for_tokens(len(p))) for p in prompts]
    rings = [[] for _ in prompts]
    toks = np.zeros((W, steps + 1), np.int32)
    kept = np.zeros((rows, 1 + len(keep), mcfg.vocab_size), np.float32)
    t0 = time.perf_counter()
    for r, p in enumerate(prompts):
        alloc.grow_window(rings[r], len(held[r]))
        Tb = InferenceEngine._bucket(len(p), mcfg.max_seq)
        padded = np.zeros((1, Tb), np.int32)
        padded[0, :len(p)] = p
        table = np.asarray(held[r], np.int32)
        slots = InferenceEngine._flat_slots(table, 0, len(p), Tb,
                                            bs).astype(np.int32)
        wt = np.zeros((R,), np.int32)
        wt[:len(rings[r])] = rings[r]
        lg, pools = prefill(params, padded, pools, slots, np.int32(len(p) - 1),
                            window_table=wt)
        kept[r, 0] = np.asarray(lg[0], np.float32)
        toks[r, 0] = kept[r, 0].argmax()
    crossed, loads = 0, 0
    for s in range(steps):
        bt = np.zeros((W, n_max), np.int32)
        wt = np.zeros((W, R), np.int32)
        pos = np.zeros((W,), np.int32)
        for r, p in enumerate(prompts):
            pos[r] = len(p) + s
            if pos[r] >= len(held[r]) * bs:
                held[r] += alloc.allocate(1)
                before = len(rings[r])
                alloc.grow_window(rings[r], len(held[r]))
                crossed += len(rings[r]) > before
            bt[r, :len(held[r])] = held[r]
            wt[r, :len(rings[r])] = rings[r]
        lg, pools, counts = decode(params, toks[:, s:s + 1], pools, bt, pos,
                                   window_tables=wt)
        loads = loads + np.asarray(counts)          # [MoE layers, E + 1]
        toks[:, s + 1] = np.asarray(jnp.argmax(lg, axis=-1))
        if s in keep:
            kept[:, 1 + keep.index(s)] = np.asarray(lg[:rows], np.float32)
    del pools
    print(f"[logits] {name}: prompts {lens}, {steps} decode steps, "
          f"{len(keep)} kept, {crossed} window blocks handed out while "
          f"decoding, rings {[len(x) for x in rings]} of {R}: "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    # how even the seeded router is over the experts held here: rows x k x
    # held / router width assignments a layer are its share
    held_n = loads.shape[1] - 1
    print(f"[logits] assignments to the {held_n} held experts by MoE layer "
          f"(the router's even share: "
          f"{rows * steps * model.moe.k * held_n / model.router_width:.0f}): "
          f"{loads[:, :held_n].sum(1).tolist()}; busiest expert "
          f"{loads[:, :held_n].max(1).tolist()}; experts with none "
          f"{(loads[:, :held_n] == 0).sum(1).tolist()}", flush=True)
    bad = {r: [int(i) for i in np.flatnonzero(~np.isfinite(kept[r]).all(-1))]
           for r in range(rows) if not np.isfinite(kept[r]).all()}
    if bad:
        print(f"[logits] NOT FINITE, by row, kept positions (0: the "
              f"prefill): {bad}", flush=True)

    def reference(cfg_r, weights_r):
        out = []
        for r in range(rows):
            n = len(prompts[r])
            seq = np.concatenate([prompts[r], toks[r, :-1]])[None]
            h = ref.final_hidden(cfg_r, weights_r, jnp.asarray(seq))
            at = np.array([n - 1] + [n + s for s in keep])
            out.append(np.asarray(ref.logits_rows(cfg_r, weights_r, h[0, at]),
                                  np.float32))
        return np.stack(out)

    want = reference(cfg, weights)
    with faulty_reference(ref, "float8") as cfg_of:
        low = reference(cfg_of(cfg), weights)
    top = np.abs(want).max(-1)
    step = 2.0 ** (np.floor(np.log2(top)) - 7)

    def reading(a):
        d = np.abs(a - want)
        return {"prefill_max_over_top": float((d[:, 0].max(-1) / top[:, 0]).max()),
                "decode_max_over_top": float((d[:, 1:].max(-1) / top[:, 1:]).max()),
                "decode_min_over_top": float((d[:, 1:].max(-1) / top[:, 1:]).min()),
                "by_row_max_over_top": [round(float(x), 5) for x in
                                        (d.max(-1) / top).max(-1)],
                "decode_max_bf16_steps": float((d[:, 1:].max(-1) / step[:, 1:]).max()),
                "by_row_rms_over_std": [
                    round(float(np.sqrt((d[r] ** 2).mean()) / want[r].std()), 5)
                    for r in range(rows)]}

    got, f8 = reading(kept), reading(low)
    # position by position; one over the limit has to be a shown flip
    err = np.abs(kept - want).max(-1) / top                 # [rows, kept]
    f8_least = float((np.abs(low - want).max(-1) / top).min())
    flips, unexplained = [], 0
    for r, i in np.argwhere(err > LOGIT_TOL)[:FLIPS_EXAMINED]:
        n = len(prompts[r])
        at = n - 1 if i == 0 else n + keep[i - 1]
        tried = _flips(ref, cfg, weights,
                       np.concatenate([prompts[r], toks[r, :-1]]), at,
                       kept[r, i])
        shown = tried[-1]["over_top"] <= LOGIT_TOL
        unexplained += not shown
        flips.append({"row": int(r), "position": int(at),
                      "over_top": round(float(err[r, i]), 5),
                      "flip_shown": bool(shown), "tried": tried})
        print("[logits] over the limit: " + json.dumps(flips[-1]), flush=True)
    unexplained += max(int((err > LOGIT_TOL).sum()) - FLIPS_EXAMINED, 0)
    within = err[err <= LOGIT_TOL]
    ok = bool(unexplained == 0 and LOGIT_TOL < f8_least)
    print(json.dumps({
        "config": name, "prompts": lens, "decode_steps": steps,
        "kept_decode_steps": len(keep), "window_blocks_handed_out": crossed,
        "largest_logit": round(float(top.max()), 4),
        "logit_std": round(float(want.std()), 5),
        "program": got, "float8_reference": f8, "tol": LOGIT_TOL,
        "positions": int(err.size),
        "positions_over_tol": int((err > LOGIT_TOL).sum()),
        "flips_shown": int(sum(f["flip_shown"] for f in flips)),
        "unexplained": int(unexplained),
        "largest_over_top_without_a_flip": float(within.max())
        if within.size else None,
        "float8_least_over_top": f8_least, "ok": ok,
        "argmax_share": float((kept.argmax(-1) == want.argmax(-1)).mean()),
        "seconds": round(time.perf_counter() - t0, 1)}), flush=True)
    return 0 if ok else 1


def _more_args(ap):
    ap.add_argument("--steps", type=int, default=136)
    ap.add_argument("--every", type=int, default=16)


if __name__ == "__main__":
    shared.main("trinity_check_controls",
                ("trinity-large-preview", "rehearsal-trinity-tiny"), CONTROLS,
                5600000101, controls, logits, _more_args)
