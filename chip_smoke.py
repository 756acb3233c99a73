"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, jax touched once, no arguments::

    python chip_smoke.py

drives the two main paths through the entry points a user calls, at the
published width of GPT-2 125M (12L, d 768, 12 heads, hd 64, vocab 50,257 —
the default of ``dscli serve``), weights random from a seed:

1. *device guard* — jax is on a TPU whose ``device_kind`` has a published
   peak and the selected accelerator agrees; anything else exits non-zero
   within seconds and prints no result;
2. *train leg* — ``deepspeed_tpu.initialize`` in bf16 with ZeRO-1, AdamW,
   remat=dots and unrolled layers at global batch 32 x seq 1024: one warm-up step (its
   wall time is the compile seconds) and five more on one fixed batch; the
   loss is finite and falls; on one device the lowered step holds the flash
   forward/backward and the three fused-CE kernels as Mosaic
   ``tpu_custom_call``s;
3. *serve leg* — ``dscli serve``'s own ``serve_main`` on port 0, then
   ``POST /v1/completions`` with different prompt lengths, some concurrent
   (a fused decode batch wider than one forms) and one streamed; every
   answer is 200 / ``finish_reason: stop`` / exactly ``max_tokens`` ids,
   ``/healthz`` says ``restarts: 0``, the fault counters are zero, the
   decode program ran the paged Pallas kernel, and every served token is the
   argmax of an ``attention_backend="xla"`` reference up to bf16 rounding;
4. *state leg* — a stack with a recurrent state beside its KV pool (the
   ``solar_open2`` toy at a state width the KDA decode kernel tiles) through
   ``init_inference`` and ``generate_batch``: the decode program ran the
   Pallas state kernel (``kda_decode=kda_kernel``), and its tokens are those
   of the same weights on the plain-XLA forms;
5. *four-chip legs* (only with >= 4 devices visible, else ``not run``) —
   the train leg under ZeRO-3 on ``mesh {"fsdp": 4}`` and the serve leg with
   ``serving.tp = 4``, asserting from ``addressable_shards`` that params,
   optimizer state and KV pools are split four ways.

Progress lines as it goes, then one ``summary {...}`` line with the detail
(versions, compile cache, every leg); the LAST line of stdout is the JSON
object ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": n}}``
with exactly those keys, the device as jax reports it. Any failed check
raises: the exit code is non-zero and there is no result line.

``--dry-run`` (never reached by the no-argument call) runs the same code at
a tiny size on the CPU backend with the Pallas kernels interpreted, for
debugging in a sandbox with no chip; its summary says ``DRY RUN`` and
``platform=cpu``.
"""

import argparse
import collections
import concurrent.futures
import gc
import importlib.metadata
import json
import os
import re
import sys
import time
import urllib.request

# bf16 logits near |x| ~ 2.5 are 2**-6 apart; with random weights the top two
# of 50k logits are often that close, so "equal greedy ids" is asserted as
# "every served token is within four such steps of the reference's maximum"
REFERENCE_LOGIT_TOL = 4 * 2.0 ** -6


def say(msg):
    print(f"[chip_smoke +{time.perf_counter() - T0:6.1f}s] {msg}", flush=True)


# ----------------------------------------------------------------------- #
# sizes


def gpt2_model(dry_run, **over):
    """GPT-2 125M at its published width; the dry run keeps the family and
    the kernel envelope (hd 64, heads divisible by 4) at toy depth/width and
    forces the kernels on, so they run interpreted on the CPU backend."""
    if not dry_run:
        from deepspeed_tpu.models.presets import get_model
        return get_model("gpt2", "125m", **over)
    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.models.transformer import TransformerConfig
    over.setdefault("attention_backend", "flash")
    return CausalLM(TransformerConfig(
        vocab_size=500, max_seq=512, n_layer=2, n_head=4, d_model=256,
        pos_embedding="learned", norm="layernorm", activation="gelu",
        tie_embeddings=True, attn_bias=True, fused_cross_entropy="on",
        **over))


def sizes(dry_run):
    if dry_run:
        return dict(batch=4, seq=128, steps=5, loss_chunk=128,
                    prompt_lens=(5, 40, 130), max_tokens=4)
    return dict(batch=32, seq=1024, steps=5, loss_chunk=2048,
                prompt_lens=(5, 40, 130, 300), max_tokens=16)


def fresh_leg():
    """Process-wide state a leg reads back — dispatch records, the metrics
    registry, the compile watchdog, the global mesh — starts empty."""
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.monitor.metrics import get_registry
    from deepspeed_tpu.monitor.trace import get_compile_watchdog
    from deepspeed_tpu.ops import dispatch
    dispatch.reset()
    get_compile_watchdog().reset()
    get_registry().reset()
    dist.set_mesh(None)


# ----------------------------------------------------------------------- #
# what the devices hold


def assert_split(what, tree, ways, min_split_share):
    """From ``addressable_shards``: ``tree`` lives on ``ways`` devices, no
    device holds the whole, and at least ``min_split_share`` of its bytes
    sit in leaves split ``ways`` ways (the rest replicates by design: leaves
    under the ZeRO persistence threshold; under tp the norms, the position
    table, and the embedding when the vocab does not divide)."""
    import jax
    total = split = 0
    held = collections.Counter()
    for leaf in jax.tree.leaves(tree):
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            held[shard.device.id] += shard.data.nbytes
        if leaf.addressable_shards[0].data.nbytes * ways == leaf.nbytes:
            split += leaf.nbytes
    worst = max(held.values())
    assert len(held) == ways, f"{what}: on {len(held)} devices, not {ways}"
    assert worst < total, f"{what}: a device holds all {total} bytes"
    assert split >= min_split_share * total, (
        f"{what}: only {split} of {total} bytes are split {ways} ways")
    return {"total_bytes": total, "split_bytes": split,
            "max_bytes_on_one_device": worst}


def device_memory():
    """Per-device ``memory_stats()`` figures ({} where the backend has none,
    e.g. the CPU dry run)."""
    from deepspeed_tpu.accelerator import get_accelerator
    return get_accelerator().memory_report()


# ----------------------------------------------------------------------- #
# train leg


def train_leg(name, dry_run, n_dev, zero_stage, mesh_axes):
    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.ops import dispatch

    sz = sizes(dry_run)
    B, S, steps = sz["batch"], sz["seq"], sz["steps"]
    say(f"{name}: ZeRO-{zero_stage} mesh={mesh_axes} global batch {B} x "
        f"seq {S}")
    fresh_leg()
    model = gpt2_model(dry_run, remat="dots", loss_chunk=sz["loss_chunk"],
                       scan_layers=False)
    params = model.init_params(jax.random.key(0))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config={
            "train_micro_batch_size_per_gpu": B // n_dev,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "AdamW",
                          "params": {"lr": 6e-4, "weight_decay": 0.1}},
            "zero_optimization": {"stage": zero_stage},
            "bf16": {"enabled": True},
            "mesh": mesh_axes,
            "steps_per_print": 0,
            "telemetry": {"enabled": True},
        })
    del params
    vocab = model.config.vocab_size
    batch = {"input_ids": np.random.default_rng(0).integers(
        0, vocab, size=(B, S)).astype(np.int32)}

    t0 = time.perf_counter()
    first = float(jax.block_until_ready(engine.train_batch(batch)))
    compile_s = time.perf_counter() - t0
    say(f"{name}: warm-up step {compile_s:.1f}s (compile), loss {first:.4f}")
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = engine.train_batch(batch)
    last = float(jax.block_until_ready(loss))
    dt = time.perf_counter() - t0
    assert np.isfinite(first) and np.isfinite(last), (first, last)
    assert last < first, f"{name}: loss did not fall: {first} -> {last}"
    # one compile of the step at most (none when the persistent cache hit)
    compiles = engine.telemetry_snapshot()["compile"]["by_fn"]
    assert compiles.get("engine.train_batch[gas=1]", 0) <= 1, (
        f"{name}: recompiled after warm-up: {compiles}")

    forms = dispatch.selected()
    kernels = lowered_kernels(engine._train_batch_jit[1], engine.state,
                              {"input_ids": jax.ShapeDtypeStruct(
                                  (1, B, S), np.int32)}, engine._rng)
    assert dry_run or not any(k.endswith("=interpret") for k in forms), forms
    if n_dev == 1 and not dry_run:
        # one chip: the Mosaic kernels, not the einsum / loss_chunk forms
        for want in ("attention=flash", "vocab_head=fused_ce",
                     "fused_ce_fwd=lane_state",
                     "kernel/flash_attention=compiled",
                     "kernel/fused_cross_entropy=compiled"):
            assert want in forms, (want, forms)
        # 32 x 1,024 tokens at D 768: the forward's own tile, four of the
        # backward's
        tiles = dispatch.details()["fused_ce_fwd=lane_state"]
        assert tiles == "bt_fwd=1024 bt=256 bv=512", tiles
        assert not {"attention=einsum", "vocab_head=loss_chunk"} & set(forms)
        for pat in ("flash(_packed)?_fwd", "flash(_packed)?_dq",
                    "flash(_packed)?_dkv", "fused_ce_fwd", "fused_ce_dh",
                    "fused_ce_dw"):
            assert any(re.fullmatch(pat, k) for k in kernels), (pat, kernels)

    out = {"ok": True, "compile_s": round(compile_s, 2), "steps": steps,
           "first_loss": round(first, 4), "last_loss": round(last, 4),
           "tokens": steps * B * S,
           # information, not a metric: same order as the old 86k row
           "tokens_per_s_info": round(steps * B * S / dt, 1),
           "forms": sorted(forms), "mosaic_kernels": kernels,
           "memory": device_memory()}
    if zero_stage == 3:
        out["params"] = assert_split(f"{name} params", engine.state.params,
                                     n_dev, 0.95)
        out["opt_state"] = assert_split(f"{name} optimizer state",
                                        engine.state.opt_state, n_dev, 0.95)
    dev = jax.devices()[0]
    say(f"{name}: ok, loss {first:.4f} -> {last:.4f}, "
        f"{out['tokens_per_s_info']:.0f} tokens/s on {n_dev} x "
        f"{dev.platform}/{dev.device_kind} (information, not a metric), "
        f"forms {sorted(forms)}, kernels {kernels}")
    engine.destroy()
    del engine
    gc.collect()
    return out


def lowered_kernels(jitted, *args):
    """Names of the Mosaic kernels in the lowered text of ``jitted`` (a jit,
    or the compile watchdog's wrapper around one) at ``args``: every
    ``tpu_custom_call`` carries its pallas_call's ``name``. Empty off-TPU,
    where the kernels interpret."""
    text = getattr(jitted, "inner", jitted).lower(*args).as_text()
    return sorted(set(re.findall(r'kernel_name = "([^"]+)"', text)))


# ----------------------------------------------------------------------- #
# serve leg


def post(url, body, timeout=600):
    req = urllib.request.Request(
        url + "/v1/completions", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, r.read()


def complete(url, prompt, max_tokens):
    status, raw = post(url, {"prompt": prompt, "max_tokens": max_tokens})
    choice = json.loads(raw)["choices"][0]
    assert status == 200 and choice["finish_reason"] == "stop", (status, raw)
    ids = choice["token_ids"]
    assert len(ids) == max_tokens, (len(ids), max_tokens)
    return ids


def complete_streamed(url, prompt, max_tokens):
    status, raw = post(url, {"prompt": prompt, "max_tokens": max_tokens,
                             "stream": True})
    assert status == 200, status
    events = [line[len("data: "):] for line in raw.decode().split("\n")
              if line.startswith("data: ")]
    assert events[-1] == "[DONE]", events[-1]
    chunks = [json.loads(e)["choices"][0] for e in events[:-1]]
    assert chunks[-1]["finish_reason"] == "stop", chunks[-1]
    ids = [t for c in chunks for t in c["token_ids"]]
    assert len(ids) == max_tokens, (len(ids), max_tokens)
    return ids


def drive_server(name, server, serving, dry_run, out):
    """The client side of the serve leg; fills ``out``."""
    import numpy as np

    sz = sizes(dry_run)
    host, port = server.server_address[:2]
    url = f"http://{host}:{port}"
    vocab = serving.engine.module.config.vocab_size
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, vocab, size=n).tolist()
               for n in sz["prompt_lens"]]
    n_new = sz["max_tokens"]

    t0 = time.perf_counter()
    first = complete(url, prompts[0], n_new)     # alone: compiles the programs
    out["compile_s"] = round(time.perf_counter() - t0, 2)
    say(f"{name}: first completion {out['compile_s']:.1f}s "
        "(prefill + decode compile)")
    with concurrent.futures.ThreadPoolExecutor(len(prompts)) as pool:
        together = list(pool.map(lambda p: complete(url, p, n_new), prompts))
    streamed = complete_streamed(url, prompts[1], n_new)
    # greedy decoding: the same prompt gives the same ids alone, inside a
    # fused batch, and streamed
    assert together[0] == first, (together[0], first)
    assert streamed == together[1], (streamed, together[1])

    with urllib.request.urlopen(url + "/healthz", timeout=60) as r:
        health = json.loads(r.read())
        assert r.status == 200, r.status
    assert health["restarts"] == 0 and health["state"] == "serving", health
    counters = serving.engine.telemetry_snapshot()["counters"]
    faults = {k: v for k, v in counters.items() if v and k.startswith((
        "serving/step_faults", "serving/request_retries",
        "serving/engine_restarts", "serving/timeouts",
        "serving/shed_requests", "serving/rejected_requests"))}
    assert not faults, f"serving faults on a clean run: {faults}"
    n_req = len(prompts) + 2
    decode_tokens = counters["serving/generated_tokens"] - n_req
    assert counters["serving/decode_steps"] < decode_tokens, (
        "no fused decode batch wider than one formed", counters)
    out.update(requests=n_req, tokens=int(counters["serving/generated_tokens"]),
               decode_steps=int(counters["serving/decode_steps"]),
               health=health, prompts=prompts, served=together)


def check_against_xla(engine, model_xla, prompt, served):
    """Teacher-forced logits check of one served completion against the
    same weights with ``attention_backend="xla"`` (einsum attention, no
    Pallas): each served token must be the reference's argmax up to
    :data:`REFERENCE_LOGIT_TOL`. Returns (exact argmax matches, worst gap)."""
    import numpy as np

    import deepspeed_tpu
    ref = deepspeed_tpu.init_inference(model_xla, params=engine.params,
                                       dtype="bf16")
    seq = np.asarray(prompt + served, np.int32)[None, :]
    logits = np.asarray(ref.forward(seq), np.float32)[0]
    rows = logits[len(prompt) - 1:len(prompt) - 1 + len(served)]
    assert np.isfinite(rows).all()
    gaps = rows.max(axis=-1) - rows[np.arange(len(served)), served]
    assert gaps.max() <= REFERENCE_LOGIT_TOL, (
        f"served tokens are not the xla reference's argmax: gaps {gaps}")
    return int((gaps == 0).sum()), float(gaps.max())


def serve_leg(name, dry_run, n_dev, tp):
    """tp == 0: ``dscli serve``'s ``serve_main`` exactly. tp > 0: the same
    path built by hand, because ``serving.tp`` has no ``dscli serve`` flag."""
    import deepspeed_tpu
    from deepspeed_tpu.inference.serve import (AsyncServingEngine,
                                               build_http_server, serve_main)
    from deepspeed_tpu.ops import dispatch

    say(f"{name}: gpt2:125m bf16 block_size 128 max_running 8"
        + (f" serving.tp={tp}" if tp else ""))
    fresh_leg()
    out, box = {}, {}

    def client(server, serving):
        try:
            drive_server(name, server, serving, dry_run, out)
        finally:
            server.shutdown()        # unblocks serve_forever either way

    t0 = time.perf_counter()
    with concurrent.futures.ThreadPoolExecutor(1) as pool:

        def ready(server, serving):
            box["serving"] = serving
            box["client"] = pool.submit(client, server, serving)

        if not tp:
            argv = ["--model", "gpt2:125m", "--port", "0", "--telemetry",
                    "--block-size", "128", "--max-running", "8"]
            rc = serve_main(argv, ready_cb=ready,
                            model=gpt2_model(dry_run) if dry_run else None)
        else:
            engine = deepspeed_tpu.init_inference(
                gpt2_model(dry_run), dtype="bf16", telemetry={"events": True},
                serving={"block_size": 128, "max_running": 8, "tp": tp})
            serving = AsyncServingEngine(engine)
            server = build_http_server(serving, port=0)
            ready(server, serving)
            try:
                server.serve_forever()
            finally:
                server.server_close()
                serving.shutdown(drain=True, timeout=60)
            rc = 0
        box["client"].result()       # re-raises anything the client hit
    assert rc == 0, f"{name}: the serve path returned {rc}"
    out["wall_s"] = round(time.perf_counter() - t0, 2)

    engine = box["serving"].engine
    forms = dispatch.selected()
    assert dry_run or not any(k.endswith("=interpret") for k in forms), forms
    if n_dev == 1 and not dry_run:
        for want in ("paged_decode=paged_kernel", "paged_prefill=flash",
                     "kernel/paged_decode_attention=compiled"):
            assert want in forms, (want, forms)
        assert "paged_decode=gather_einsum" not in forms, forms
    if tp:
        out["params"] = assert_split(f"{name} params", engine.params, tp,
                                     0.6)
        out["kv_pools"] = assert_split(f"{name} KV pools",
                                       engine._paged_workspace[2], tp, 0.99)
    exact, gap = check_against_xla(
        engine, gpt2_model(dry_run, attention_backend="xla"),
        out["prompts"][0], out["served"][0])
    out.update(ok=True, forms=sorted(forms), memory=device_memory(),
               xla_argmax_matches=f"{exact}/{len(out['served'][0])}",
               xla_worst_logit_gap=round(gap, 5))
    del out["prompts"], out["served"]
    say(f"{name}: ok, {out['requests']} requests, {out['tokens']} tokens in "
        f"{out['decode_steps']} decode steps, restarts 0, xla argmax "
        f"{out['xla_argmax_matches']} (worst gap {gap:.4f}), forms "
        f"{sorted(forms)}")
    del engine, box
    gc.collect()
    return out


def state_leg(name, dry_run, n_dev):
    """A stack with a recurrent state beside its KV pool: the ``solar_open2``
    toy at a width the KDA decode kernel tiles (two heads of a 128 x 128
    float32 state; GQA heads of 64, which the paged and flash kernels take),
    six requests over four rows through ``generate_batch``, so rows idle on
    the dummy slot and slots are handed on. The decode step must have taken
    the Pallas state kernel, and its tokens are those of the same weights on
    the plain-XLA forms (float32 weights, matmuls in full float32: greedy
    picks decided by the last bit aside, the two forms are one result)."""
    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models.presets import get_model
    from deepspeed_tpu.ops import dispatch

    say(f"{name}: solar_open2 toy (1 GQA + 3 KDA layers, 2 heads of a "
        "128 x 128 state) fp32 block_size 128 max_running 4")
    fresh_leg()

    def toy(backend):
        return get_model("solar_open2", "tiny", head_size=64, lin_heads=2,
                         lin_head_dim=128, attention_backend=backend)

    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 512, size=n) for n in (5, 40, 130, 70, 9, 200)]
    serving = {"block_size": 128, "max_running": 4}
    n_new = sizes(dry_run)["max_tokens"]
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        engine = deepspeed_tpu.init_inference(
            toy("flash" if dry_run else "auto"), dtype="fp32", serving=serving)
        served = engine.generate_batch(prompts, max_new_tokens=n_new)
        forms = dispatch.selected()
        ref = deepspeed_tpu.init_inference(toy("xla"), params=engine.params,
                                           dtype="fp32", serving=serving)
        want = ref.generate_batch(prompts, max_new_tokens=n_new)
    assert dry_run or not any(k.endswith("=interpret") for k in forms), forms
    if n_dev == 1:
        # (over several devices the engine builds a mesh, where a bare
        # pallas_call is illegal: the step takes its plain-XLA form there)
        how = "interpret" if dry_run else "compiled"
        for need in ("kda_decode=kda_kernel", "paged_decode=paged_kernel",
                     f"kernel/kda_decode_update={how}"):
            assert need in forms, (need, forms)
        assert "kda_decode=slot_update" not in forms, forms
    assert "kda_decode=slot_update" in dispatch.selected()
    same = sum(np.array_equal(a, b) for a, b in zip(served, want))
    assert same == len(prompts), (
        f"{name}: {len(prompts) - same} of {len(prompts)} completions differ "
        "from the plain-XLA forms'")
    out = dict(ok=True, forms=sorted(forms), requests=len(prompts),
               tokens=len(prompts) * n_new, same_as_xla=f"{same}/{len(prompts)}",
               wall_s=round(time.perf_counter() - t0, 2))
    say(f"{name}: ok, {out['requests']} requests, tokens as the plain-XLA "
        f"forms' in {out['same_as_xla']}, forms {sorted(forms)}")
    del engine, ref
    gc.collect()
    return out


def blockgen_leg(name, dry_run, n_dev):
    """A model that generates by diffusion over blocks: the ``sdar`` toy at
    a head size the paged and flash kernels take (64), six requests over
    four rows through ``generate_batch``, so rows sit at different passes of
    different blocks in one fused step. The block step must have taken the
    paged kernel (a row's 4 positions on its position axis) and the
    prefill the flash kernel's staircase, and the tokens are those of the
    same weights on the plain-XLA forms. The experts of a pass (a call of
    16 positions) and of both prefill buckets (128 tokens: all rows ride
    every visit; 256: each expert over its own rows) must have been read by
    the grouped expert kernel (``experts=grouped_kernel``, never ``dense``)."""
    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models.presets import get_model
    from deepspeed_tpu.ops import dispatch

    say(f"{name}: sdar toy (d 128, GQA heads of 64, 4 of 8 experts of width "
        "128 held, blocks of 4) fp32 block_size 128 max_running 4")
    fresh_leg()

    def toy(backend):
        # matrices at 0.3 and the embedding at 1.0: at the default 0.02 a
        # toy this narrow answers the same few tokens whatever it is asked;
        # d and the experts' width whole lanes, which the grouped expert
        # kernel tiles
        return get_model("sdar", "tiny", head_size=64, d_model=128,
                         moe=dict(expert_d_ff=128), init_std=0.3,
                         embed_init_std=1.0, attention_backend=backend)

    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 512, size=n) for n in (5, 41, 130, 70, 9, 203)]
    serving = {"block_size": 128, "max_running": 4}
    n_new = sizes(dry_run)["max_tokens"]
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        engine = deepspeed_tpu.init_inference(
            toy("flash" if dry_run else "auto"), dtype="fp32", serving=serving)
        served = engine.generate_batch(prompts, max_new_tokens=n_new)
        forms = dispatch.selected()
        ref = deepspeed_tpu.init_inference(toy("xla"), params=engine.params,
                                           dtype="fp32", serving=serving)
        want = ref.generate_batch(prompts, max_new_tokens=n_new)
    assert dry_run or not any(k.endswith("=interpret") for k in forms), forms
    if n_dev == 1:
        how = "interpret" if dry_run else "compiled"
        # a pass's 16 positions and both buckets take the grouped expert
        # kernel (the 256-token one past one row tile), none the dense form
        assert "experts=dense" not in forms, forms
        for need in ("paged_block=paged_kernel", "paged_prefill=flash",
                     "experts=grouped_kernel",
                     f"kernel/paged_decode_attention={how}",
                     f"kernel/flash_attention={how}",
                     f"kernel/grouped_expert_mlp={how}"):
            assert need in forms, (need, forms)
    assert "paged_block=gather_einsum" in dispatch.selected()
    same = sum(np.array_equal(a, b) for a, b in zip(served, want))
    assert same == len(prompts), (
        f"{name}: {len(prompts) - same} of {len(prompts)} completions differ "
        "from the plain-XLA forms'")
    out = dict(ok=True, forms=sorted(forms), requests=len(prompts),
               tokens=len(prompts) * n_new, same_as_xla=f"{same}/{len(prompts)}",
               wall_s=round(time.perf_counter() - t0, 2))
    say(f"{name}: ok, {out['requests']} requests, tokens as the plain-XLA "
        f"forms' in {out['same_as_xla']}, forms {sorted(forms)}")
    del engine, ref
    gc.collect()
    return out


# ----------------------------------------------------------------------- #


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dry-run", action="store_true",
                    help="tiny sizes on the CPU backend with interpreted "
                         "kernels (sandbox debugging; proves nothing about "
                         "the chip)")
    args = ap.parse_args(argv)
    dry_run = args.dry_run
    if dry_run:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count=4")

    import jax
    import jax.monitoring

    from deepspeed_tpu.accelerator import require_tpu
    from deepspeed_tpu.utils.compile_cache import (compile_cache_entries,
                                                   enable_compile_cache)

    if dry_run:
        dev = {"platform": jax.devices()[0].platform,
               "kind": jax.devices()[0].device_kind,
               "count": len(jax.devices())}
        assert dev["platform"] == "cpu", dev
    else:
        try:
            dev = require_tpu()
        except RuntimeError as e:
            sys.exit(f"chip_smoke: {e}")
    say(f"device: {dev['count']} x {dev['kind']} (platform={dev['platform']})"
        + ("  *** DRY RUN ***" if dry_run else ""))

    cache_events = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda name, **kw: cache_events.update([name]))
    summary = {
        "ok": False, "device": dev,
        "versions": {"jax": jax.__version__,
                     "jaxlib": importlib.metadata.version("jaxlib"),
                     "libtpu": importlib.metadata.version("libtpu")},
        # a sandbox dry run must leave no cache inside the tree chiprun copies
        "compile_cache": {"dir": None if dry_run else enable_compile_cache()},
    }
    if not dry_run:
        summary["compile_cache"]["entries_before"] = compile_cache_entries()

    n = dev["count"]
    legs = summary["legs"] = {}
    legs["train"] = train_leg("train", dry_run, n, 1, {"dp": -1})
    legs["serve"] = serve_leg("serve", dry_run, n, 0)
    legs["serve_state"] = state_leg("serve_state", dry_run, n)
    legs["serve_blockgen"] = blockgen_leg("serve_blockgen", dry_run, n)
    if n >= 4:
        legs["train_zero3_fsdp4"] = train_leg("train_zero3_fsdp4", dry_run, 4,
                                              3, {"fsdp": 4})
        legs["serve_tp4"] = serve_leg("serve_tp4", dry_run, 4, 4)
    else:
        legs["train_zero3_fsdp4"] = legs["serve_tp4"] = \
            f"not run ({n} device)"

    if not dry_run:
        summary["compile_cache"].update(
            entries_after=compile_cache_entries(),
            hits=cache_events["/jax/compilation_cache/cache_hits"],
            misses=cache_events["/jax/compilation_cache/cache_misses"])
    summary["peak_bytes_in_use"] = max(
        m.get("peak_bytes_in_use", 0) for m in device_memory().values())
    summary["wall_s"] = round(time.perf_counter() - T0, 1)
    summary["ok"] = True             # a failed check raised before this line
    if dry_run:
        summary["dry_run"] = "DRY RUN: platform=cpu, proves nothing about the chip"
    say("summary" + (" (DRY RUN, platform=cpu)" if dry_run else "") + ":")
    print("summary " + json.dumps(summary), flush=True)
    print(result_line(dev), flush=True)
    return 0


def result_line(dev):
    """The last line of stdout, read by the driver: exactly the keys ``ok``
    and ``device`` {``platform``, ``kind`` (text), ``count`` (int)}. Only
    reached when every leg that ran passed; the detail is on the ``summary``
    line before it."""
    return json.dumps({"ok": True, "device": {
        "platform": str(dev["platform"]), "kind": str(dev["kind"]),
        "count": int(dev["count"])}})


T0 = time.perf_counter()

if __name__ == "__main__":
    sys.exit(main())
