"""The train runner: ``deepspeed_tpu.initialize`` + ``train_batch``, any
configuration ``models/presets.py get_model`` can build, any train traffic
file.

Telemetry stays OFF in the engine: switched on, ``train_batch`` blocks on the
loss of every step and compiles a second forward program for its FLOPs gauge
(PERF.md §3), which changes what is timed. Compiles are counted by the
harness through ``jax.monitoring`` instead.
"""

from __future__ import annotations

import time

import numpy as np

import correctness
from build_model import build_model
import traffic as traffic_mod
from tracing import annotate
from weights import make_params


class TrainRunner:
    kind = "train"

    def __init__(self, cell, config, spec, seed, say):
        self.cell, self.config, self.spec = cell, config, spec
        self.seed, self.say = seed, say
        self.chips = cell["chips"]

    # ------------------------------------------------------------------ #

    def setup(self):
        import jax
        import jax.numpy as jnp

        import deepspeed_tpu

        cfgf, spec = self.config, self.spec
        devices = jax.devices()[: self.chips]
        preset = cfgf["preset"]
        train = cfgf["assumed"]["train"]
        self.model = build_model(preset, remat=train["remat"])
        self.vocab = self.model.config.vocab_size
        self.seq = int(spec["seq"] * cfgf.get("length_scale", 1.0))
        self.micro = int(train.get("micro_batch_per_chip",
                                   spec["micro_batch_per_chip"]))
        self.batch = self.micro * self.chips
        t0 = time.perf_counter()
        params = make_params(self.model, self.seed, jnp.float32, devices)
        jax.block_until_ready(params)
        self.say(f"weights: {time.perf_counter() - t0:.1f}s, float32, seeded")
        mesh = dict(spec["mesh"])
        self.engine, _, _, _ = deepspeed_tpu.initialize(
            model=self.model, model_parameters=params, config={
                "train_micro_batch_size_per_gpu": self.micro,
                "gradient_accumulation_steps":
                    train["gradient_accumulation_steps"],
                "optimizer": {"type": train["optimizer"],
                              "params": {"lr": train["lr"],
                                         "weight_decay": train["weight_decay"]}},
                "zero_optimization": {"stage": spec["zero_stage"]},
                "bf16": {"enabled": True},
                "mesh": mesh,
                "steps_per_print": 0,
            })
        del params
        self.sampler = traffic_mod.TokenSampler(spec["tokens"], self.vocab,
                                                self.seed)
        self.warm_losses = []
        for i in range(int(spec["warmup_steps"])):
            t0 = time.perf_counter()
            loss = float(self.engine.train_batch(self._batch(-1 - i)))
            self.warm_losses.append(loss)
            self.say(f"warm-up step {i}: {time.perf_counter() - t0:.2f}s, "
                     f"loss {loss:.4f}")

    def _batch(self, step):
        return traffic_mod.train_batch(self.sampler, self.seed, step,
                                       self.batch, self.seq)

    # ------------------------------------------------------------------ #

    def window(self, seconds, trace):
        """Steps until ``seconds`` have passed, the host blocking on every
        ``sync_every``-th loss; the next batch is drawn while the step runs
        (dispatch is asynchronous). Returns the facts the metrics read."""
        engine, every = self.engine, int(self.spec["sync_every"])
        trace_steps = int(self.spec["trace_steps"])
        syncs = []                       # (time, steps done, loss)
        step = 0
        batch = self._batch(0)
        t_open = time.perf_counter()
        syncs.append((t_open, 0, None))
        traced = None
        while True:
            if trace.can_start and step % every == 0 \
                    and time.perf_counter() - t_open >= 0.4 * seconds:
                trace.start()
                traced = [step, None]
            with annotate("train_batch"):
                loss = engine.train_batch(batch)
            step += 1
            with annotate("input"):
                batch = self._batch(step)
            ends_trace = trace.active and step - traced[0] >= trace_steps
            if step % every == 0 or ends_trace:
                with annotate("sync"):
                    val = float(loss)
                now = time.perf_counter()
                if step % every == 0:
                    syncs.append((now, step, val))
                if ends_trace:
                    trace.stop()
                    traced[1] = step
                if now - t_open >= seconds and step % every == 0:
                    break
        t_first, t_last = syncs[0][0], syncs[-1][0]
        tokens = step * self.batch * self.seq
        losses = [v for _, _, v in syncs[1:]]
        return {
            "t_open": t_open, "t_close": t_last,
            "steps": step, "tokens": tokens, "syncs": len(losses),
            "tokens_per_s": tokens / (t_last - t_first),
            "step_ms_median": float(np.median(np.diff(
                [t for t, _, _ in syncs]) / every) * 1e3),
            "losses": losses,
            "traced_steps": (traced[1] - traced[0]) if traced and traced[1] else 0,
            "attempted": step, "failed": 0,
        }

    def end_to_end(self, facts, names):
        return {"train_tokens_per_s_per_chip":
                (facts["window"]["tokens_per_s"] / self.chips, "tokens/s")}

    def shapes(self, dims):
        return {**dims, "batch_per_chip": self.micro, "seq": self.seq,
                "tokens_per_chip": self.micro * self.seq}

    # ------------------------------------------------------------------ #

    def check(self, facts, name_map):
        # every synchronised step of the process, warm-up included
        losses = self.warm_losses + facts["window"]["losses"]
        k = max(min(4, len(losses) // 2), 1)
        finite = bool(np.isfinite(losses).all())
        fell = len(losses) >= 2 and \
            float(np.mean(losses[-k:])) < float(np.mean(losses[:k]))
        self.say(f"loss: first {losses[:k]}, last {losses[-k:]} "
                 f"(final {losses[-1]:.6f})")
        # the data axis needs a multiple of the chips; the reference is
        # given the same sequences
        n_eval = max(int(self.spec["eval_sequences"]), self.chips)
        tokens = traffic_mod.train_batch(self.sampler, self.seed, -100,
                                         n_eval, self.seq)["input_ids"]
        got = float(self.engine.eval_batch({"input_ids": tokens}))
        cfg = correctness.reference_config(self.config, name_map)
        weights = correctness.Weights(self.engine.state.params, name_map)
        res = correctness.check_train(got, cfg, weights, tokens)
        return {"ok": finite and fell and res["ok"], "loss_finite": finite,
                "loss_fell": fell, "reference": res}

    def close(self):
        self.engine.destroy()
