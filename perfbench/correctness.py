"""The comparison that decides ``correct``: the system against the plain
reference of its configuration on the program's own weights.

The reference is a module under ``reference/``, named by the configuration's
map (``reference/maps/<config>.json`` ``"reference"``; ``dense_decoder``
where the map names none). What is asked of it, and all that is:

* ``final_hidden(cfg, weights, tokens)`` — tokens [B, S] -> the hidden state
  the head reads, [B, S, D];
* ``logits_rows(cfg, weights, h_rows)`` — [N, D] of those rows -> logits
  [N, V]: the head is the reference's business, tied or not;
* ``next_token_loss(cfg, weights, tokens)`` — the loss the model trains on as
  a float, every auxiliary term included and named in the module's docstring;
* optionally ``Weights``, its own view of the program's parameter tree, for
  a stack whose layers are not one leading axis (``Weights`` below serves
  every stack that is).

``cfg`` is ``reference_config`` of the configuration file and the map. One
set of tolerances holds for every reference.

Tolerances, with their reasons:

* ``TRAIN_LOSS_TOL`` — the engine's ``eval_batch`` computes in bf16 with
  float32 accumulation, the reference in float32. Over some thousands of
  tokens the per-token rounding mostly averages out: the 29 chip runs of
  PR 22 differed by 1e-6 to 4.6e-4 nats (median 4.6e-5; the largest on four
  chips, BLOOM's largest 1.6e-4). 2e-3 is four times the largest seen. The
  check is a mean over i.i.d. tokens, so it is blunt to an error that moves
  single positions only (PERF.md section 7: it wants per-token losses, which
  ``eval_batch`` does not give).
* ``SERVE_ULPS`` — served tokens are greedy picks from bf16 logits. With
  random weights the two largest of ~50k logits are often a rounding step
  apart, so equal token ids cannot be asked for. Each served token's
  REFERENCE logit must be within 4 bf16 steps (at the magnitude of the
  reference's maximum, a step is 2^(floor(log2 |max|) - 7)) of that maximum,
  at every position, teacher-forced on the served tokens.
"""

from __future__ import annotations

import importlib
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
TRAIN_LOSS_TOL = 2e-3
SERVE_ULPS = 4


def load_map(config_name: str) -> dict:
    with open(os.path.join(HERE, "reference", "maps", f"{config_name}.json")) as f:
        return json.load(f)


def load_reference(name_map: dict):
    """The map's reference module, imported when a check first needs it."""
    return importlib.import_module(
        "reference." + name_map.get("reference", "dense_decoder"))


def reference_config(config: dict, name_map: dict) -> dict:
    cfg = dict(name_map["fixed"])
    for ours, theirs in name_map["from_config"].items():
        cfg[ours] = config[theirs]
    cfg.setdefault("d_ff", 4 * cfg["d_model"])
    return cfg


class Weights:
    """The program's parameter tree seen through the reference's names, in
    float32 on one device, one layer at a time."""

    def __init__(self, params, name_map: dict, device=None):
        self.params, self.map = params, name_map
        self.device = device or jax.devices()[0]
        self._top = None

    def _get(self, path: str):
        node = self.params
        for part in path.split("/"):
            node = node[part]
        return node

    def _f32(self, a):
        return jax.device_put(a, self.device).astype(jnp.float32)

    def top(self) -> dict:
        if self._top is None:
            self._top = {k: self._f32(self._get(p))
                         for k, p in self.map["top"].items()}
        return self._top

    def layer(self, l: int) -> dict:
        return {k: self._f32(self._get(p)[l])
                for k, p in self.map["layer"].items()}


def _reference_of(weights: Weights):
    """The reference the weights' map names, and the weights in that
    reference's own view where it has one."""
    ref = load_reference(weights.map)
    view = getattr(ref, "Weights", Weights)
    if type(weights) is not view:
        weights = view(weights.params, weights.map, weights.device)
    return ref, weights


def check_train(engine_loss: float, cfg: dict, weights: Weights,
                tokens: np.ndarray) -> dict:
    ref, weights = _reference_of(weights)
    want = ref.next_token_loss(cfg, weights, jnp.asarray(tokens))
    diff = abs(engine_loss - want)
    return {"ok": bool(math.isfinite(want) and diff <= TRAIN_LOSS_TOL),
            "engine_loss": engine_loss, "reference_loss": want,
            "abs_diff": diff, "tol": TRAIN_LOSS_TOL}


def check_served(cfg: dict, weights: Weights, prompt: np.ndarray,
                 served: list) -> dict:
    """Teacher-forced: the reference runs prompt + served tokens once; at
    each served position the served token's logit is compared with the
    reference's largest."""
    ref, weights = _reference_of(weights)
    seq = np.concatenate([prompt, np.asarray(served, np.int32)])[None, :]
    h = ref.final_hidden(cfg, weights, jnp.asarray(seq))
    rows = h[0, len(prompt) - 1: len(prompt) - 1 + len(served)]
    logits = np.asarray(ref.logits_rows(cfg, weights, rows), np.float32)
    top = logits.max(axis=-1)
    got = logits[np.arange(len(served)), np.asarray(served)]
    step = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(top), 1e-30))) - 7)
    gaps = (top - got) / step
    return {"ok": bool(np.isfinite(logits).all() and gaps.max() <= SERVE_ULPS),
            "prompt_tokens": int(len(prompt)), "served": len(served),
            "argmax_matches": int((gaps == 0).sum()),
            "worst_gap_bf16_steps": float(gaps.max()), "tol_steps": SERVE_ULPS}
