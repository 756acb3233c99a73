"""The one general traffic generator. A mix is a data file under
``perfbench/traffic/`` (``<name>.json``); everything drawn is a function of
``--seed``, so the same seed gives the same inputs.

Train files (``"kind": "train"``): ``seq``, ``micro_batch_per_chip``,
``zero_stage``, ``mesh``, ``tokens`` (``{"dist": "zipf", "a"}``),
``sync_every``, ``warmup_steps``, ``trace_steps``, ``eval_sequences``.

Serve files (``"kind": "serve"``): ``loop`` (``open`` | ``closed``),
``arrivals`` (open: ``{"dist": "poisson", "rate_per_s"}`` and optionally
``"plan_seed"``: see ``ServeTraffic.open_plan``), ``clients_per_row``
(closed: clients = that x ``max_running``), ``plan_seed`` (closed, optional:
see ``ServeTraffic.request``), ``weights_seed`` (optional: the seed the
runner makes the weights from, for every ``--seed``; a rehearsal obeys it
too, so it narrows what the cell's check and its kept control see, and only
``closed_shortlong_6k`` names one), ``ramp_s`` (load offered before
the window opens, not measured), ``classes`` (each ``share``, ``prompt`` and
``answer`` length distributions), ``trace_seconds``, ``drain_s`` (the most the
runner waits, after the close, for the requests it cuts), ``check``.

Length distributions, each through its quantile function (``lengths_at``):
``{"dist": "fixed", "value"}``, ``{"dist": "uniform", "lo", "hi"}``
(inclusive), ``{"dist": "lognormal", "median", "sigma", "lo", "hi"}``
(clipped). A closed loop consumes as many requests as the system completes,
so request ``i`` takes its class and lengths at quantiles drawn freely from
``(plan_seed, i)``: one sample path of the mix for every ``--seed`` (every
closed loop a cell names has one since PR 59; a file without the key, a trial
mix or a test's own, draws them from ``(seed, i)``; a rehearsal loads its
cell's own mix and so keeps the plan). An open loop's window
holds a known number of requests, so its plan takes them at evenly spread
quantiles (``ServeTraffic.open_plan``).
``length_scale`` (set only by a rehearsal configuration) multiplies every
length.
"""

from __future__ import annotations

import json
import math
import os
import statistics
from typing import List, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> dict:
    """``perfbench/traffic/<name>.json``; a path to a file is taken as it is
    (a trial mix kept outside the tree)."""
    path = os.path.join(HERE, "traffic", f"{name}.json")
    if not os.path.exists(path) and os.path.isfile(name):
        path = name
    with open(path) as f:
        spec = json.load(f)
    if spec.get("kind") not in ("train", "serve"):
        raise ValueError(f"traffic {name!r}: kind must be train or serve")
    return spec


def lengths_at(dist: dict, u, scale: float = 1.0) -> np.ndarray:
    """The lengths at quantiles ``u`` (each in [0, 1)) of ``dist``."""
    u = np.atleast_1d(np.asarray(u, np.float64))
    kind = dist["dist"]
    if kind == "fixed":
        x = np.full(u.shape, float(dist["value"]))
    elif kind == "uniform":
        x = dist["lo"] + np.floor(u * (dist["hi"] - dist["lo"] + 1))
    elif kind == "lognormal":
        inv = statistics.NormalDist().inv_cdf
        z = np.array([inv(v) for v in np.clip(u, 1e-9, 1 - 1e-9)])
        x = np.clip(np.exp(math.log(dist["median"]) + dist["sigma"] * z),
                    dist["lo"], dist["hi"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.maximum(np.round(x * scale), 1).astype(int)


def length_bounds(dist: dict, scale: float = 1.0) -> Tuple[int, int]:
    if dist["dist"] == "fixed":
        lo = hi = dist["value"]
    else:
        lo, hi = dist["lo"], dist["hi"]
    return max(int(round(lo * scale)), 1), max(int(round(hi * scale)), 1)


# ----------------------------------------------------------------------- #
# training batches


class TokenSampler:
    """Token ids over ``vocab``, Zipf with exponent ``a`` over ranks (rank r
    has weight r**-a; ranks are mapped to ids by a fixed permutation of the
    seed, so frequent tokens are spread over the table)."""

    def __init__(self, spec: dict, vocab: int, seed: int):
        if spec["dist"] != "zipf":
            raise ValueError(f"unknown token distribution {spec['dist']!r}")
        self.vocab = vocab
        w = np.arange(1, vocab + 1, dtype=np.float64) ** -float(spec["a"])
        self.cdf = np.cumsum(w / w.sum())
        self.perm = np.random.default_rng([seed, 0x7a]).permutation(vocab)

    def sample(self, rng: np.random.Generator, shape) -> np.ndarray:
        ranks = np.searchsorted(self.cdf, rng.random(size=shape))
        return self.perm[np.minimum(ranks, self.vocab - 1)].astype(np.int32)


def train_batch(sampler: TokenSampler, seed: int, step: int, batch: int,
                seq: int) -> Dict[str, np.ndarray]:
    """The batch of global step ``step`` (negative: warm-up and checks)."""
    rng = np.random.default_rng([seed, 1, step + (1 << 20)])
    return {"input_ids": sampler.sample(rng, (batch, seq))}


# ----------------------------------------------------------------------- #
# serving requests


class ServeTraffic:
    def __init__(self, spec: dict, vocab: int, seed: int,
                 length_scale: float = 1.0):
        self.spec, self.vocab, self.seed = spec, vocab, seed
        self.scale = length_scale
        self.classes = spec["classes"]
        shares = np.array([c["share"] for c in self.classes], np.float64)
        self.shares = shares / shares.sum()

    def _request(self, index, ci, n_prompt, n_new, rng) -> dict:
        return {"index": index, "cls": ci, "max_new": int(n_new),
                "prompt": rng.integers(0, self.vocab,
                                       size=int(n_prompt)).astype(np.int32)}

    def request(self, i: int) -> dict:
        """Request ``i`` of a closed loop: ``{"index", "cls", "prompt" (int32
        ids), "max_new"}``, whatever was consumed before it.

        A closed loop's tokens/s follows how many prefills its window holds,
        and a window holds some percent more or fewer by where a seed's draw
        of lengths puts the completions. So a mix names a ``plan_seed`` at
        its top level: request ``i`` then takes its class and its lengths
        from ``(plan_seed, i)``, the same for every ``--seed``, and the seed
        draws only the prompt's tokens (and the weights): every seed offers
        the same work in the same order. Without the key both come from
        ``(seed, i)``."""
        rng = np.random.default_rng([self.seed, 2, i])
        plan_seed = self.spec.get("plan_seed")
        shape = rng if plan_seed is None else \
            np.random.default_rng([int(plan_seed), 2, i])
        ci = min(int(np.searchsorted(np.cumsum(self.shares), shape.random())),
                 len(self.classes) - 1)
        cls = self.classes[ci]
        n_prompt, n_new = (lengths_at(cls[k], shape.random(), self.scale)[0]
                           for k in ("prompt", "answer"))
        return self._request(i, ci, n_prompt, n_new, rng)

    def prompt_bounds(self) -> List[Tuple[int, int]]:
        """(shortest, longest) prompt of each class: what the runner warms."""
        return [length_bounds(c["prompt"], self.scale) for c in self.classes]

    def longest_request(self) -> int:
        return max(length_bounds(c["prompt"], self.scale)[1]
                   + length_bounds(c["answer"], self.scale)[1]
                   for c in self.classes)

    def open_plan(self, ramp_s: float, seconds: float) -> List[dict]:
        """The requests of an open loop, ascending by ``due`` (seconds from
        the opening of the window; negative during the ramp): a Poisson
        process at ``rate_per_s`` given its count. The window holds exactly
        ``round(rate x seconds)`` requests (so do the ramp's seconds), at
        arrival times that are uniform order statistics, which is what a
        Poisson process's are once its count is known: gaps are exponential,
        bursts and lulls fall where the seed puts them. Each class has its
        share of the requests and its lengths at evenly spread quantiles of
        their distributions, dealt out in an order of the seed. Two seeds
        thus offer the same work under another sample path.

        Where a window holds too few requests for two sample paths to read
        alike (some eighty: a tail then reads where the seed bunched them),
        ``arrivals`` names a ``plan_seed``: times, classes and lengths are
        drawn from it, the same for every ``--seed``, and the seed draws only
        the prompts' tokens."""
        rate = float(self.spec["arrivals"]["rate_per_s"])
        if self.spec["arrivals"]["dist"] != "poisson":
            raise ValueError("arrivals: only poisson is known")
        plan_seed = self.spec["arrivals"].get("plan_seed")
        out = []
        for part, (t0, span) in enumerate(((-ramp_s, ramp_s), (0.0, seconds))):
            rng = np.random.default_rng(
                [self.seed if plan_seed is None else int(plan_seed), 4, part])
            tokens = rng if plan_seed is None else \
                np.random.default_rng([self.seed, 5, part])
            n = int(round(rate * span))
            quota = np.floor(self.shares * n).astype(int)
            for i in np.argsort(-(self.shares * n - quota))[: n - quota.sum()]:
                quota[i] += 1
            cls = rng.permutation(np.repeat(np.arange(len(self.classes)), quota))
            lengths = [[rng.permutation(lengths_at(
                c[k], (np.arange(q) + rng.random(q)) / max(q, 1), self.scale))
                for k in ("prompt", "answer")]
                for c, q in zip(self.classes, quota)]
            due = t0 + span * np.sort(rng.random(n))
            taken = [0] * len(self.classes)
            for k in range(n):
                ci = int(cls[k])
                j = taken[ci]
                taken[ci] += 1
                out.append({**self._request(len(out), ci, lengths[ci][0][j],
                                            lengths[ci][1][j], tokens),
                            "due": float(due[k])})
        return out
