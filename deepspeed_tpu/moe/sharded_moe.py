"""Top-k gating and the expert-parallel MoE layer.

Reference parity: ``deepspeed/moe/sharded_moe.py`` — ``TopKGate`` (:176) with
top-1/top-2 gating, capacity factor, jittered gates, load-balancing auxiliary
loss, and random token selection; ``MOELayer`` (:417) dispatching tokens to
experts with all-to-all over the expert-parallel group.

TPU-native design: the gating math keeps the GShard einsum formulation (the
reference's own ancestry) in pure jnp with STATIC capacity (XLA requires
static shapes — ``drop_tokens=False`` therefore sets capacity = tokens
instead of growing it dynamically). Expert parallelism is declarative:
expert-stacked weights are sharded over the ``ep`` mesh axis and the
dispatched token tensor ``[E, C, D]`` is constrained to ``P("ep")`` on the
expert dim — the SPMD partitioner inserts the all-to-all pair the reference
issues by hand (``sharded_moe.py:467-499``).

Beside the capacity path there is a routing for any ``k`` and two dispatches
that drop nothing (:func:`topk_routing`, :func:`sorted_dispatch`,
:func:`dense_dispatch`): what a model with many small experts and a large
``k`` needs (OLMoE: top-8 of 64), where a ``[T, E, C]`` one-hot pair is
almost all zeros and a full capacity bucket would drop tokens the model
never drops.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


def _capacity(num_tokens: int, num_experts: int, capacity_factor: float, min_capacity: int) -> int:
    cap = int(math.ceil(num_tokens / num_experts * capacity_factor))
    return max(cap, min_capacity)


def _one_hot(idx, n):
    return jax.nn.one_hot(idx, n, dtype=jnp.float32)


_warned_rts = False


def gumbel_noise(rng, shape):
    u = jax.random.uniform(rng, shape, minval=1e-9, maxval=1.0 - 1e-9)
    return -jnp.log(-jnp.log(u))


def top1gating(logits: jnp.ndarray,
               capacity_factor: float = 1.0,
               min_capacity: int = 4,
               used_token: Optional[jnp.ndarray] = None,
               noisy_gate_policy: Optional[str] = None,
               drop_tokens: bool = True,
               use_rts: bool = True,
               rng=None) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-1 gating (reference sharded_moe.py:176-300).

    Returns (l_aux, combine_weights [T,E,C], dispatch_mask [T,E,C] bool,
    exp_counts [E]).

    - ``noisy_gate_policy``: None | 'RSample' (gumbel-perturbed routing) |
      'Jitter' (multiplicative input jitter is applied by the gate module).
    - ``use_rts``: random token selection — capacity slots go to a random
      subset of each expert's tokens rather than the lowest token indices,
      debiasing drops (reference :262). Needs ``rng``: gating is a pure
      function, so without a key there is no randomness to draw — RTS falls
      back to positional priority (with a one-time warning) rather than
      reusing a constant key that would re-drop the same positions every step.
    """
    T, E = logits.shape
    C = T if not drop_tokens else _capacity(T, E, capacity_factor, min_capacity)
    C = min(C, T)

    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    route_logits = logits
    if noisy_gate_policy == "RSample":
        if rng is None:
            raise ValueError("RSample gating needs an rng")
        route_logits = logits + gumbel_noise(rng, logits.shape)
    idx1 = jnp.argmax(route_logits, axis=-1)
    mask1 = _one_hot(idx1, E)
    if used_token is not None:
        mask1 = mask1 * used_token[:, None]

    # load-balancing loss: E * sum_e mean_gate_e * mean_count_e
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1, axis=0)
    l_aux = jnp.sum(me * ce) * E

    # expert load BEFORE capacity truncation (reference :203) — the
    # monitoring signal must show overflow, not the clipped counts
    exp_counts = jnp.sum(mask1, axis=0).astype(jnp.int32)

    if use_rts and rng is None:
        global _warned_rts
        if not _warned_rts:
            from deepspeed_tpu.utils.logging import logger
            logger.warning("top1gating: use_rts=True but no rng was provided; "
                           "falling back to positional capacity priority")
            _warned_rts = True
        use_rts = False

    # capacity assignment priority: positional, or randomized (RTS)
    if use_rts:
        scores = jax.random.uniform(jax.random.fold_in(rng, 1), (T,))
        order = jnp.argsort(scores)  # random permutation of token priority
        mask1_prio = mask1[order]
        loc_sorted = jnp.cumsum(mask1_prio, axis=0) - mask1_prio
        inv = jnp.argsort(order)
        locations1 = jnp.sum(loc_sorted[inv] * mask1, axis=1)
    else:
        loc = jnp.cumsum(mask1, axis=0) - mask1
        locations1 = jnp.sum(loc * mask1, axis=1)

    keep = (locations1 < C).astype(jnp.float32) * jnp.sum(mask1, axis=1)
    mask1 = mask1 * keep[:, None]

    gates1 = jnp.sum(gates * mask1, axis=1)  # selected gate value (0 if dropped)
    combine = (gates1[:, None, None] * mask1[:, :, None] *
               _one_hot(locations1.astype(jnp.int32), C)[:, None, :])
    dispatch_mask = combine > 0
    return l_aux, combine, dispatch_mask, exp_counts


def top2gating(logits: jnp.ndarray,
               capacity_factor: float = 1.0,
               min_capacity: int = 4,
               drop_tokens: bool = True,
               rng=None) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-2 gating (reference sharded_moe.py:303-415): second expert chosen
    from gumbel-perturbed logits with the first masked out; gate values of the
    two experts renormalized; capacity doubled vs top-1."""
    T, E = logits.shape
    C = T if not drop_tokens else _capacity(T, E, 2 * capacity_factor, min_capacity)
    C = min(C, T)

    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    idx1 = jnp.argmax(gates, axis=-1)
    mask1 = _one_hot(idx1, E)

    noise = gumbel_noise(rng, logits.shape) if rng is not None else 0.0
    logits2 = logits.astype(jnp.float32) + noise
    logits2 = jnp.where(mask1 > 0, -jnp.inf, logits2)
    idx2 = jnp.argmax(logits2, axis=-1)
    mask2 = _one_hot(idx2, E)

    loc1 = jnp.cumsum(mask1, axis=0) - mask1
    # expert-1 tokens take priority; expert-2 slots start after them
    loc2 = jnp.cumsum(mask2, axis=0) - mask2 + jnp.sum(mask1, axis=0, keepdims=True)

    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1, axis=0)
    l_aux = jnp.sum(me * ce) * E

    # first-choice expert load before truncation (reference parity)
    exp_counts = jnp.sum(mask1, axis=0).astype(jnp.int32)

    locations1 = jnp.sum(loc1 * mask1, axis=1)
    locations2 = jnp.sum(loc2 * mask2, axis=1)
    mask1 = mask1 * (locations1 < C)[:, None]
    mask2 = mask2 * (locations2 < C)[:, None]

    g1 = jnp.sum(gates * mask1, axis=1)
    g2 = jnp.sum(gates * mask2, axis=1)
    denom = jnp.clip(g1 + g2, 1e-9, None)
    g1, g2 = g1 / denom, g2 / denom

    combine1 = g1[:, None, None] * mask1[:, :, None] * _one_hot(locations1.astype(jnp.int32), C)[:, None, :]
    combine2 = g2[:, None, None] * mask2[:, :, None] * _one_hot(locations2.astype(jnp.int32), C)[:, None, :]
    combine = combine1 + combine2
    dispatch_mask = combine > 0
    return l_aux, combine, dispatch_mask, exp_counts


# --------------------------------------------------------------------- #
# Top-k of any size, no capacity, no dropped token.


def topk_routing(logits: jnp.ndarray, k: int, norm_topk_prob: bool = False,
                 scoring: str = "softmax", select_bias=None,
                 norm_eps: Optional[float] = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """A score for every expert first (``scoring``: ``softmax`` over the
    experts, or ``sigmoid`` of each logit), then the ``k`` largest as they
    are (``norm_topk_prob``: divided by their sum). ``select_bias`` [E]
    (the sigmoid router's load-balancing bias) is added to the scores for
    the CHOICE only: the weights are the scores without it. logits [T, E] ->
    (weights [T, k] float32, experts [T, k] int32, scores [T, E] float32).
    ``norm_eps``: what the normalisation adds to the sum (None: 1e-20 under
    sigmoid scoring, whose k scores can all underflow, and nothing under
    softmax). All in float32 whatever the logits came in."""
    logits = logits.astype(jnp.float32)
    if scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
    elif scoring == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"scoring={scoring!r} (expected softmax|sigmoid)")
    if select_bias is None:
        weights, experts = jax.lax.top_k(probs, k)
    else:
        _, experts = jax.lax.top_k(probs + select_bias.astype(jnp.float32), k)
        weights = jnp.take_along_axis(probs, experts, axis=-1)
    if norm_topk_prob:
        if norm_eps is None:
            norm_eps = 1e-20 if scoring == "sigmoid" else 0.0
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + norm_eps)
    return weights, experts.astype(jnp.int32), probs


def topk_balance_loss(probs: jnp.ndarray, counts: jnp.ndarray,
                      k: int) -> jnp.ndarray:
    """Load-balancing loss of one layer for top-k routing: ``E * sum_e f_e *
    P_e`` with ``f_e`` expert e's share of the ``T * k`` assignments and
    ``P_e`` its mean routing probability (Switch / megablocks convention,
    1.0 when both are uniform). probs [T, E], counts [E]."""
    T, E = probs.shape
    f = counts.astype(jnp.float32) / (T * k)
    return E * jnp.sum(f * jnp.mean(probs, axis=0))


def _masked_experts(experts, num_experts: int, valid):
    """Flat [T*k] expert index per assignment; a row that is not ``valid``
    (a padded prompt position, an empty decode row) goes to the sentinel
    ``num_experts``: sorted last, in no group, counted nowhere."""
    flat = experts.reshape(-1)
    if valid is None:
        return flat
    return jnp.where(jnp.repeat(valid.astype(bool), experts.shape[1]),
                     flat, num_experts)


def _group_sizes(flat, num_experts: int):
    return jnp.sum(flat[:, None] == jnp.arange(num_experts)[None, :],
                   axis=0, dtype=jnp.int32)


def sorted_dispatch(tokens: jnp.ndarray, weights: jnp.ndarray,
                    experts: jnp.ndarray, num_experts: int,
                    grouped_fn: Callable, valid=None, cap: int = 0):
    """No-drop dispatch over ragged groups: the ``T * k`` assignments sorted
    by expert, ``grouped_fn(xs [T*k, D], group_sizes [E]) -> [T*k, D]`` (the
    experts as grouped matmuls, ``jax.lax.ragged_dot``) run over them, and
    the rows weighted and summed back per token. Differentiable; work and
    memory are ``T * k`` rows whatever ``E`` is. ``valid`` [T] keeps padding
    out (see :func:`_masked_experts`). ``cap`` (static, under ``T * k``;
    inference only): where ``experts`` names mostly experts that are not
    here (a chip's share of an expert-parallel layer: index ``num_experts``),
    the sorted order holds the assignments that ARE first, and only its
    first ``cap`` rows are gathered, multiplied and summed back; a call
    with more than ``cap`` of them takes the whole order, so nothing is
    ever dropped. Two bodies, on purpose: the whole order is un-sorted by a
    gather and summed by an einsum (what training differentiates and what
    every model that holds all its experts traces to: its programs are
    pinned, ``tests/unit/paged_program_digests.py``); a head of the order
    has no whole order to un-sort and is summed back by a scatter-add,
    at either length (``over``). Returns (out [T, D], counts [E])."""
    T, k = experts.shape
    with jax.named_scope("moe_dispatch"):
        flat = _masked_experts(experts, num_experts, valid)
        order = jnp.argsort(flat)
        counts = _group_sizes(flat, num_experts)

    def over(n):
        """The first ``n`` rows of the sorted order, summed back a token."""
        rows = order[:n]
        with jax.named_scope("moe_dispatch"):
            xs = tokens[rows // k]
        ys = grouped_fn(xs, counts)
        with jax.named_scope("moe_dispatch"):
            # rows past the last group belong to no expert: whatever the
            # grouped matmul left there must not reach a token, and a
            # weight of zero would not stop it (the chip leaves those rows
            # as it found them: a NaN or an infinity times 0 is a NaN)
            w = weights.reshape(-1)[rows].astype(jnp.float32)
            ys = jnp.where((flat[rows] < num_experts)[:, None],
                           ys.astype(jnp.float32) * w[:, None], 0.0)
            return jnp.zeros((T, tokens.shape[1]), jnp.float32).at[
                rows // k].add(ys)

    if cap and cap < T * k:
        out = jax.lax.cond(jnp.sum(counts) <= cap, lambda: over(cap),
                           lambda: over(T * k))
        return out.astype(tokens.dtype), counts
    with jax.named_scope("moe_dispatch"):
        xs = tokens[order // k]
    ys = grouped_fn(xs, counts)
    with jax.named_scope("moe_dispatch"):
        # rows past the last group belong to no expert: whatever the grouped
        # matmul left there must not reach a token
        ys = jnp.where((flat[order] < num_experts)[:, None], ys, 0)
        back = jnp.argsort(order)
        y = ys[back].reshape(T, k, -1)
        out = jnp.einsum("tk,tkd->td", weights.astype(jnp.float32),
                         y.astype(jnp.float32))
    return out.astype(tokens.dtype), counts


def dense_dispatch(tokens: jnp.ndarray, weights: jnp.ndarray,
                   experts: jnp.ndarray, num_experts: int,
                   dense_fn: Callable, valid=None):
    """No-drop dispatch for FEW rows: every expert computes every row and
    ``combine`` [T, E] (a row's weight for each expert it chose, else 0)
    picks. Exact like :func:`sorted_dispatch`; ``E / k`` times its
    arithmetic, but no sort, no gather and no ragged group, so it is the
    form for a decode batch that touches every expert anyway and is bound by
    reading their weights. ``dense_fn(tokens [T, D], combine [T, E]) ->
    [T, D]``. Returns (out [T, D], counts [E])."""
    with jax.named_scope("moe_dispatch"):
        chosen = experts[:, :, None] == jnp.arange(num_experts)[None, None, :]
        if valid is not None:
            chosen = chosen & valid.astype(bool)[:, None, None]
        combine = jnp.sum(jnp.where(chosen, weights[:, :, None], 0.0), axis=1)
        counts = jnp.sum(chosen, axis=(0, 1), dtype=jnp.int32)
    return dense_fn(tokens, combine).astype(tokens.dtype), counts


class TopKGate:
    """Gate module (reference sharded_moe.py:176): a linear router + top-k
    gating. ``params`` = {"wg": [D, E]}."""

    def __init__(self, model_dim: int, num_experts: int, k: int = 1,
                 capacity_factor: float = 1.0, eval_capacity_factor: float = 1.0,
                 min_capacity: int = 4, noisy_gate_policy: Optional[str] = None,
                 drop_tokens: bool = True, use_rts: bool = True):
        if k not in (1, 2):
            raise ValueError("TopKGate supports k=1 or k=2")
        self.model_dim = model_dim
        self.num_experts = num_experts
        self.k = k
        self.capacity_factor = capacity_factor
        self.eval_capacity_factor = eval_capacity_factor
        self.min_capacity = min_capacity
        self.noisy_gate_policy = noisy_gate_policy
        self.drop_tokens = drop_tokens
        self.use_rts = use_rts

    def init(self, rng):
        scale = 1.0 / math.sqrt(self.model_dim)
        return {"wg": jax.random.normal(rng, (self.model_dim, self.num_experts)) * scale}

    def __call__(self, params, tokens, used_token=None, rng=None, train: bool = True):
        """tokens [T, D] → (l_aux, combine [T,E,C], dispatch [T,E,C], counts)."""
        x = tokens
        if train and self.noisy_gate_policy == "Jitter" and rng is not None:
            x = x * jax.random.uniform(rng, x.shape, minval=0.99, maxval=1.01)
        logits = x.astype(jnp.float32) @ params["wg"].astype(jnp.float32)
        cf = self.capacity_factor if train else self.eval_capacity_factor
        if self.k == 1:
            return top1gating(logits, cf, self.min_capacity, used_token,
                              self.noisy_gate_policy if train else None,
                              # RTS is a training regularizer: eval routes
                              # deterministically (reference inference kernels)
                              self.drop_tokens, self.use_rts and train, rng=rng)
        return top2gating(logits, cf, self.min_capacity, self.drop_tokens, rng=rng)


def dispatch_combine(tokens: jnp.ndarray,
                     combine: jnp.ndarray,
                     dispatch: jnp.ndarray,
                     expert_fn: Callable,
                     expert_params: Any,
                     mesh=None) -> jnp.ndarray:
    """Dispatch → expert compute → combine (shared by MOELayer and the MoE
    model zoo). ``tokens [T,D]``, ``combine/dispatch [T,E,C]`` →  ``[T,D]``.

    The dispatched tensor is constrained to ``P("ep")`` on its expert dim so
    the SPMD partitioner inserts the all-to-all pair over the ep axis.
    """
    dispatched = jnp.einsum("tec,td->ecd", dispatch.astype(tokens.dtype), tokens)
    if mesh is not None and "ep" in mesh.shape:
        dispatched = jax.lax.with_sharding_constraint(
            dispatched, NamedSharding(mesh, P("ep", None, None)))
    expert_out = jax.vmap(expert_fn)(expert_params, dispatched)
    if mesh is not None and "ep" in mesh.shape:
        expert_out = jax.lax.with_sharding_constraint(
            expert_out, NamedSharding(mesh, P("ep", None, None)))
    return jnp.einsum("tec,ecd->td", combine.astype(tokens.dtype), expert_out)


class MOELayer:
    """Dispatch → expert compute → combine (reference sharded_moe.py:417).

    ``expert_fn(expert_params_slice, x[C, D]) -> [C, D]`` is vmapped over the
    leading expert dim; expert params and the dispatched tensor are sharded
    over ``ep`` so each device computes only its local experts and XLA
    inserts the all-to-all pair.
    """

    def __init__(self, gate: TopKGate, expert_fn: Callable, num_local_experts: int = 1,
                 mesh=None):
        self.gate = gate
        self.expert_fn = expert_fn
        self.num_local_experts = num_local_experts
        self.mesh = mesh

    def __call__(self, params, x, rng=None, train: bool = True):
        """x [B, S, D] (or [T, D]) → same shape; returns (out, l_aux, exp_counts)."""
        orig_shape = x.shape
        D = orig_shape[-1]
        tokens = x.reshape(-1, D)
        l_aux, combine, dispatch, exp_counts = self.gate(params["gate"], tokens, rng=rng, train=train)
        out = dispatch_combine(tokens, combine, dispatch, self.expert_fn, params["experts"],
                               mesh=self.mesh)
        return out.reshape(orig_shape), l_aux, exp_counts
