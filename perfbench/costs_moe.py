"""Operations and bytes of a mixture-of-experts layer, from shapes and from
what was COUNTED: beside ``costs.py``, for configurations whose map carries
``n_experts``, ``experts_per_token`` and ``d_expert``.

``shapes`` is the runner's dict (the map's sizes); the expert-matmul cost
also takes the two operands a run counted, per MoE layer of one decode step:
``assignments`` ((row, expert) pairs computed) and ``experts_touched``
(experts with at least one row). Nothing is counted that a kernel may skip:
an expert no row chose costs nothing here, whether or not the program read
its weights, so a share of this roofline cannot pass 100%.
"""

from __future__ import annotations

BF16 = 2


def expert_matmuls(shapes):
    """The gated expert's three matrices (gate, up: [d, f]; down: [f, d]) for
    one MoE layer of one step: every assignment multiplies through all three,
    and each touched expert's three matrices are read once. Activations (a
    few rows of d and f) are not counted."""
    d, f = shapes["d_model"], shapes["d_expert"]
    return (shapes["assignments"] * 3 * 2.0 * d * f,
            shapes["experts_touched"] * 3 * d * f * BF16)


def active_param_count(dims: dict) -> int:
    """Parameters a token's matmuls touch in an OLMoE-like decoder: no bias,
    four attention matrices, the router, ``experts_per_token`` gated experts,
    and an untied head (the embedding is a lookup)."""
    d, L, f = dims["d_model"], dims["n_layer"], dims["d_expert"]
    per_layer = 4 * d * d + d * dims["n_experts"] \
        + dims["experts_per_token"] * 3 * d * f
    return L * per_layer + dims["vocab"] * d


def train_flops_per_token(dims: dict, seq: int) -> float:
    """6 x the parameters a token touches + 12 L d S for attention scores
    and values; recomputation is not counted."""
    return 6.0 * active_param_count(dims) \
        + 12.0 * dims["n_layer"] * dims["d_model"] * seq
