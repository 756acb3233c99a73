"""Operations and bytes of latent (MLA) attention, from shapes and from what
was COUNTED: beside ``costs.py``, for configurations whose map carries
``kv_lora_rank``, ``qk_rope_head_dim``, ``qk_nope_head_dim`` and
``v_head_dim``.

``shapes`` is the runner's dict (the map's sizes) plus the operand a run
counted, per decode step: ``live_tokens`` (cached tokens of the live rows,
what ONE cache layer reads). Nothing is counted that an implementation may
skip: the tail of a row's last block and the idle rows' dummy block cost
nothing here, so a share of this roofline cannot pass 100%. The same counted
work whatever implements the attention, a kernel or XLA's gather and einsums.
"""

from __future__ import annotations

BF16 = 2


def latent_row(shapes) -> int:
    """Values a token keeps in one cache layer."""
    return shapes["kv_lora_rank"] + shapes["qk_rope_head_dim"]


def latent_decode(shapes):
    """One cache layer of one decode step, the key/value expansion absorbed:
    each live row of ``latent_row`` bf16 values read ONCE (it is key and
    value); every head scores it over all its lanes and sums its first
    ``kv_lora_rank`` as values: 2 x (row + kv_lora_rank) operations a head
    and cached token. The absorbed query and the value expansion (kv_b_proj's
    two halves over the step's rows) are not counted."""
    toks = shapes["live_tokens"]
    per_head = latent_row(shapes) + shapes["kv_lora_rank"]
    return (2.0 * toks * shapes["n_head"] * per_head,
            toks * latent_row(shapes) * BF16)


def latent_prefill(shapes):
    """One cache layer over ONE prompt of ``prompt_tokens``, the latent
    expanded to heads: causal scores over keys of ``qk_nope_head_dim +
    qk_rope_head_dim`` and values of ``v_head_dim`` (half of the S x S
    grid); reads q, k and v and writes the output once, in bf16."""
    s, h = shapes["prompt_tokens"], shapes["n_head"]
    dqk = shapes["qk_nope_head_dim"] + shapes["qk_rope_head_dim"]
    dv = shapes["v_head_dim"]
    return (2.0 * h * s * s * 0.5 * (dqk + dv),
            h * s * (2 * dqk + 2 * dv) * BF16)
