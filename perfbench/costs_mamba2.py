"""Operations and bytes of the Mamba-2 (SSD) layer's state work, from shapes
and from what was COUNTED: beside ``costs.py``, for configurations whose map
carries ``ssm_heads``, ``ssm_head_dim`` and ``ssm_state``.

``shapes`` is the runner's dict (the map's sizes) plus the operand a run
counted, per decode step: ``state_rows`` (live rows whose state the step
updates). Nothing is counted that an implementation may skip: a slot no live
row holds costs nothing here, whether or not the program moved it, so a share
of this roofline cannot pass 100%. The same counted work whatever implements
the update, XLA fusions or a kernel.
"""

from __future__ import annotations

BF16, F32 = 2, 4


def _state_elems(shapes) -> int:
    """Elements of ONE row's state in one layer: heads x channels x state."""
    return shapes["ssm_heads"] * shapes["ssm_head_dim"] * shapes["ssm_state"]


def ssd_decode_update(shapes):
    """One Mamba-2 layer of one decode step: each live row's float32 state
    ``[ssm_heads, ssm_head_dim, ssm_state]`` read once and written once; an
    element takes about 6 operations (the decay's product, the rank-one
    term's two products and its sum into the state, ``S C``'s product and
    sum). The rows' vectors (x, B, C, dt: a few KB a row) are not counted."""
    elems = shapes["state_rows"] * _state_elems(shapes)
    return 6.0 * elems, 2 * elems * F32


def ssd_chunk_scan(shapes):
    """One Mamba-2 layer over ONE prompt of ``prompt_tokens`` in chunks of
    ``ssm_chunk``, the last chunk what is left of the prompt, as the program
    runs it: a chunk of Q tokens takes the matmuls ``C B^T`` (2 Q Q N, shared
    by the heads), ``(C B^T * L)(dt x)`` (2 Q Q P a head), the carried
    state's part ``C S`` (2 Q P N a head) and the chunk's own state ``(dt
    x)^T B`` (2 Q P N a head). Bytes: the state read and written once a chunk
    in float32, and the chunk's x, B, C in and y out in bf16. The pairwise
    decays (Q Q a head, exponentials on the VPU) are not matmul operations
    and are not counted."""
    T = shapes["prompt_tokens"]
    whole, rest = divmod(T, shapes["ssm_chunk"])
    H, P, N = shapes["ssm_heads"], shapes["ssm_head_dim"], shapes["ssm_state"]
    flops = nbytes = 0.0
    for Q in [shapes["ssm_chunk"]] * whole + [rest] * bool(rest):
        flops += 2.0 * Q * Q * N + H * (2.0 * Q * Q * P + 4.0 * Q * P * N)
        nbytes += 2 * _state_elems(shapes) * F32 + Q * (2 * H * P + 2 * N) * BF16
    return flops, nbytes
