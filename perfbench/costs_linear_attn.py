"""Operations and bytes of the linear-attention (KDA) layer's state update,
from shapes and from what was COUNTED: beside ``costs.py``, for configurations
whose map carries ``lin_heads`` and ``lin_head_dim``.

``shapes`` is the runner's dict (the map's sizes) plus the operand a run
counted, per decode step: ``state_rows`` (live rows whose state the step
updates). Nothing is counted that a kernel may skip: a slot no live row holds
costs nothing here, whether or not the program moved it, so a share of this
roofline cannot pass 100%.
"""

from __future__ import annotations

F32 = 4


def kda_decode_update(shapes):
    """One KDA layer of one decode step: each live row's state, ``lin_heads``
    matrices of dk x dv in float32, read once and written once; an element
    takes about 7 operations (the decay, two products and sums for S'^T k and
    S'^T q, the rank-one update's product and sum). The rows' vectors (a few
    times dk a head) are not counted."""
    elems = shapes["state_rows"] * shapes["lin_heads"] * shapes["lin_head_dim"] ** 2
    return 7.0 * elems, 2 * elems * F32
