"""Operations and bytes of attention in a stack whose layers are FULL in
part and WINDOWED in part, from shapes and from what was COUNTED: beside
``costs.py``, for configurations whose map carries ``window``,
``n_full_attn_layer`` and ``n_window_layer``.

``shapes`` is the runner's dict (the map's sizes) plus the operands a run
counted. Nothing is counted that a kernel may skip: a key before a row's
window, a block no row holds, the padding behind a prompt cost nothing here,
whether or not the program read them, so a share of these rooflines cannot
pass 100%.
"""

from __future__ import annotations

BF16 = 2


def window_paged_decode(shapes):
    """The paged attention of ONE LAYER of one decode step, the mean over
    the stack's layers (the reader multiplies by ``n_layer``): a full layer
    reads each of a row's cached keys and values once
    (``live_kv_tokens``: the sum of the rows' positions), a window layer
    the last ``window`` of them (``live_window_kv_tokens``: the sum of
    min(pos + 1, window)). Bytes: tokens x 2 x kv_heads x head_dim x 2 B;
    operations 4 x tokens x heads x head_dim, far under them."""
    nf, nw = shapes["n_full_attn_layer"], shapes["n_window_layer"]
    t = (shapes["live_kv_tokens"] * nf
         + shapes["live_window_kv_tokens"] * nw) / (nf + nw)
    h, hd = shapes["n_head"], shapes["head_dim"]
    return 4.0 * t * h * hd, 2 * t * shapes["n_kv_head"] * hd * BF16


def flash_fwd_band(shapes):
    """The banded forward flash kernel of ONE window layer over a prompt
    bucket of ``bucket`` tokens (the mean of the window's prefills): a head
    computes the scores of the band ``i - window < j <= i``, ``W S - W^2 /
    2`` of them (``S^2 / 2`` where the bucket is inside the window), each 2
    multiply-adds in q k^T and 2 in p v over ``head_dim``; reads q, k, v and
    writes o once."""
    s, w = shapes["bucket"], shapes["window"]
    scores = w * s - w * w / 2.0 if s > w else s * s / 2.0
    h, kv, hd = shapes["n_head"], shapes["n_kv_head"], shapes["head_dim"]
    return 4.0 * scores * h * hd, 2 * s * (h + kv) * hd * BF16
