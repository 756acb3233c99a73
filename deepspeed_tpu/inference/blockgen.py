"""Generation by diffusion over blocks: what a pass decides.

A model whose config carries a ``BlockGeneration`` record
(``models/transformer.py``) generates a block of ``Bg`` positions by
denoise passes and one commit of its final tokens' k/v
(``transformer.forward_paged_block`` is both: what it runs over is
ENTRIES, each a block of some row). A row's open block is ONE int32
vector of ``Bg``: a decided position holds its token, an undecided one
-1. **Masked-ness is this state, not a token id**: a prompt token or an
argmax that equals the model's mask id is a token like any other; the
mask id only stands in for an undecided position at the embedding
(``tokens_of``).

A fused step has one MAIN entry a row (the block the row's pass decides
in) and ``ride_slots`` RIDER entries behind them: a row whose block is
whole and whose request goes on takes its NEXT block's first denoise pass
in its main entry, at ``pos + Bg``, and the whole block rides the same
launch as a rider at ``pos`` over the same block table (the commit: its
k/v stays, it decides nothing and has no logits). Every entry's k/v is in
the pools before any entry's queries read, and an entry reads up to its
own block's end, so that is, layer by layer, the commit pass followed by
the denoise pass. A whole block that finds no rider slot is committed by
its row's main entry alone (``commit``), as every block was before.

Device side (ops of the serving step's one program): ``feed`` resolves
each entry's state from the step before, still on the device, or from the
host's; ``unmask`` takes the main entries' logits to their next state.
Host side: ``open_block`` is the state a row enters a block with,
``ride_slots`` the rider entries of the one program width.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

UNDECIDED = -1


def ride_slots(gen, rows: int) -> int:
    """Rider entries of a fused step over ``rows`` rows: a row's block is
    whole after one of its ``gen.steps`` denoise passes, so a step finds
    about ``rows / gen.steps`` whole blocks; up to whole sublane tiles of
    entries (8), so the entries' axis stays tiled."""
    return -(-(-(-rows // gen.steps)) // 8) * 8


def feed(prev, idx, host):
    """Entry i's block state: ``prev[idx[i]]`` (what the pass in flight
    left at its main entry ``idx[i]``, on the device) where ``idx[i] >=
    0``, else the host's ``host[i]``. prev [W, Bg] int32 (the main entries
    alone keep a state); idx [N] int32, host [N, Bg] int32 over all ``N =
    W + ride_slots`` entries."""
    return jnp.where(idx[:, None] >= 0, prev[jnp.maximum(idx, 0)], host)


def tokens_of(gen, state):
    """What the pass embeds: a decided position's token, the mask id at an
    undecided one."""
    return jnp.where(state >= 0, state, jnp.int32(gen.mask_id))


def unmask(gen, logits, state, n_decide, commit, draw=None):
    """One pass's decision, on the device, over the W main entries (a
    rider decides nothing: it has no logits and no next state). logits [W,
    Bg, V] at every position of the rows' blocks (each position's OWN:
    there is no next-token shift); state [W, Bg] int32 as fed; n_decide
    [W] the positions this pass decides at least (``gen.transfers`` of the
    row's pass, 0 for a lone commit); commit [W] bool, the rows that commit
    ALONE in this step (a whole block that found no rider slot; an idle
    row): their output is dropped and they enter the next block, all
    undecided. A row whose commit rides is a denoise row here: its main
    entry is its next block. ``draw``: ``logits [N, V] -> tokens [N]`` under a
    temperature (the logits it draws from are then what the confidence is
    taken over), None = greedy. Returns the next state [W, Bg]."""
    with jax.named_scope("unmask"):
        W, Bg, V = logits.shape
        logits = logits.astype(jnp.float32)
        x0 = (jnp.argmax(logits, axis=-1) if draw is None
              else draw(logits.reshape(W * Bg, V)).reshape(W, Bg)
              ).astype(jnp.int32)
        masked = state < 0
        at = jnp.arange(Bg, dtype=jnp.int32)
        if gen.rule == "sequential":
            score = jnp.broadcast_to(-at.astype(jnp.float32), (W, Bg))
        else:
            # confidence: the chosen token's softmax probability (greedy:
            # the largest)
            chosen = jnp.take_along_axis(logits, x0[..., None], axis=-1)[..., 0]
            score = conf = jnp.exp(
                chosen - jax.nn.logsumexp(logits, axis=-1))
        score = jnp.where(masked, score, -jnp.inf)
        # a masked position's rank in the rule's order among the masked
        # ones (a tie goes to the leftmost): Bg is a handful
        si, sj = score[:, None, :], score[:, :, None]
        before = (si > sj) | ((si == sj) & (at[None, None, :] < at[None, :, None]))
        rank = jnp.sum(before & masked[:, None, :], axis=-1)
        take = masked & (rank < n_decide[:, None])
        if gen.data_dependent:
            passing = masked & (conf > gen.threshold)
            enough = jnp.sum(passing, axis=-1) >= n_decide
            take = jnp.where(enough[:, None], passing, take)
        new = jnp.where(take, x0, state)
        return jnp.where(commit[:, None], jnp.int32(UNDECIDED), new)


def open_block(gen, prefix, start: int) -> np.ndarray:
    """The state a row enters the block at ``start`` with: the tokens of
    ``prefix`` (prompt + what was streamed) that lie inside it are decided
    from the start, the rest undecided."""
    state = np.full((gen.block,), UNDECIDED, np.int32)
    inside = np.asarray(prefix[start:start + gen.block], np.int32)
    state[:inside.size] = inside
    return state
