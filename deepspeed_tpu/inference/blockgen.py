"""Generation by diffusion over blocks: what a pass decides.

A model whose config carries a ``BlockGeneration`` record
(``models/transformer.py``) generates a block of ``Bg`` positions by
denoise passes and one commit pass (``transformer.forward_paged_block``
is both). A row's open block is ONE int32 vector of ``Bg``: a decided
position holds its token, an undecided one -1. **Masked-ness is this
state, not a token id**: a prompt token or an argmax that equals the
model's mask id is a token like any other; the mask id only stands in
for an undecided position at the embedding (``feed``).

Device side (ops of the serving step's one program): ``feed`` resolves
each row's state from the step before, still on the device, or from the
host's; ``unmask`` takes the pass's logits to the next state. Host side:
``open_block`` is the state a row enters a block with.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

UNDECIDED = -1


def feed(prev, idx, host):
    """Row i's block state: ``prev[idx[i]]`` (what the pass in flight left
    for it, on the device) where ``idx[i] >= 0``, else the host's
    ``host[i]``. prev, host [W, Bg] int32; idx [W] int32."""
    return jnp.where(idx[:, None] >= 0, prev[jnp.maximum(idx, 0)], host)


def tokens_of(gen, state):
    """What the pass embeds: a decided position's token, the mask id at an
    undecided one."""
    return jnp.where(state >= 0, state, jnp.int32(gen.mask_id))


def unmask(gen, logits, state, n_decide, commit, draw=None):
    """One pass's decision, on the device. logits [W, Bg, V] at every
    position of the rows' blocks (each position's OWN: there is no
    next-token shift); state [W, Bg] int32 as fed; n_decide [W] the
    positions this pass decides at least (``gen.transfers`` of the row's
    pass, 0 for a commit row); commit [W] bool, the rows whose block was
    whole before this pass: their output is dropped and they enter the next
    block, all undecided. ``draw``: ``logits [N, V] -> tokens [N]`` under a
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
