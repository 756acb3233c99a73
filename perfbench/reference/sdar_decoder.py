"""The plain reference of SDAR-MoE (``JetLM/SDAR-30B-A3B-Chat``,
``model_type`` ``sdar_moe``): a Qwen3-MoE stack that generates by diffusion
over blocks. Straight ``jax.numpy``, float32 under
``jax.default_matmul_precision("highest")``; no kernels, no cache, no
batching of rows: every held expert over every token, one at a time, and a
mask picks. Written from the family's description (SDAR: block diffusion
with bidirectional attention inside a block and causal attention between
blocks; Qwen3-MoE's ``config.json`` keys; rotary embeddings, Su et al.
2021), not from ``models/``. It answers the contract at the top of
``correctness.py`` and is fed the program's weights through its map.

The stack, for positions ``t[0..S)``, block length ``B`` (``cfg["block"]``),
``RMS(u; g) = u / sqrt(mean(u^2) + eps) * g``::

    x_0   = E[t]
    a     = RMS(x; g_1)
    q,k,v = a Wq, a Wk, a Wv            32 query, 4 key/value heads of 128
    q, k  = RMS_128(q) * g_q, RMS_128(k) * g_k   HEAD BY HEAD over its 128,
            the two weights of 128 shared by the heads
    q, k  : position m turns each head, pair (i, i + hd/2), theta 1e6
    s_ij  = q_i . k_j / sqrt(hd) ; masked unless j // B <= i // B:
            i sees all of its own block, both directions, and every earlier
            block; query head h reads key/value head h // (H / KV)
    h     = x + softmax_j(s) v Wo
    m     = RMS(h; g_2)
    r     = softmax(m Wr)               over all ``n_experts`` (128)
    top   = the K (8) experts of largest r ; c_e = r_e / sum_top r
    x     = h + sum over e in top AND HELD HERE of
                c_e ( silu(m Wgate_e) * (m Wup_e) ) Wdown_e
    out   = RMS(x_L; g_f) ; logits = out W_head   (untied, no bias)

``experts_held`` experts from ``expert_offset`` on are here (one chip's share
of an expert-parallel layer); an assignment to another is left out of the
sum, in the program and here alike, and the partial sum goes on to the next
layer. Nothing is dropped; no shared expert; no bias anywhere.

Generation (the family's ``block_diffusion_generate``), for a prompt
followed by ``[MASK]`` in blocks of B by absolute position:

1. the first ``B * (len(prompt) // B)`` prompt tokens are given (their
   activations under the mask above are what later blocks see);
2. each further block (its first ``len(prompt) % B`` positions may be
   prompt tokens, decided from the start) takes DENOISE passes: the block's
   B positions, decided tokens and ``[MASK]`` ids, forward against the
   final tokens of all earlier blocks; the logits AT EACH MASKED POSITION
   ITSELF (no next-token shift) give ``x0 = argmax`` and the confidence
   ``c = max softmax``; pass ``i`` of a block then decides ``n_i = B //
   steps`` (+1 on the first ``B % steps`` passes; never more than are
   masked) of the masked positions: ``sequential`` the leftmost,
   ``low_confidence_static`` the largest ``c`` (a tie to the leftmost),
   ``low_confidence_dynamic`` every one with ``c > threshold`` if at least
   ``n_i`` pass, else the static rule's;
3. once no position of the block is masked it is final (the program's
   commit pass writes its KV; here "final tokens" says the same) and the
   next block begins. Output is cut at ``max_new`` inside the last block.

**What ``final_hidden`` returns.** ``correctness.check_served`` hands over
``prompt + served`` with no prompt length, reads row ``len(prompt) - 1 + i``
for served token ``i`` and asks that the token's logit lie within 4 bf16
steps of that row's largest. So row ``r`` here is DEFINED as the hidden
state that DECIDED token ``r + 1``: position ``r + 1`` of a denoise pass over
its block with the block's earlier positions at their final tokens and
position ``r + 1`` and all after it at ``[MASK]``, against the final tokens
of every earlier block. That is a function of the tokens alone exactly when
one token is decided a pass, left to right (``sequential`` at ``steps`` =
B): then it does not depend on where the prompt ended inside the block.
Under a confidence order the row would have to redo the program's choice
of position (PERF.md section 7).

``final_hidden`` computes the activations of the whole blocks ONCE (the
"kept" pass: a plain forward under the block mask) and the S deciding
passes of B positions each against them, layer by layer. That is exact
under this mask, not an approximation: a block's positions see nothing
after their block, so the kept activations of block b are those of the
forward over ``t[0 .. (b+1)B)`` whatever follows, which is what a deciding
pass over a later block attends. ``naive_row`` is the whole-sequence
forward of one row, no sharing; a CPU test holds the two together.

Departures from the family's code, each noted because a reader comparing
would trip on it. **Masked-ness is state, not an id**: the loop here knows
which positions of a block are decided; a prompt token or an argmax that
equals ``mask_id`` is a token like any other (the family's loop compares
ids, so such a token would be taken for undecided and decided again; with
seeded weights one token in ``vocab`` hits it). ``mask_id`` is the last id
of the held vocabulary slice (the published 151,669 lies outside this
chip's eighth of the rows). The block length, the steps, the rule and the
threshold are the family's ``generate`` defaults or the configuration's
stated choice, not in ``config.json`` (the configuration file's
``assumed``). Rope in the half-split pairing (the ``transformers`` port's).
``next_token_loss`` is the mean cross-entropy of each token under the row
that decided it (rows as above), over the held slice, no auxiliary term:
training this model is not built, so no cell asks for it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

#: the layer weights that are stacked over experts and stay as stored
EXPERT_STACKS = ("w_gate", "w_up", "w_down")
RULES = ("sequential", "low_confidence_static", "low_confidence_dynamic")


class Weights:
    """The program's parameter tree under the reference's names: float32, one
    layer at a time, but for the three expert stacks [E, ., .], which stay in
    the stored type until :func:`expert` casts one expert's matrices."""

    def __init__(self, params, name_map: dict, device=None):
        self.params, self.map = params, name_map
        self.device = device or jax.devices()[0]
        self._top = None

    def _get(self, path: str):
        node = self.params
        for part in path.split("/"):
            node = node[part]
        return node

    def top(self) -> dict:
        if self._top is None:
            self._top = {
                k: jax.device_put(self._get(p), self.device).astype(jnp.float32)
                for k, p in self.map["top"].items()}
        return self._top

    def layer(self, l: int) -> dict:
        out = {}
        for k, p in self.map["layer"].items():
            a = jax.device_put(self._get(p)[l], self.device)
            out[k] = a if k in EXPERT_STACKS else a.astype(jnp.float32)
        return out


class _Cfg(dict):
    """A configuration jit can take as a static argument: the stack's sizes
    (how it generates is the loop's business, not a compiled layer's)."""

    def __init__(self, cfg):
        super().__init__({k: v for k, v in cfg.items()
                          if k not in ("rule", "steps", "threshold")})

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def rotate(u, pos, theta: float):
    """u [..., S, H, hd] turned head by head at positions ``pos`` [..., S]."""
    hd = u.shape[-1]
    half = hd // 2
    th = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / hd)
    ang = pos[..., None].astype(jnp.float32) * th              # [..., S, half]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    a, b = u[..., :half], u[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def project(cfg, w, x, pos):
    """x [..., S, D] at positions pos [..., S] -> (a = RMS(x), q [..., S, H,
    hd], k, v [..., S, KV, hd]): normed head by head, turned."""
    H, KV, hd = cfg["n_head"], cfg["n_kv_head"], cfg["head_dim"]
    a = _rms(x, w["ln1_g"], cfg["eps"])
    heads = lambda u, n: u.reshape(*u.shape[:-1], n, hd)       # noqa: E731
    q = _rms(heads(a @ w["wq"], H), w["q_g"], cfg["eps"])
    k = _rms(heads(a @ w["wk"], KV), w["k_g"], cfg["eps"])
    v = heads(a @ w["wv"], KV)
    return rotate(q, pos, cfg["rope_theta"]), rotate(k, pos, cfg["rope_theta"]), v


def attend(cfg, q, k, v, visible):
    """q [N, Sq, H, hd] over k, v [N, Sk, KV, hd]; visible [N, Sq, Sk] bool.
    Query head h reads key/value head h // (H / KV). Returns [N, Sq, H*hd]."""
    N, Sq, H, hd = q.shape
    KV = k.shape[2]
    q5 = q.reshape(N, Sq, KV, H // KV, hd)
    s = jnp.einsum("nickd,njcd->nckij", q5, k) / math.sqrt(hd)
    p = jax.nn.softmax(jnp.where(visible[:, None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("nckij,njcd->nickd", p, v).reshape(N, Sq, H * hd)


def route(cfg, w, h):
    """h [T, D] -> (m = RMS(h), c [T, n_experts]: each token's weight for
    the K experts it takes, normalised over all K, else 0)."""
    m = _rms(h, w["ln2_g"], cfg["eps"])
    r = jax.nn.softmax(m @ w["router"], axis=-1)
    _, top = jax.lax.top_k(r, cfg["experts_per_token"])
    chosen = jnp.sum(jax.nn.one_hot(top, cfg["n_experts"], dtype=r.dtype), axis=-2)
    c = r * chosen
    if cfg.get("norm_topk_prob", True):
        c = c / jnp.sum(c, axis=-1, keepdims=True)
    return m, c


def expert(m, c_e, w_gate, w_up, w_down):
    """One expert over every token, weighted by that token's c_e (0 for a
    token that did not choose it)."""
    w_gate, w_up, w_down = (a.astype(jnp.float32) for a in (w_gate, w_up, w_down))
    return c_e[..., None] * ((jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down)


_route = jax.jit(route, static_argnums=0)
_expert = jax.jit(expert)


def moe(cfg, w, h):
    """h [T, D] -> h + the held experts' part of the MoE."""
    cfg = _Cfg(cfg)
    m, c = _route(cfg, {k: v for k, v in w.items() if k not in EXPERT_STACKS}, h)
    out = h
    first = cfg.get("expert_offset", 0)
    for e in range(cfg.get("experts_held", cfg["n_experts"])):
        out = out + _expert(m, c[..., first + e], w["w_gate"][e], w["w_up"][e],
                            w["w_down"][e])
    return out


# ------------------------------------------------------------------ #
# the whole-sequence forward (the definition), and one row of it

def _full_attention(cfg, w, x):
    """x [1, S, D] -> x + Attn under the block mask."""
    S, B = x.shape[1], cfg["block"]
    pos = jnp.arange(S)[None]
    q, k, v = project(cfg, w, x, pos)
    blk = jnp.arange(S) // B
    return x + attend(cfg, q, k, v, (blk[None, :] <= blk[:, None])[None]) @ w["wo"]


_full_attention_jit = jax.jit(_full_attention, static_argnums=0)


def forward_full(cfg, weights, tokens):
    """tokens [S] -> RMS_f(x_L) [S, D]: the plain forward of a whole
    sequence under the block mask (mask ids, where the caller put them, are
    tokens like any other here)."""
    cfg = _Cfg(cfg)
    with jax.default_matmul_precision("highest"):
        top = weights.top()
        x = top["wte"][jnp.asarray(tokens)][None]
        for l in range(cfg["n_layer"]):
            w = weights.layer(l)
            small = {k: v for k, v in w.items() if k not in EXPERT_STACKS}
            h = _full_attention_jit(cfg, small, x)
            x = moe(cfg, w, h[0])[None]
        return _rms(x[0], top["lnf_g"], cfg["eps"])


def deciding_pass(cfg, tokens, r: int) -> np.ndarray:
    """The sequence of the pass that decided token ``r + 1`` of ``tokens``:
    the final tokens before it, ``[MASK]`` at it and to the end of its
    block."""
    B = cfg["block"]
    t = r + 1
    end = (t // B + 1) * B
    seq = np.full((end,), cfg["mask_id"], np.int64)
    seq[:t] = np.asarray(tokens)[:t]
    return seq


def naive_row(cfg, weights, tokens, r: int):
    """Row ``r`` of :func:`final_hidden` with nothing shared: the whole
    forward of the deciding pass's sequence, read at position ``r + 1``."""
    return forward_full(cfg, weights, deciding_pass(cfg, tokens, r))[r + 1]


# ------------------------------------------------------------------ #
# all rows at once: the kept activations once, the S passes against them

def _blockwise_attention(cfg, w, xk, xv):
    """One layer's attention for the kept blocks xk [nb, B, D] (block b at
    positions bB..) and the deciding passes xv [S, B, D] (row r's pass over
    the block of position r + 1): each pass reads the KEPT keys and values
    of the blocks before its own, and its own B positions."""
    nb, B, D = xk.shape
    S = xv.shape[0]
    kept_pos = jnp.arange(nb * B).reshape(nb, B)
    blk_of = (jnp.arange(S) + 1) // B                           # [S]
    var_pos = blk_of[:, None] * B + jnp.arange(B)[None]
    out_k = xk
    if nb:
        q, k, v = project(cfg, w, xk.reshape(1, nb * B, D),
                          kept_pos.reshape(1, -1))
        b_of = jnp.arange(nb * B) // B
        out_k = (xk.reshape(1, nb * B, D) + attend(
            cfg, q, k, v, (b_of[None, :] <= b_of[:, None])[None]) @ w["wo"]
        ).reshape(nb, B, D)
    qv, kv_, vv = project(cfg, w, xv, var_pos)
    if nb:
        KV, hd = k.shape[2], k.shape[3]
        ctx_k = jnp.broadcast_to(k, (S, nb * B, KV, hd))
        ctx_v = jnp.broadcast_to(v, (S, nb * B, KV, hd))
        keys = jnp.concatenate([ctx_k, kv_], axis=1)
        vals = jnp.concatenate([ctx_v, vv], axis=1)
        sees = jnp.concatenate([
            jnp.broadcast_to((b_of[None, :] < blk_of[:, None])[:, None, :],
                             (S, B, nb * B)),
            jnp.ones((S, B, B), bool)], axis=-1)
    else:
        keys, vals, sees = kv_, vv, jnp.ones((S, B, B), bool)
    out_v = xv + attend(cfg, qv, keys, vals, sees) @ w["wo"]
    return out_k, out_v


_blockwise_attention_jit = jax.jit(_blockwise_attention, static_argnums=0)


def final_hidden(cfg, weights, tokens):
    """tokens [N, S] -> [N, S, D]: row ``r`` the hidden state that DECIDED
    token ``r + 1`` (module docstring); ``weights`` gives ``top()`` and
    ``layer(l)`` dicts under the map's names. The N sequences go through
    the stack together, a layer's weights taken once: each its own
    attention, all their positions one batch of tokens for the experts."""
    cfg = _Cfg(cfg)
    tokens = np.asarray(tokens)
    N, S = tokens.shape
    B = cfg["block"]
    nb = S // B
    passes = np.stack([[deciding_pass(cfg, t, r)[-B:] for r in range(S)]
                       for t in tokens])                          # [N, S, B]
    with jax.default_matmul_precision("highest"):
        top = weights.top()
        xk = top["wte"][jnp.asarray(tokens[:, :nb * B]).reshape(N, nb, B)]
        xv = top["wte"][jnp.asarray(passes)]
        D = xv.shape[-1]
        for l in range(cfg["n_layer"]):
            w = weights.layer(l)
            small = {k: v for k, v in w.items() if k not in EXPERT_STACKS}
            hk, hv = zip(*(_blockwise_attention_jit(cfg, small, xk[n], xv[n])
                           for n in range(N)))
            both = moe(cfg, w, jnp.concatenate(
                [jnp.stack(hk).reshape(-1, D), jnp.stack(hv).reshape(-1, D)]))
            xk = both[:N * nb * B].reshape(N, nb, B, D)
            xv = both[N * nb * B:].reshape(N, S, B, D)
        at = (np.arange(S) + 1) % B
        return _rms(xv[:, np.arange(S), at], top["lnf_g"], cfg["eps"])


def logits_rows(cfg, weights, h_rows):
    """h_rows [N, D] -> logits [N, V] through the untied head over the held
    slice [D, V]."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(jnp.matmul)(h_rows, weights.top()["head"])


def next_token_loss(cfg, weights, tokens):
    """Mean cross-entropy of tokens [N, S] (from the second on), each under
    the row that decided it, over the held slice of the vocabulary."""
    h = final_hidden(cfg, weights, tokens)
    tokens = jnp.asarray(tokens)
    with jax.default_matmul_precision("highest"):
        logits = h[:, :-1] @ weights.top()["head"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
        return float(-jnp.mean(picked))


# ------------------------------------------------------------------ #
# generation, as the family's loop has it (naive: a whole forward a pass)

def transfers(cfg, i: int) -> int:
    B, steps = cfg["block"], cfg["steps"]
    return B // steps + (i < B % steps)


def generate(cfg, weights, prompt, max_new: int, record=None):
    """Greedy block-diffusion generation of ``max_new`` tokens after
    ``prompt`` under ``cfg["rule"]``, ``cfg["steps"]``, ``cfg["threshold"]``.
    ``record``: a list that takes, a denoise pass, ``(block start, decided
    flags before the pass [B], logits [B, V])``. Returns the new tokens."""
    B, rule = cfg["block"], cfg["rule"]
    if rule not in RULES:
        raise ValueError(f"rule {rule!r}")
    seq = [int(t) for t in np.asarray(prompt)]
    n_prompt = len(seq)
    want = n_prompt + max_new
    start = n_prompt // B * B
    while len(seq) < want:
        # the open block: what of the sequence lies inside it is decided
        tok = np.full((B,), cfg["mask_id"], np.int64)
        dec = np.zeros((B,), bool)
        inside = seq[start:start + B]
        tok[:len(inside)], dec[:len(inside)] = inside, True
        i = 0
        while not dec.all():
            h = forward_full(cfg, weights, np.concatenate(
                [np.asarray(seq[:start], np.int64),
                 np.where(dec, tok, cfg["mask_id"])]))[start:]
            logits = np.asarray(logits_rows(cfg, weights, h), np.float64)
            if record is not None:
                record.append((start, dec.copy(), logits))
            x0 = logits.argmax(-1)
            z = logits - logits.max(-1, keepdims=True)
            conf = 1.0 / np.exp(z).sum(-1)                     # max softmax
            masked = np.flatnonzero(~dec)
            n = min(transfers(cfg, i), masked.size)
            if rule == "sequential":
                take = masked[:n]
            else:
                # largest confidence first, a tie to the leftmost
                take = masked[np.argsort(-conf[masked], kind="stable")[:n]]
                passing = masked[conf[masked] > cfg["threshold"]]
                if rule == "low_confidence_dynamic" and passing.size >= n:
                    take = passing
            tok[take], dec[take] = x0[take], True
            i += 1
        seq = seq[:start] + [int(t) for t in tok]
        start += B
    return seq[n_prompt:want]
