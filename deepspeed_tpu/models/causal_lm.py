"""Generic causal-LM wrapper over the shared transformer backbone."""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import transformer as T


class CausalLM:
    """A causal language model ready for ``deepspeed_tpu.initialize``.

    batch: dict(input_ids[B,S] int32, optional labels, attention_mask).
    """

    def __init__(self, config: T.TransformerConfig, param_dtype=jnp.float32):
        self.config = config
        self.param_dtype = param_dtype

    def init_params(self, rng) -> Dict[str, Any]:
        from deepspeed_tpu.runtime import zero
        from deepspeed_tpu.utils.init_on_device import materialize_params
        ctx = zero.active_init()
        init = lambda r: T.init_params(self.config, r, dtype=self.param_dtype)
        if ctx is not None:
            # inside `with zero.Init(...)`: materialise ZeRO-3-sharded, the
            # full tree never exists on any single device/host
            return ctx.materialize(init, rng, tp_specs=self.tp_specs())
        return materialize_params(init, rng)

    def forward(self, params, tokens, attn_mask=None):
        return T.forward(self.config, params, tokens, attn_mask)

    def __call__(self, params, tokens, attn_mask=None):
        return self.forward(params, tokens, attn_mask)

    def loss(self, params, batch, rng=None):
        """Training loss; ``rng`` (threaded by the engine's train path)
        enables cfg.dropout — eval/inference paths pass None and stay
        deterministic. The vocab head dispatches per
        ``cfg.fused_cross_entropy``: the fused logits-free Pallas CE kernel
        by default on TPU, the ``cfg.loss_chunk`` XLA streaming path
        elsewhere (transformer.py ``vocab_head_ce``)."""
        return T.lm_loss(self.config, params, batch, rng=rng)

    def tp_specs(self) -> Dict[str, Any]:
        return T.tp_specs(self.config)

    # ---- KV-cache inference (see transformer.forward_cached) ----

    def init_cache(self, batch_size: int, max_len: Optional[int] = None,
                   dtype=jnp.bfloat16) -> Dict[str, Any]:
        return T.init_kv_cache(self.config, batch_size, max_len, dtype)

    def forward_cached(self, params, tokens, cache, pos, pad_bias=None):
        return T.forward_cached(self.config, params, tokens, cache, pos, pad_bias)

    # ---- paged KV serving (see transformer.forward_paged_*) ----

    def init_paged_cache(self, num_blocks: int, block_size: int,
                         dtype=jnp.bfloat16, state_slots: int = 0,
                         window_blocks: Optional[int] = None) -> Dict[str, Any]:
        return T.init_paged_kv_cache(self.config, num_blocks, block_size, dtype,
                                     state_slots=state_slots,
                                     window_blocks=window_blocks)

    def forward_paged_prefill(self, params, tokens, pools, slots, last_idx,
                              state_slot=None, window_table=None):
        return T.forward_paged_prefill(self.config, params, tokens, pools,
                                       slots, last_idx, state_slot=state_slot,
                                       window_table=window_table)

    def forward_paged_prefill_chunk(self, params, tokens, pools,
                                    block_tables, slots, start_pos, last_idx,
                                    state_slot=None):
        return T.forward_paged_prefill_chunk(self.config, params, tokens,
                                             pools, block_tables, slots,
                                             start_pos, last_idx,
                                             state_slot=state_slot)

    def forward_paged_decode(self, params, tokens, pools, block_tables, pos,
                             pad_bias=None, state_slots=None,
                             window_tables=None):
        return T.forward_paged_decode(self.config, params, tokens, pools,
                                      block_tables, pos, pad_bias,
                                      state_slots=state_slots,
                                      window_tables=window_tables)

    def forward_paged_verify(self, params, tokens, pools, block_tables,
                             slots, pos):
        return T.forward_paged_verify(self.config, params, tokens, pools,
                                      block_tables, slots, pos)

    def forward_paged_block(self, params, tokens, pools, block_tables, pos,
                            n_logits=None):
        return T.forward_paged_block(self.config, params, tokens, pools,
                                     block_tables, pos, n_logits=n_logits)

    @property
    def num_parameters(self) -> int:
        cfg = self.config
        embed = cfg.vocab_size * cfg.d_model + (cfg.max_seq * cfg.d_model if cfg.pos_embedding == "learned" else 0)
        n_lead = len(cfg.lead_kinds)
        mlps = (cfg.n_layer - n_lead) * T.dense_mlp_params(cfg) \
            + n_lead * T.dense_mlp_params(cfg, cfg.lead_d_ff)
        norms = (4 if cfg.norm == "layernorm" else 2) * cfg.d_model \
            * (2 if cfg.norm_position == "sandwich" else 1)
        final_norm = (2 if cfg.norm == "layernorm" else 1) * cfg.d_model
        if cfg.embed_layernorm:
            final_norm += (2 if cfg.norm == "layernorm" else 1) * cfg.d_model
        head = 0 if cfg.tie_embeddings else cfg.d_model * cfg.vocab_size
        return embed + mlps + cfg.n_layer * norms + T.mixer_params(cfg) \
            + final_norm + head

    def flops_per_token(self, seq_len: Optional[int] = None) -> float:
        """Approximate training FLOPs/token (6N + attention term)."""
        cfg = self.config
        s = seq_len or cfg.max_seq
        n = self.num_parameters
        return 6.0 * n + 12.0 * cfg.n_layer * cfg.d_model * s
