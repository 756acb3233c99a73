"""MoE causal LM: transformer backbone with mixture-of-experts MLPs.

The model-zoo analogue of DeepSpeed-MoE models (reference ``deepspeed/moe/``
integrated into Megatron-style GPT). Every ``moe_freq``-th block replaces its
dense MLP with an expert-parallel MoE; the load-balancing aux loss is
accumulated across layers and added to the LM loss.

Layers are stacked and scanned like the dense backbone; expert weights carry
dims ``[n_moe_layers, num_experts, ...]`` sharded ``P(None, "ep", ...)``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.ops import dispatch
from deepspeed_tpu.ops.quant import Quantized8
from deepspeed_tpu.ops.pallas.grouped_expert_mlp import (MAX_ROWS,
                                                         dense_expert_mlp,
                                                         envelope_ok,
                                                         grouped_expert_mlp,
                                                         own_rows, row_tile,
                                                         touched_visits)
from deepspeed_tpu.utils.init_on_device import honors_on_device
from deepspeed_tpu.moe.sharded_moe import (dense_dispatch, dispatch_combine,
                                           sorted_dispatch, top1gating,
                                           top2gating, topk_balance_loss,
                                           topk_routing)

# The three forms of the no-drop experts (``_nodrop_mlp``; ``ops.dispatch``
# site ``experts``), chosen by the rows of a call and by nothing else.
#
# ``grouped_kernel``: a PAGED program's call of at most
# ``_GROUPED_KERNEL_MAX_ROWS`` rows (a decode step; a prefill bucket) where
# a bare Pallas call is legal (one device; ``T._use_flash``) reads the
# experts its rows chose from the layer stack in place
# (ops/pallas/grouped_expert_mlp.py): time goes with the TOUCHED experts'
# bytes. Measured on a v5e (benchmarks/moe_dispatch_bench.py --forms dense
# kernel [--touched N], device times under the layer scan; PERF.md section
# 6, PR 40, calls 1 and 6), ms a layer, dense | kernel (touched experts):
# SmallThinker's 16 rows x top-6 of 64 experts of 2,560 x 768: 1.007 | 0.802
# (50.75; 746 GB/s of the chip's 819), the routing folded onto 16 / 32 / 50:
# 1.007 | 0.261 (15.75), 1.007 | 0.483 (30.1), 1.007 | 0.661 (41.6); OLMoE's
# 64 rows x top-8 of 64 of 2,048 x 1,024: 1.123 | 1.076 (64: every expert
# touched and still ahead: no scan slice, 749 GB/s), 16 rows 1.188 | 0.931
# (55.25), 128 rows 1.127 | 1.081; Solar's 128 rows, 40 held of 320 of 4,096
# x 1,280: 1.811 | 1.712 (38.5); SDAR's 16 held of 128 of 2,048 x 768: 64
# rows 0.210 | 0.208, 128 rows 0.216 | 0.215 (16). jax's own megablox.gmm
# over the same whole stacks (three calls behind a sort): 0.898 at
# SmallThinker's 16 rows, 0.333 / 0.567 / 0.754 at 16 / 32 / 50. Up to 128
# rows every row rides every visit, one MXU row tile; past it a visit's
# products would outlast its weights' copy, so each expert computes its OWN
# rows, 32 at a time, gathered from and added back to the resident rows
# inside the kernel (PR 53). The same bench, PERF.md section 6, PR 53, call
# 1, ms a layer, dense | kernel, all experts touched: LFM2's 64 of 2,048 x
# 1,536, top-4: 256 rows 1.935 | 1.623, 384 rows 2.623 | 1.632, 512 rows
# 3.288 | 1.640; SDAR's 256 positions 0.2443 | 0.2154, 512 rows 0.4293 |
# 0.2237; Solar's 256 rows 1.962 | 1.800, 512 rows 3.631 | 1.888; OLMoE's
# 256 rows 1.243 | 1.082, 512 rows 2.219 | 1.133: ahead at every measured
# shape, so the number is the kernel's largest call, 512. Calls of 513 rows
# to the ragged side keep ``dense`` (Solar's 1,024 bucket: ROADMAP S13 (b)).
#
# ``dense`` (sharded_moe.dense_dispatch: every held expert over every row):
# every other call of fewer than ``_SORTED_DISPATCH_MIN_ROWS`` rows (more
# than 512 rows; a mesh; int8 experts; ungated experts; training and the
# dense-workspace cache), the kernel's plain-XLA twin and the CPU's form.
#
# ``ragged`` (sorted_dispatch over jax.lax.ragged_dot) from
# ``_SORTED_DISPATCH_MIN_ROWS`` rows on. Measured on a v5e at OLMoE's sizes
# with the layer's weights a scan's slices (the same bench, PERF.md section 6,
# PR 26, call 7), ms a layer, dense | sorted: 1 row 1.07 | 2.60, 16 rows
# 1.19 | 3.59, 64 rows 1.12 | 5.01, 512 rows 2.21 | 5.44, 1,024 rows 4.36 |
# 5.92, 1,536 rows 6.53 | 6.44, 2,048 rows 8.66 | 6.90. The dense form costs
# a pass over the weights or rows x E expert-rows of arithmetic, whichever is
# longer; the ragged form rows x k of them plus ~5 ms that no row count
# changes (XLA's ragged matmul takes a scan's slice as a copy and reads it at
# a fifth of the memory's rate). Where they cross is the chip's balance of
# arithmetic to memory and moves little with E and k (E / (E - 1.8 k)), so
# one number and no option; SmallThinker's 6-10 k-token prefills run the
# ragged side (ROADMAP S13 keeps its copy).
_SORTED_DISPATCH_MIN_ROWS = 1536
_GROUPED_KERNEL_MAX_ROWS = MAX_ROWS


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    k: int = 1
    capacity_factor: float = 1.25
    eval_capacity_factor: float = 2.0
    min_capacity: int = 4
    aux_loss_coef: float = 0.01
    noisy_gate_policy: Optional[str] = None
    drop_tokens: bool = True
    use_rts: bool = True
    expert_ff_mult: int = 4
    # an expert's width in its own right (OLMoE: 1,024 under d_model 2,048);
    # None = expert_ff_mult * d_model
    expert_d_ff: Optional[int] = None
    # "gelu": gelu(x W_up + b) W_down + b; "swiglu": the gated SiLU expert
    # W_down(silu(x W_gate) * (x W_up)), no bias anywhere; "reglu": the same
    # with a ReLU gate, W_down(relu(x W_gate) * (x W_up))
    expert_activation: str = "gelu"
    # "capacity": the GShard one-hot dispatch above (k 1 or 2, tokens over an
    # expert's capacity dropped, ep-sharded); "nodrop": softmax, then the k
    # largest of ANY k, every assignment computed (moe/sharded_moe.py
    # sorted_dispatch / dense_dispatch)
    dispatch: str = "capacity"
    norm_topk_prob: bool = False         # nodrop: divide the k weights by their sum
    # what that division adds to the sum (LFM2: 1e-6); None: the router's
    # own (``topk_routing``: 1e-20 under sigmoid scoring, else nothing)
    norm_topk_eps: Optional[float] = None
    # nodrop: how a token scores the experts before its k largest are taken:
    # "softmax" over them, or "sigmoid" of each logit with a learned bias
    # ``b_select`` added for the choice alone (DeepSeek-V3 / GLM-4-MoE)
    scoring: str = "softmax"
    # nodrop: what the router reads. "mlp_input": what the experts read (the
    # post-attention norm's output); "mixer_input": what the block's mixer
    # read (the first norm's output: a router placed before attention, whose
    # choice does not wait for the attention)
    router_input: str = "mlp_input"
    # THE CHIP'S SHARE of an expert-parallel layer (nodrop): the router
    # scores ``router_experts`` experts (None = num_experts: all are here),
    # of which this model HOLDS the ``num_experts`` from ``expert_offset``
    # on; an assignment to an expert held elsewhere is left out of the
    # result, as that chip's part of the sum. No exchange, nothing stands
    # in for the absent chips.
    router_experts: Optional[int] = None
    expert_offset: int = 0
    # a gated-SiLU expert every token takes beside its routed ones, of this
    # width (n_shared_experts x their width); 0 = none
    shared_expert_d_ff: int = 0
    # nodrop: the k weights times this, after any normalisation
    # (DeepSeek-V3's and LongCat's ``routed_scaling_factor``); 1.0 puts no
    # multiply into the program
    routed_scaling_factor: float = 1.0
    # nodrop: a selection bias under softmax scoring too (LongCat's
    # ``e_score_correction_bias``; sigmoid scoring always has one). Its leaf
    # is ``select_bias``, zero at init like ``b_select`` unless a preset's
    # seeded init gives ``select_bias_init_std``: a softmax router's scores
    # are ~1 / its width, and a seeded bias of that order moves a token's
    # marginal picks, as a trained balance correction does
    select_bias: bool = False
    select_bias_init_std: float = 0.0
    # ZERO-COMPUTE experts (LongCat-Flash): the router's LAST
    # ``zero_experts`` outputs, past its ``router_experts`` real ones, are
    # identity experts without weights: an assignment to one adds its
    # weight times the expert's INPUT. Every chip computes them for its own
    # rows (they are all "here", as a shared expert is)
    zero_experts: int = 0
    # SHORTCUT-CONNECTED MoE (LongCat-Flash): the stack's period is one
    # published layer of several sub-blocks, each with its own DENSE MLP;
    # the period's ONE MoE reads what the FIRST sub-block's dense MLP reads
    # (that sub-block's post-attention norm) and joins the residual stream
    # at the END of the period, with the last sub-block's dense MLP. Its
    # parameters lie under ``moe`` of the period's first group
    shortcut: bool = False
    # the router's draw is N(0, 1/d_model) times this: a seeded model's
    # logits have this standard deviation (over hundreds of outputs a
    # softmax of unit logits is nearly flat, and the k weights vanish)
    router_init_scale: float = 1.0
    # Residual (PR-)MoE, arXiv:2201.05596: each MoE MLP is blended with a
    # dense MLP through a learned 2-way softmax coefficient (reference
    # moe/layer.py use_residual + inference moe_type='residual')
    use_residual: bool = False


class MoECausalLM:
    """Causal LM where every block's MLP is an MoE layer, but for the
    LEADING layers of its stack (``config.lead_kinds``), which keep the
    dense MLPs ``transformer.init_params`` gives them."""

    def __init__(self, config: T.TransformerConfig, moe_config: MoEConfig = MoEConfig(),
                 param_dtype=jnp.float32, mesh=None):
        self.config = config
        self.moe = moe_config
        self.param_dtype = param_dtype
        self.mesh = mesh
        self.num_experts = moe_config.num_experts
        if moe_config.dispatch not in ("capacity", "nodrop"):
            raise ValueError(f"MoEConfig.dispatch={moe_config.dispatch!r} "
                             "(expected capacity|nodrop)")
        if moe_config.expert_activation not in ("gelu", "swiglu", "reglu"):
            raise ValueError("MoEConfig.expert_activation is gelu, swiglu "
                             "or reglu")
        if moe_config.router_input not in ("mlp_input", "mixer_input"):
            raise ValueError("MoEConfig.router_input is mlp_input or "
                             "mixer_input")
        if moe_config.dispatch == "capacity" and moe_config.k not in (1, 2):
            raise ValueError("the capacity dispatch routes top-1 or top-2; "
                             "k > 2 needs dispatch='nodrop'")
        if moe_config.dispatch == "nodrop" and (
                moe_config.use_residual or moe_config.noisy_gate_policy):
            raise ValueError("dispatch='nodrop' has no residual MLP and no "
                             "noisy gate")
        share = (moe_config.router_experts is not None
                 or moe_config.expert_offset or moe_config.shared_expert_d_ff
                 or moe_config.scoring != "softmax"
                 or moe_config.router_input != "mlp_input"
                 or moe_config.routed_scaling_factor != 1.0
                 or moe_config.select_bias or moe_config.zero_experts
                 or moe_config.shortcut)
        if share and moe_config.dispatch != "nodrop":
            raise ValueError("a share of the experts, a shared expert, "
                             "sigmoid scoring, a router that reads the "
                             "mixer's input, a scaling factor, a selection "
                             "bias, zero-compute experts and a shortcut MoE "
                             "need dispatch='nodrop'")
        if moe_config.shortcut and len(config.period) < 2:
            raise ValueError("a shortcut MoE spans a period of two or more "
                             "sub-blocks (config.layer_kinds)")
        if moe_config.expert_offset + moe_config.num_experts > self.router_width:
            raise ValueError(
                f"experts {moe_config.expert_offset}.."
                f"{moe_config.expert_offset + moe_config.num_experts} held "
                f"here lie outside the router's {self.router_width}")

    @property
    def expert_ff(self) -> int:
        return self.moe.expert_d_ff or self.moe.expert_ff_mult * self.config.d_model

    @property
    def _gated(self) -> bool:
        return self.moe.expert_activation in ("swiglu", "reglu")

    @property
    def real_router_width(self) -> int:
        """The router's outputs that are experts with weights."""
        return self.moe.router_experts or self.moe.num_experts

    @property
    def router_width(self) -> int:
        return self.real_router_width + self.moe.zero_experts

    @property
    def _select_bias(self) -> bool:
        return self.moe.scoring == "sigmoid" or self.moe.select_bias

    @property
    def _moe_root(self) -> str:
        """The key of a layer group the MoE's parameters lie under."""
        return "moe" if self.moe.shortcut else "mlp"

    @property
    def n_moe_layers(self) -> int:
        cfg = self.config
        return cfg.n_periods if self.moe.shortcut \
            else cfg.n_layer - len(cfg.lead_kinds)

    # -------------------- params -------------------- #

    @honors_on_device
    def init_params(self, rng) -> Dict[str, Any]:
        cfg = self.config
        base = T.init_params(cfg, rng, dtype=self.param_dtype)
        if self.moe.shortcut:
            # every sub-block keeps its dense MLP; the period's MoE beside
            # the first
            base["layers"][0]["moe"] = self._mlp_params(
                jax.random.fold_in(rng, 2000), cfg.n_periods)
        elif cfg.layer_kinds is None:
            base["layers"]["mlp"] = self._mlp_params(rng, cfg.n_periods)
        else:
            for j, group in enumerate(base["layers"]):
                group["mlp"] = self._mlp_params(
                    jax.random.fold_in(rng, 2000 + j), cfg.n_periods)
        return base

    def _mlp_params(self, rng, n: int) -> Dict[str, Any]:
        """The MoE MLP of ``n`` stacked layers."""
        cfg, moe = self.config, self.moe
        L, D = cfg.n_layer, cfg.d_model
        E = moe.num_experts
        F = self.expert_ff
        dt = self.param_dtype
        k1, k2, k3 = jax.random.split(jax.random.fold_in(rng, 999), 3)
        s_in, s_out = cfg.init_std, cfg.init_std / math.sqrt(2 * L)
        mlp = {
            "gate_w": (jax.random.normal(k1, (n, D, self.router_width))
                       * (moe.router_init_scale / math.sqrt(D))).astype(dt),
            "w_up": (jax.random.normal(k2, (n, E, D, F)) * s_in).astype(dt),
            "w_down": (jax.random.normal(k3, (n, E, F, D)) * s_out).astype(dt),
        }
        if self._gated:
            k7 = jax.random.fold_in(rng, 1003)
            mlp["w_gate"] = (jax.random.normal(k7, (n, E, D, F)) * s_in).astype(dt)
        else:
            mlp.update({"b_up": jnp.zeros((n, E, F), dt),
                        "b_down": jnp.zeros((n, E, D), dt)})
        if moe.scoring == "sigmoid":
            mlp["b_select"] = jnp.zeros((n, self.router_width), dt)
        elif moe.select_bias:
            kb = jax.random.fold_in(rng, 1007)
            mlp["select_bias"] = (
                jax.random.normal(kb, (n, self.router_width))
                * moe.select_bias_init_std).astype(dt)
        if moe.shared_expert_d_ff:
            Fs = moe.shared_expert_d_ff
            k8, k9, k10 = jax.random.split(jax.random.fold_in(rng, 1005), 3)
            mlp["shared"] = {
                "w_gate": (jax.random.normal(k8, (n, D, Fs)) * s_in).astype(dt),
                "w_up": (jax.random.normal(k9, (n, D, Fs)) * s_in).astype(dt),
                "w_down": (jax.random.normal(k10, (n, Fs, D)) * s_out).astype(dt)}
        if moe.use_residual:
            k4, k5, k6 = jax.random.split(jax.random.fold_in(rng, 1001), 3)
            mlp.update({
                "res_w_up": (jax.random.normal(k4, (n, D, F)) * s_in).astype(dt),
                "res_b_up": jnp.zeros((n, F), dt),
                "res_w_down": (jax.random.normal(k5, (n, F, D)) * s_out).astype(dt),
                "res_b_down": jnp.zeros((n, D), dt),
                "coef_w": (jax.random.normal(k6, (n, D, 2)) * 0.02).astype(dt),
                "coef_b": jnp.zeros((n, 2), dt),
            })
        return mlp

    def tp_specs(self) -> Dict[str, Any]:
        if self.config.layer_kinds is not None or self.moe.shared_expert_d_ff \
                or self._select_bias:
            # replicated: no sharded form of these stacks is built
            return T.replicated_specs(
                lambda: self.init_params(jax.random.key(0)))
        specs = T.tp_specs(self.config)
        specs["layers"]["mlp"] = {
            "gate_w": P(None, None, None),
            "w_up": P(None, "ep", None, "tp"),
            "w_down": P(None, "ep", "tp", None),
            **({"w_gate": P(None, "ep", None, "tp")} if self._gated else
               {"b_up": P(None, "ep", "tp"), "b_down": P(None, "ep", None)}),
        }
        if self.moe.use_residual:
            specs["layers"]["mlp"].update({
                "res_w_up": P(None, None, "tp"), "res_b_up": P(None, "tp"),
                "res_w_down": P(None, "tp", None), "res_b_down": P(None, None),
                "coef_w": P(None, None, None), "coef_b": P(None, None),
            })
        return specs

    # -------------------- forward -------------------- #

    def _expert_keys(self):
        return ("w_gate", "w_up", "w_down") if self._gated else \
            ("w_up", "b_up", "w_down", "b_down")

    def _act(self, up, gate=None):
        """``up`` with its bias already on; ``gate`` for the gated expert."""
        if self.moe.expert_activation == "reglu":
            return jax.nn.relu(gate) * up
        if self._gated:
            return jax.nn.silu(gate) * up
        return jax.nn.gelu(up, approximate=True)

    def _moe_mlp(self, lp, x, rng, train: bool, used_token=None,
                 with_owed: bool = False, mixer_in=None, stack=None):
        """x [B,S,D] → ([B,S,D], l_aux, counts) via top-k expert routing.
        ``used_token`` [B*S] 1/0 keeps masked tokens away from the experts
        (nodrop: any k; capacity: top-1 only, the reference's top-2 gate has
        no mask either). ``counts`` [E] int32: the assignments each expert
        HELD HERE computed for rows that are ``used_token`` (what the decode
        program hands the engine's ``serving/moe_*`` counters).
        ``with_owed``: fourth, the assignments the layer owed (those of
        ``used_token`` rows to experts held here). ``mixer_in`` [B,S,D]:
        what the block's mixer read, which the router reads in x's place
        under ``router_input="mixer_input"``. ``stack``: see
        ``_nodrop_mlp``."""
        if self.moe.dispatch == "nodrop":
            out = self._nodrop_mlp(
                lp, x, used_token,
                mixer_in if self.moe.router_input == "mixer_input" else None,
                stack)
        else:
            used = x.shape[0] * x.shape[1] if used_token is None \
                else jnp.sum(used_token > 0, dtype=jnp.int32)
            out = (*self._capacity_mlp(lp, x, rng, train, used_token),
                   used * self.moe.k)
        return out if with_owed else out[:3]

    def _route(self, lp, tokens):
        """tokens [T, D] -> (weights [T, k] float32, experts [T, k] int32 as
        indices into the experts HELD HERE, ``num_experts`` for one held
        elsewhere or a zero-compute expert, scores [T, router width], and
        fourth which assignments [T, k] chose a zero-compute expert; None
        for a model without)."""
        moe = self.moe
        # float32 for real: on the chip a default-precision float32
        # matmul rounds its operands to bf16
        logits = jnp.dot(tokens.astype(jnp.float32),
                         lp["gate_w"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        # the epsilon only where the configuration states one: the calls of
        # every other preset stay what stand-ins written for them take
        eps = {} if moe.norm_topk_eps is None \
            else {"norm_eps": moe.norm_topk_eps}
        if not self._select_bias:
            weights, experts, probs = topk_routing(logits, moe.k,
                                                   moe.norm_topk_prob, **eps)
        else:
            weights, experts, probs = topk_routing(
                logits, moe.k, moe.norm_topk_prob, scoring=moe.scoring,
                select_bias=lp["b_select" if moe.scoring == "sigmoid"
                               else "select_bias"], **eps)
        if moe.routed_scaling_factor != 1.0:
            weights = weights * moe.routed_scaling_factor
        zero = experts >= self.real_router_width if moe.zero_experts else None
        if self.router_width != moe.num_experts:
            local = experts - moe.expert_offset
            experts = jnp.where((local >= 0) & (local < moe.num_experts),
                                local, moe.num_experts)
        return weights, experts, probs, zero

    def _grouped_kernel(self, params, rows: int) -> bool:
        """Whether a paged program's calls of ``rows`` rows take the grouped
        expert kernel (``_GROUPED_KERNEL_MAX_ROWS``): a rule on static
        shapes and on what a bare ``pallas_call`` may be handed, chosen as
        the paged kernel is (``T._use_flash``), never by the model."""
        cfg = self.config
        groups = params["layers"] if cfg.layer_kinds is not None \
            else (params["layers"],)
        root = self._moe_root
        return (self.moe.dispatch == "nodrop" and self._gated
                and rows <= _GROUPED_KERNEL_MAX_ROWS and T._use_flash(cfg)
                and (envelope_ok(rows, cfg.d_model, self.expert_ff)
                     or not dispatch.on_tpu())
                # a Quantized8 leaf keeps the XLA forms (T._w dequantises)
                and not any(isinstance(g[root][k], Quantized8)
                            for g in groups if root in g
                            for k in self._expert_keys()))

    def expert_row_tile(self, params, rows: int) -> int:
        """The rows of an expert's own a visit of the grouped kernel computes
        at a time in a paged program's call of ``rows`` rows; 0 where all
        rows ride every visit or the call takes another form (what the
        engine's ``serving/moe_expert_row_tiles`` counts by)."""
        return row_tile(rows) if self._grouped_kernel(params, rows) else 0

    def _nodrop_mlp(self, lp, x, valid=None, route_x=None, stack=None):
        """A score an expert in float32 (softmax, or sigmoid with a
        selection bias), the k largest as they are, every assignment to an
        expert held here computed, in one of three forms (``ops.dispatch``
        site ``experts``): ``grouped_kernel``, the touched experts read from
        the layer stack in place (``stack = (leaves [n, E, ...] whole, the
        layer's index in them)``: a paged program whose rows
        ``_grouped_kernel`` accepts); ``dense``, every held expert over
        every row, for any other call of fewer than
        ``_SORTED_DISPATCH_MIN_ROWS`` rows; ``ragged``, rows sorted into
        groups (``jax.lax.ragged_dot``), from there on; then the shared
        expert, if the model has one, and the zero-compute experts (a row's
        weights for them times the row itself). Scopes ``router`` /
        ``moe_dispatch`` / ``experts`` / ``shared_expert`` / ``zero_experts``
        name the parts in a device trace. ``route_x``: what the router reads
        where that is not ``x``. Returns (out, l_aux, counts [E], owed); a
        model with zero-compute experts fifth [2] int32: the assignments of
        ``valid`` rows to them, and all the router made for those rows."""
        moe = self.moe
        B, S, D = x.shape
        E = moe.num_experts
        tokens = x.reshape(-1, D)
        rows = tokens.shape[0]
        form = "grouped_kernel" if stack is not None else \
            "dense" if rows < _SORTED_DISPATCH_MIN_ROWS else "ragged"
        # a kernel call past one row tile: the rows of an expert's own a
        # visit computes at a time (``row_tile``), else 0
        tm = row_tile(rows) if stack is not None else 0
        dispatch.record("experts", form,
                        f"rows={rows} k={moe.k} E={E} D={D} F={self.expert_ff}"
                        + (f" tm={tm}" if tm else ""))
        with jax.named_scope("router"):
            weights, experts, probs, zero = self._route(
                lp, tokens if route_x is None else route_x.reshape(-1, D))
        if self.router_width == E:
            owed = (rows if valid is None
                    else jnp.sum(valid, dtype=jnp.int32)) * moe.k
        else:
            held = experts < E
            if valid is not None:
                held = held & valid.astype(bool)[:, None]
            owed = jnp.sum(held, dtype=jnp.int32)
        relu = moe.expert_activation == "reglu"
        if stack is None:
            p = {k: T._w(lp[k], tokens) for k in self._expert_keys()}

        def grouped(xs, sizes):
            """xs [T*k, D] sorted by expert, ``sizes`` [E] rows a group."""
            with jax.named_scope("experts"):
                dot = lambda a, w: jax.lax.ragged_dot(a, w, sizes)  # noqa: E731
                if self._gated:
                    h = self._act(dot(xs, p["w_up"]), dot(xs, p["w_gate"]))
                    return dot(h.astype(xs.dtype), p["w_down"])
                row = jnp.repeat(jnp.arange(E), sizes,
                                 total_repeat_length=xs.shape[0])
                h = self._act(dot(xs, p["w_up"]) + p["b_up"][row])
                return dot(h.astype(xs.dtype), p["w_down"]) + p["b_down"][row]

        def dense(xs, combine):
            """Every expert over every row of xs [T, D]; combine [T, E]."""
            with jax.named_scope("experts"):
                if self._gated:
                    return dense_expert_mlp(xs, combine, p["w_gate"],
                                            p["w_up"], p["w_down"], relu=relu)
                h = self._act(jnp.einsum(
                    "td,edf->tef", xs, p["w_up"],
                    preferred_element_type=jnp.float32) + p["b_up"][None])
                out = jnp.einsum(
                    "tef,efd->td", (h * combine[:, :, None]).astype(xs.dtype),
                    p["w_down"], preferred_element_type=jnp.float32)
                return out + combine @ p["b_down"]

        def kernel(xs, combine):
            """The touched experts over every row (past one row tile: each
            over its own rows), the stacks in place."""
            whole, layer = stack
            with jax.named_scope("moe_dispatch"):
                visits = touched_visits(combine)
                own = own_rows(combine, tm) if tm else None
            with jax.named_scope("experts"):
                w = {k: a.reshape(-1, *a.shape[2:]) for k, a in whole.items()}
                return grouped_expert_mlp(
                    xs, combine, w["w_gate"], w["w_up"], w["w_down"],
                    layer * E, relu=relu, visits=visits, rows=own)

        if form == "ragged":
            # a share computes ~E / width of its rows' assignments: four
            # times that many rows of the sorted order, or all of it
            cap = 0 if self.router_width == E else \
                -(-4 * rows * moe.k * E // self.router_width // 128) * 128
            out, counts = sorted_dispatch(tokens, weights, experts, E, grouped,
                                          valid, cap=cap)
        else:
            out, counts = dense_dispatch(
                tokens, weights, experts, E,
                kernel if form == "grouped_kernel" else dense, valid)
        if self.router_width == E:
            l_aux = topk_balance_loss(probs, counts, moe.k)
        else:
            # a share sees its own experts' loads only: the loss is the
            # whole layer's, taken where all shares meet (training across
            # the ep axis is not built)
            l_aux = jnp.zeros((), jnp.float32)
        if moe.shared_expert_d_ff:
            with jax.named_scope("shared_expert"):
                sp = {k: T._w(w, tokens) for k, w in lp["shared"].items()}
                out = out + (jax.nn.silu(tokens @ sp["w_gate"])
                             * (tokens @ sp["w_up"])) @ sp["w_down"]
        if zero is None:
            return out.reshape(B, S, D), l_aux, counts, owed
        with jax.named_scope("zero_experts"):
            wz = jnp.sum(jnp.where(zero, weights, 0.0), axis=1, keepdims=True)
            out = out + (wz * tokens.astype(jnp.float32)).astype(out.dtype)
            real = jnp.ones((rows,), bool) if valid is None \
                else valid.astype(bool)
            routed = jnp.stack([
                jnp.sum(zero & real[:, None], dtype=jnp.int32),
                jnp.sum(real, dtype=jnp.int32) * moe.k])
        return out.reshape(B, S, D), l_aux, counts, owed, routed

    def _capacity_mlp(self, lp, x, rng, train: bool, used_token=None):
        moe = self.moe
        B, S, D = x.shape
        tokens = x.reshape(-1, D)
        if train and moe.noisy_gate_policy == "Jitter" and rng is not None:
            tokens = tokens * jax.random.uniform(rng, tokens.shape, minval=0.99, maxval=1.01)
        logits = tokens.astype(jnp.float32) @ lp["gate_w"].astype(jnp.float32)
        cf = moe.capacity_factor if train else moe.eval_capacity_factor
        if moe.k == 1:
            l_aux, combine, dispatch, _ = top1gating(
                logits, cf, moe.min_capacity, used_token,
                moe.noisy_gate_policy if train else None, moe.drop_tokens,
                # RTS is a TRAINING regularizer: eval/serving routes
                # deterministically (positional capacity priority), matching
                # the reference's inference kernels — and without the
                # no-rng fallback warning in every serving process
                moe.use_rts and train, rng=rng)
        else:
            l_aux, combine, dispatch, _ = top2gating(logits, cf, moe.min_capacity,
                                                     moe.drop_tokens, rng=rng)
        # assignments that kept a capacity slot, rows that are real only
        kept = dispatch if used_token is None else \
            dispatch & (used_token > 0)[:, None, None]
        counts = jnp.sum(kept, axis=(0, 2), dtype=jnp.int32)

        def expert(p, xe):
            # T._w dequantises int8 Quantized8 expert weights transparently
            up = xe @ T._w(p["w_up"], xe)
            if self._gated:
                return self._act(up, xe @ T._w(p["w_gate"], xe)) @ T._w(p["w_down"], xe)
            return self._act(up + p["b_up"]) @ T._w(p["w_down"], xe) + p["b_down"]

        eps = {k: lp[k] for k in self._expert_keys()}
        combined = dispatch_combine(tokens, combine, dispatch, expert, eps, mesh=self.mesh)
        if moe.use_residual:
            # PR-MoE blend (reference moe/layer.py:115-123): dense MLP +
            # 2-way softmax coefficient over [moe, dense]
            h = jax.nn.gelu(tokens @ T._w(lp["res_w_up"], tokens) + lp["res_b_up"],
                            approximate=True)
            res = h @ T._w(lp["res_w_down"], tokens) + lp["res_b_down"]
            coef = jax.nn.softmax(tokens @ lp["coef_w"] + lp["coef_b"], axis=-1)
            combined = combined * coef[..., 0:1] + res * coef[..., 1:2]
        return combined.reshape(B, S, D), l_aux, counts

    def _block(self, x, lp, positions, mask_bias, rng, train: bool):
        cfg = self.config
        k_route = ka = km = None
        if rng is not None:
            if cfg.dropout and train:
                k_route, ka, km = jax.random.split(rng, 3)
            else:
                k_route = rng
        xa = T._norm(cfg, x, lp["ln_attn"])
        a = T.attention(cfg, xa, lp["attn"], positions, mask_bias)
        x = x + T._dropout(cfg, a, ka)
        m, l_aux, _ = self._moe_mlp(lp["mlp"], T._norm(cfg, x, lp["ln_mlp"]),
                                    k_route, train, mixer_in=xa)
        return x + T._dropout(cfg, m, km), l_aux

    def forward(self, params, tokens, attn_mask=None, rng=None, train: bool = True):
        cfg = self.config
        T._paged_path_only(cfg, "the training forward (and its scan's backward)")
        B, S = tokens.shape
        x = params["embed"]["tokens"][tokens]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
        if cfg.pos_embedding == "learned":
            x = x + params["embed"]["positions"][:S][None, :, :]
        mask_bias = T.key_mask_bias(attn_mask)
        # No rng means no stochastic routing: RTS/Jitter would otherwise draw
        # the same permutation every step from a constant key, silently biasing
        # which tokens get dropped at capacity (top1gating's own rng=None path
        # makes the same choice).
        def run_block(carry, scan_in):
            h, aux = carry
            lp, i = scan_in
            block_rng = None if rng is None else jax.random.fold_in(rng, i)
            h, l_aux = self._block(h, lp, positions, mask_bias, block_rng, train)
            return (h, aux + l_aux), None

        if cfg.remat:
            run_block = jax.checkpoint(run_block, prevent_cse=False)
        (x, aux_total), _ = jax.lax.scan(run_block, (x, jnp.zeros((), jnp.float32)),
                                         (params["layers"], jnp.arange(cfg.n_layer)))

        x = T._norm(cfg, x, params["ln_f"])
        if cfg.tie_embeddings:
            logits = x @ params["embed"]["tokens"].T
        else:
            logits = x @ T._w(params["lm_head"], x)
        return logits, aux_total / cfg.n_layer

    # -------------------- KV-cache serving path -------------------- #

    def init_cache(self, batch_size: int, max_len: Optional[int] = None,
                   dtype=jnp.bfloat16) -> Dict[str, Any]:
        return T.init_kv_cache(self.config, batch_size, max_len, dtype)

    def forward_cached(self, params, tokens, cache, pos, pad_bias=None,
                       valid=None):
        """Incremental MoE decode (reference DeepSpeedMoEInference serving,
        ops/transformer/inference/moe_inference.py) on the shared cached
        path with the MoE MLP slotted in: attention runs against the KV
        cache, the MLP routes the step's tokens with eval capacity.
        ``valid`` [B, T] (1 = real token) keeps prefill bucket PADDING out
        of the expert-capacity competition (top1 used_token; top-2 has no
        mask, same as the reference). Routing capacity is per call, so with
        drop_tokens at tight capacity a decoded step can drop differently
        than the same token inside one long forward — the reference's
        per-forward capacity semantics."""
        used = None if valid is None else valid.reshape(-1)

        def moe_mlp_fn(cfg, x_normed, lp, mixer_in):
            return self._moe_mlp(lp["mlp"], x_normed, None, train=False,
                                 used_token=used, mixer_in=mixer_in)[0]

        return T.forward_cached(self.config, params, tokens, cache, pos,
                                pad_bias, mlp_fn=moe_mlp_fn)

    # ---- paged KV serving: transformer.forward_paged_* with the MoE MLP ----
    # A position the engine routes to the dummy block (a prompt bucket's
    # padding, an empty decode or verify row) reaches no expert and is
    # counted nowhere (T.paged_real_rows).

    def init_paged_cache(self, num_blocks: int, block_size: int,
                         dtype=jnp.bfloat16, state_slots: int = 0,
                         window_blocks: Optional[int] = None) -> Dict[str, Any]:
        return T.init_paged_kv_cache(self.config, num_blocks, block_size, dtype,
                                     state_slots=state_slots,
                                     window_blocks=window_blocks)

    def _paged(self, params, pools, slots, counts: bool = False):
        """The ``mlp_fn`` of a ``transformer.forward_paged_*`` call whose
        positions write to ``slots``. ``counts``: it also returns [E + 1]
        int32, the assignments each expert computed and, last, those the
        layer owed (real rows x k). Where the call's rows take the grouped
        kernel (``_grouped_kernel``) the function asks the layer scan for
        the expert stacks whole (``stack_keys``)."""
        used = T.paged_real_rows(pools, slots).reshape(-1)
        root = self._moe_root

        def moe_fn(cfg, x_normed, lp, mixer_in, stack=None):
            with jax.named_scope("mlp"):
                out, _, n, owed, *routed = self._moe_mlp(
                    lp[root], x_normed, None, train=False, used_token=used,
                    with_owed=True, mixer_in=mixer_in, stack=stack)
            if not counts:
                return out
            aux = jnp.append(n, owed)
            return out, jnp.concatenate([aux, *routed]) if routed else aux

        mlp_fn = moe_fn
        if self.moe.shortcut:
            def mlp_fn(cfg, x_normed, lp, mixer_in, held, last, stack=None):
                """A sub-block's dense MLP. The period's MoE rides beside it
                as ``held`` (``carries``: the layer scan hands a sub-block
                what the one before returned): computed on what the dense
                MLP reads by the sub-block whose group holds it, the first,
                and added by the ``last``."""
                out, aux = T.mlp(cfg, x_normed, lp["mlp"]), None
                if root in lp:
                    with jax.named_scope("shortcut_moe"):
                        held = moe_fn(cfg, x_normed, lp, mixer_in, stack)
                    held, aux = held if counts else (held, None)
                if last:
                    out = out + held
                return (out if aux is None else (out, aux)), held
            mlp_fn.carries = True
        if self._grouped_kernel(params, used.shape[0]):
            mlp_fn.stack_keys = self._expert_keys()
            mlp_fn.stack_root = root
        return mlp_fn

    def forward_paged_prefill(self, params, tokens, pools, slots, last_idx,
                              state_slot=None, window_table=None):
        mlp_fn = self._paged(params, pools, slots)
        return T.forward_paged_prefill(self.config, params, tokens, pools,
                                       slots, last_idx, mlp_fn=mlp_fn,
                                       state_slot=state_slot,
                                       window_table=window_table)

    def forward_paged_prefill_chunk(self, params, tokens, pools,
                                    block_tables, slots, start_pos, last_idx,
                                    state_slot=None):
        mlp_fn = self._paged(params, pools, slots)
        return T.forward_paged_prefill_chunk(
            self.config, params, tokens, pools, block_tables, slots,
            start_pos, last_idx, mlp_fn=mlp_fn, state_slot=state_slot)

    def forward_paged_verify(self, params, tokens, pools, block_tables,
                             slots, pos):
        mlp_fn = self._paged(params, pools, slots)
        return T.forward_paged_verify(self.config, params, tokens, pools,
                                      block_tables, slots, pos, mlp_fn=mlp_fn)

    def forward_paged_decode(self, params, tokens, pools, block_tables, pos,
                             pad_bias=None, state_slots=None,
                             window_tables=None):
        """(logits [B, vocab], new pools, counts [L, E + 1]): third, the
        assignments each expert of each MoE layer computed in this step and,
        in column E, those the layer owed (real rows x k; a share: those to
        experts held here): what the engine's ``serving/moe_*`` counters are
        fed. A model with zero-compute experts adds two columns: the
        assignments they took, and all the router made."""
        bs = T.pool_geometry(pools)[1]
        slots = block_tables[jnp.arange(pos.shape[0]), pos // bs] * bs + pos % bs
        mlp_fn = self._paged(params, pools, slots, counts=True)
        return T.forward_paged_decode(self.config, params, tokens, pools,
                                      block_tables, pos, pad_bias, mlp_fn=mlp_fn,
                                      state_slots=state_slots,
                                      window_tables=window_tables)

    def forward_paged_block(self, params, tokens, pools, block_tables, pos,
                            n_logits=None):
        """(logits [n_logits or N, Bg, vocab], new pools, counts [L, E +
        1]) of one pass of block generation: as ``forward_paged_decode``,
        with every entry's Bg positions each a row of the experts' work
        (a rider's too: its positions are computed like any other's)."""
        bs = pools["k"].shape[2]
        slots = (block_tables[jnp.arange(pos.shape[0]), pos // bs] * bs
                 + pos % bs)[:, None] + jnp.zeros_like(tokens)
        mlp_fn = self._paged(params, pools, slots, counts=True)
        return T.forward_paged_block(self.config, params, tokens, pools,
                                     block_tables, pos, mlp_fn=mlp_fn,
                                     n_logits=n_logits)

    def loss(self, params, batch, rng=None):
        logits, aux = self.forward(params, batch["input_ids"], batch.get("attention_mask"),
                                   rng=rng, train=True)
        tokens = batch["input_ids"]
        labels = batch.get("labels")
        if labels is None:
            labels = jnp.concatenate([tokens[:, 1:], jnp.full_like(tokens[:, :1], -100)], axis=1)
        logits = logits.astype(jnp.float32)
        valid = labels != -100
        safe = jnp.where(valid, labels, 0)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        lm = jnp.sum(nll * valid) / jnp.maximum(jnp.sum(valid), 1)
        return lm + self.moe.aux_loss_coef * aux

    @property
    def num_parameters(self) -> int:
        cfg, moe = self.config, self.moe
        D, E = cfg.d_model, moe.num_experts
        F = self.expert_ff
        embed = cfg.vocab_size * D + (cfg.max_seq * D if cfg.pos_embedding == "learned" else 0)
        moe_mlp = D * self.router_width \
            + E * (3 * D * F if self._gated else 2 * D * F + F + D)
        if self._select_bias:
            moe_mlp += self.router_width                   # the bias
        moe_mlp += 3 * D * moe.shared_expert_d_ff
        norms = (4 if cfg.norm == "layernorm" else 2) * D \
            * (2 if cfg.norm_position == "sandwich" else 1)
        final_norm = (2 if cfg.norm == "layernorm" else 1) * D
        head = 0 if cfg.tie_embeddings else D * cfg.vocab_size
        # a shortcut MoE: one a period, and a dense MLP in every sub-block;
        # a leading layer's MLP is dense (gated or not, as the stack's are)
        n_lead = len(cfg.lead_kinds)
        dense = (cfg.n_layer - n_lead) * 3 * D * cfg.ff_dim \
            if moe.shortcut else 0
        dense += n_lead * T.dense_mlp_params(cfg, cfg.lead_d_ff)
        return embed + self.n_moe_layers * moe_mlp + cfg.n_layer * norms \
            + dense + T.mixer_params(cfg) + final_norm + head
