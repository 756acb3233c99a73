"""Pallas TPU kernels — the device-side native-op tranche.

TPU-native replacements for the reference's CUDA kernel families
(SURVEY.md §2.2): attention/softmax (``csrc/transformer/softmax_kernels.cu``,
inference ``softmax_context``) → :mod:`flash_attention`; the vocab head's
fused softmax-xent (``csrc/transformer/inference`` fused logits) →
:mod:`fused_cross_entropy`; quantization with stochastic rounding
(``csrc/quantization/``) → :mod:`quantization`; fused optimizer step
(``csrc/adam/multi_tensor_adam.cu``) → :mod:`fused_adam`.

Every kernel runs compiled on TPU and in interpreter mode on CPU (that is
what the unit suite exercises); the wrappers pick automatically.
"""

from deepspeed_tpu.ops.pallas.decode_attention import decode_attention
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
from deepspeed_tpu.ops.pallas.fused_cross_entropy import fused_cross_entropy
from deepspeed_tpu.ops.pallas.grouped_expert_mlp import grouped_expert_mlp
from deepspeed_tpu.ops.pallas.kda_decode_update import kda_decode_update
from deepspeed_tpu.ops.pallas.latent_decode_attention import \
    latent_decode_attention
from deepspeed_tpu.ops.pallas.mamba2_decode_update import \
    mamba2_decode_update
from deepspeed_tpu.ops.pallas.paged_decode_attention import \
    paged_decode_attention

__all__ = ["decode_attention", "flash_attention", "fused_cross_entropy",
           "grouped_expert_mlp", "kda_decode_update", "latent_decode_attention",
           "mamba2_decode_update",
           "paged_decode_attention"]
