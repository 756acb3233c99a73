"""``"autotuning"`` config section.

Reference parity: ``deepspeed/autotuning/config.py``
(``DeepSpeedAutotuningConfig``) and ``constants.py`` — same key names where
the concept carries over (enabled/fast/metric/tuner_type/num_trials/
early-stopping/mbs bounds/results_dir), plus the TPU-native search axes
(remat policies, loss-chunk sizes) the reference does not have.
"""

from __future__ import annotations

from typing import List, Optional

from pydantic import Field

from deepspeed_tpu.config.config_utils import ConfigModel

METRIC_THROUGHPUT = "throughput"
METRIC_LATENCY = "latency"

TUNER_GRIDSEARCH = "gridsearch"
TUNER_RANDOM = "random"
TUNER_MODELBASED = "model_based"


class AutotuningConfig(ConfigModel):
    enabled: bool = False
    fast: bool = True                      # fast mode: micro-batch only, fixed policies
    metric: str = Field(METRIC_THROUGHPUT, pattern="^(throughput|latency)$")
    tuner_type: str = Field(TUNER_GRIDSEARCH,
                            pattern="^(gridsearch|random|model_based)$")
    # model_based: how many spread-out survivors seed the cost model before
    # prediction starts steering the measure order
    tuner_num_seed_trials: int = Field(3, ge=1)
    # trial cap for random/model_based tuners. gridsearch deliberately
    # IGNORES it (a stage-major cut would drop whole ZeRO stages) and
    # measures the full cross product zero_stages × micro-batches ×
    # remat_policies × loss_chunks × scan_layers_options × attn_blocks —
    # every extra option in any axis MULTIPLIES wall-time, so widen one
    # axis at a time (early stopping only bounds the tail, not the grid)
    tuner_num_trials: int = Field(50, ge=1)
    tuner_early_stopping: int = Field(5, ge=1)
    results_dir: str = "autotuning_results"
    overwrite: bool = True

    # measurement window (reference start/end_profile_step)
    start_profile_step: int = Field(2, ge=0)
    end_profile_step: int = Field(6, ge=1)

    # search-space bounds
    min_train_micro_batch_size_per_gpu: int = Field(1, ge=1)
    max_train_micro_batch_size_per_gpu: Optional[int] = None  # None = probe upward
    zero_stages: List[int] = [1, 2, 3]
    remat_policies: List[str] = ["none", "dots", "selective", "full"]
    loss_chunks: List[int] = [0, 2048]
    # layer-stacking search: the default [None] keeps the model's setting
    # out of the grid — searching it DOUBLES every gridsearch (see
    # tuner_num_trials above), which silently doubled wall-time for every
    # tunable model when [True, False] was the default. Opt in with
    # [True, False] to re-discover the chip-measured ~12% unrolled win.
    scan_layers_options: List = [None]
    # flash-attention block override candidates (0 = the kernel's default);
    # e.g. [0, 512, 1024] re-discovers the measured 1024-block win at S=2048
    attn_blocks: List[int] = [0]

    # per-device HBM budget for the static prune; None = ask the device,
    # fall back to 16 GiB
    hbm_budget_bytes: Optional[int] = None
    # fraction of the budget usable by one step's live buffers (leaves room
    # for fragmentation + runtime overheads)
    hbm_fraction: float = Field(0.9, gt=0, le=1)
