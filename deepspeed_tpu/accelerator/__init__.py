from deepspeed_tpu.accelerator.abstract_accelerator import DeepSpeedAccelerator
from deepspeed_tpu.accelerator.real_accelerator import (get_accelerator,
                                                        require_tpu,
                                                        set_accelerator)

__all__ = ["DeepSpeedAccelerator", "get_accelerator", "require_tpu",
           "set_accelerator"]
