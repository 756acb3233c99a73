"""Accelerator selection (reference: accelerator/real_accelerator.py:35-56).

Selection order:
1. explicit ``set_accelerator()``
2. ``DS_ACCELERATOR`` env var (``tpu`` | ``cpu``)
3. runtime probe: whatever ``jax.default_backend()`` reports.
"""

from __future__ import annotations

import os
from typing import Optional

from deepspeed_tpu.accelerator.abstract_accelerator import DeepSpeedAccelerator
from deepspeed_tpu.accelerator.tpu_accelerator import CPU_Accelerator, TPU_Accelerator

_accelerator: Optional[DeepSpeedAccelerator] = None


def is_current_accelerator_supported() -> bool:
    return get_accelerator()._name in ("tpu", "cpu")


def get_accelerator() -> DeepSpeedAccelerator:
    global _accelerator
    if _accelerator is not None:
        return _accelerator

    accelerator_name = os.environ.get("DS_ACCELERATOR", None)
    if accelerator_name is None:
        # a backend that fails to initialise is an error here, never "cpu"
        import jax
        accelerator_name = jax.default_backend()

    if accelerator_name == "cpu":
        _accelerator = CPU_Accelerator()
    else:
        _accelerator = TPU_Accelerator(platform=accelerator_name)
    return _accelerator


def set_accelerator(accel: DeepSpeedAccelerator) -> None:
    global _accelerator
    _accelerator = accel


def require_tpu() -> dict:
    """Device guard of the measurement entry points (``chip_smoke.py``,
    ``perfbench/run.py``): they exist to say something about the chip, so anything else is an error — no retry, no
    child probe, no CPU leg. Returns ``{"platform", "kind", "count"}`` as
    jax reports them when the default backend is a TPU whose ``device_kind``
    has a published peak and the selected accelerator agrees; raises
    ``RuntimeError`` naming what was found otherwise."""
    import jax

    backend = jax.default_backend()
    dev = jax.devices()[0]
    found = (f"jax.default_backend()={backend!r}, jax.devices()[0]="
             f"{dev.platform!r}/{dev.device_kind!r}, "
             f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}")
    if backend != "tpu" or dev.platform != "tpu":
        raise RuntimeError(f"no TPU: {found}")
    if dev.device_kind not in TPU_Accelerator.PEAK_BF16_TFLOPS:
        raise RuntimeError(
            f"TPU kind {dev.device_kind!r} has no entry in the peaks table "
            f"{sorted(TPU_Accelerator.PEAK_BF16_TFLOPS)}")
    acc = get_accelerator()
    if acc.device_name() != "tpu":
        raise RuntimeError(
            f"jax is on the TPU but the selected accelerator is "
            f"{acc.device_name()!r} (DS_ACCELERATOR="
            f"{os.environ.get('DS_ACCELERATOR')!r}); {found}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
