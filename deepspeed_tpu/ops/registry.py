"""Op-builder registry.

Reference parity: ``op_builder/builder.py`` + ``accelerator.create_op_builder``
— a named registry of kernel families with compatibility probing and lazy
loading. On TPU there is no JIT C++ compilation against torch; device ops are
Pallas/XLA (imported lazily, compiled by XLA on first trace) and host ops are
C++ shared libraries built once via ``make`` and loaded with ctypes.

Builder names keep the reference spelling (``CPUAdamBuilder`` etc.) so code
and configs that probe ops by name port over.
"""

from __future__ import annotations

import importlib
from typing import Dict, Optional, Type

from deepspeed_tpu.utils.logging import logger


class OpBuilder:
    """Base op builder: probe availability + load the op module."""

    BUILD_VAR = "DS_BUILD_OPS"
    NAME = "op"
    # python module (relative to deepspeed_tpu) that implements the op family
    MODULE: Optional[str] = None

    def __init__(self):
        self.error_log: Optional[str] = None

    def is_compatible(self, verbose: bool = True) -> bool:
        if self.MODULE is None:
            return False
        try:
            importlib.import_module(self.MODULE)
            return True
        except Exception as e:  # pragma: no cover - env specific
            self.error_log = str(e)
            if verbose:
                logger.warning(f"op {self.NAME} incompatible: {e}")
            return False

    def load(self, verbose: bool = True):
        if self.MODULE is None:
            raise RuntimeError(f"Op {self.NAME} has no implementation module")
        return importlib.import_module(self.MODULE)

    def builder_available(self) -> bool:
        return self.is_compatible(verbose=False)


class NativeOpBuilder(OpBuilder):
    """Host-side C++ op loaded via ctypes from a shared library.

    The library is built from ``csrc/`` with ``make`` (no torch cpp_extension
    involved) by :func:`deepspeed_tpu.ops.native.ensure_library`, which only
    accepts an on-disk file built from the current sources on this host.
    """

    def build(self, verbose: bool = True) -> bool:
        from deepspeed_tpu.ops import native
        try:
            native.ensure_library()
            return True
        except (OSError, RuntimeError) as e:
            self.error_log = f"native build failed: {e}"
            if verbose:
                logger.warning(self.error_log)
            return False

    def is_compatible(self, verbose: bool = True) -> bool:
        return self.build(verbose=verbose)

    def load(self, verbose: bool = True):
        if not self.build(verbose=verbose):
            raise RuntimeError(f"Could not build native library for {self.NAME}: {self.error_log}")
        return importlib.import_module(self.MODULE)


# --------------------------------------------------------------------- #
# Concrete builders (names mirror op_builder/*.py)

class CPUAdamBuilder(NativeOpBuilder):
    NAME = "cpu_adam"
    MODULE = "deepspeed_tpu.ops.adam.cpu_adam_binding"


class CPUAdagradBuilder(NativeOpBuilder):
    NAME = "cpu_adagrad"
    MODULE = "deepspeed_tpu.ops.adagrad.cpu_adagrad_binding"


class AsyncIOBuilder(NativeOpBuilder):
    NAME = "async_io"
    MODULE = "deepspeed_tpu.ops.aio.aio_binding"


class UtilsBuilder(OpBuilder):
    NAME = "utils"
    MODULE = "deepspeed_tpu.ops.flatten"


class FusedAdamBuilder(OpBuilder):
    NAME = "fused_adam"
    MODULE = "deepspeed_tpu.ops.adam.fused_adam_kernel"


class FusedLambBuilder(OpBuilder):
    NAME = "fused_lamb"
    MODULE = "deepspeed_tpu.ops.lamb.fused_lamb_kernel"


class QuantizerBuilder(OpBuilder):
    NAME = "quantizer"
    MODULE = "deepspeed_tpu.ops.quantizer.kernels"


class RandomLTDBuilder(OpBuilder):
    NAME = "random_ltd"
    MODULE = "deepspeed_tpu.ops.random_ltd.dropping_utils"


class SparseAttnBuilder(OpBuilder):
    NAME = "sparse_attn"
    MODULE = "deepspeed_tpu.ops.sparse_attention.kernels"


class TransformerBuilder(OpBuilder):
    NAME = "transformer"
    MODULE = "deepspeed_tpu.ops.transformer.training_kernels"


class StochasticTransformerBuilder(OpBuilder):
    NAME = "stochastic_transformer"
    MODULE = "deepspeed_tpu.ops.transformer.training_kernels"


class InferenceBuilder(OpBuilder):
    NAME = "transformer_inference"
    MODULE = "deepspeed_tpu.ops.transformer.inference_kernels"


class SpatialInferenceBuilder(OpBuilder):
    NAME = "spatial_inference"
    MODULE = "deepspeed_tpu.ops.spatial.kernels"


_BUILDERS: Dict[str, Type[OpBuilder]] = {
    cls.__name__: cls
    for cls in (CPUAdamBuilder, CPUAdagradBuilder, AsyncIOBuilder, UtilsBuilder, FusedAdamBuilder, FusedLambBuilder,
                QuantizerBuilder, RandomLTDBuilder, SparseAttnBuilder, TransformerBuilder,
                StochasticTransformerBuilder, InferenceBuilder, SpatialInferenceBuilder)
}


def get_builder_class(class_name: str) -> Optional[Type[OpBuilder]]:
    return _BUILDERS.get(class_name)


def all_builder_names():
    return sorted(_BUILDERS)


def op_report() -> Dict[str, bool]:
    """Compatibility matrix for ds_report (reference env_report.py)."""
    return {name: cls().builder_available() for name, cls in _BUILDERS.items()}
