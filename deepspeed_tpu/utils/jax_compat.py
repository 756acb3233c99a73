"""The one import site of ``shard_map`` (``jax.shard_map``, with its
``check_vma`` keyword, as the installed jax 0.9 spells it)."""

from jax import shard_map

__all__ = ["shard_map"]
