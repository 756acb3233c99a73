"""Seeded weights, made on the device in one jitted call, in the type the
program holds them in (bf16 to serve, float32 to train: the engine keeps the
float32 master and casts its own bf16 copy).

The program's ``init_params`` leaves every bias 0 and every norm scale 1, and
a decoder whose biases are 0 cannot show a bias that is dropped or applied
twice. So biases get N(0, 0.02) and scales 1 + N(0, 0.1) on top of it.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _perturb(path, leaf, key):
    name = str(getattr(path[-1], "key", path[-1]))
    where = "/".join(str(getattr(p, "key", p)) for p in path)
    k = jax.random.fold_in(key, zlib.crc32(where.encode()) % (2 ** 31))
    if name == "scale":
        return leaf + 0.1 * jax.random.normal(k, leaf.shape, leaf.dtype)
    if name.startswith("b") and leaf.ndim <= 2:     # bias, bq.., b_up, b_down
        return leaf + 0.02 * jax.random.normal(k, leaf.shape, leaf.dtype)
    return leaf


def spread_shardings(shapes, devices):
    """One chip: None. Several: each leaf split along its first dimension
    that divides by the device count (else replicated), so that no chip ever
    holds the whole model while the engine takes it into its own layout."""
    n = len(devices)
    if n == 1:
        return None
    mesh = Mesh(np.array(devices), ("all",))

    def one(a):
        for i, d in enumerate(a.shape):
            if d % n == 0 and d >= n:
                return NamedSharding(mesh, P(*([None] * i + ["all"])))
        return NamedSharding(mesh, P())
    return jax.tree.map(one, shapes)


def make_params(model, seed: int, dtype, devices):
    def build(key):
        k_init, k_pert = jax.random.split(key)
        params = model.init_params(k_init)
        params = jax.tree_util.tree_map_with_path(
            lambda p, a: _perturb(p, a, k_pert), params)
        return jax.tree.map(lambda a: a.astype(dtype), params)

    key = jax.random.key(seed)
    shardings = spread_shardings(jax.eval_shape(build, key), devices)
    if shardings is None:
        return jax.jit(build)(key)
    return jax.jit(build, out_shardings=shardings)(key)
