"""Device-mesh construction for all parallel axes.

This is the TPU-native replacement for the reference's process-group world
(``deepspeed/comm/comm.py:179`` ``new_group`` + ``deepspeed/utils/groups.py``):
instead of explicit NCCL communicators per parallel dimension, one
``jax.sharding.Mesh`` with named axes is built once and every subsystem
addresses its collectives by axis name.

Canonical axis order (outer → inner): ``("pp", "dp", "fsdp", "ep", "tp", "sp")``.
Outer axes map to DCN (slower, inter-slice) and inner axes to ICI, matching
how ``mesh_utils.create_hybrid_device_mesh`` lays out devices, so TP/SP
collectives always ride ICI.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from deepspeed_tpu.utils.logging import logger

# outer → inner; pp outermost (least communication), tp/sp innermost (most)
CANONICAL_AXIS_ORDER = ("pp", "dp", "fsdp", "ep", "tp", "sp")


def _resolve_axis_sizes(axes: Dict[str, int], n_devices: int) -> Dict[str, int]:
    """Fill in a single ``-1`` axis so the product equals ``n_devices``."""
    sizes = dict(axes)
    wildcard = [name for name, size in sizes.items() if size == -1]
    if len(wildcard) > 1:
        raise ValueError(f"At most one mesh axis may be -1, got {wildcard}")
    known = math.prod(size for size in sizes.values() if size != -1)
    if wildcard:
        if n_devices % known != 0:
            raise ValueError(f"Device count {n_devices} not divisible by fixed axes product {known}")
        sizes[wildcard[0]] = n_devices // known
    else:
        if known != n_devices:
            raise ValueError(f"Mesh axes product {known} != device count {n_devices}")
    return sizes


def build_mesh(axes: Optional[Dict[str, int]] = None,
               devices: Optional[Sequence] = None,
               axis_order: Sequence[str] = CANONICAL_AXIS_ORDER) -> Mesh:
    """Build a named-axis mesh over ``devices``.

    ``axes`` maps axis name → size, with at most one ``-1`` meaning "all
    remaining devices". Axes not mentioned get size 1 and are dropped from
    the mesh only if absent from ``axes`` entirely.

    On multi-host TPU, devices from ``jax.devices()`` are already ordered so
    that contiguous blocks share ICI; keeping the canonical (outer→inner)
    order therefore places the innermost axes on ICI neighbours.
    """
    import jax

    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if axes is None:
        axes = {"dp": -1}

    sizes = _resolve_axis_sizes(axes, len(devices))

    # order the declared axes canonically; unknown axes go innermost
    names = sorted(sizes, key=lambda n: axis_order.index(n) if n in axis_order else len(axis_order))
    shape = tuple(sizes[n] for n in names)
    mesh_devices = np.array(devices).reshape(shape)
    mesh = Mesh(mesh_devices, tuple(names))
    logger.info(f"Built device mesh {dict(zip(names, shape))} over {len(devices)} devices")
    return mesh


def build_hybrid_mesh(ici_axes: Dict[str, int], dcn_axes: Dict[str, int]) -> Mesh:
    """Multi-slice mesh: per axis, ``dcn_axes[name]`` replicas span slices
    over DCN and ``ici_axes[name]`` chips span within a slice over ICI
    (the reference's multi-node NCCL topology, rebuilt on
    ``mesh_utils.create_hybrid_device_mesh``).

    Both dicts must cover the same axis names; the resulting mesh axis size
    is the elementwise product. Example for 2 slices of 16 chips::

        build_hybrid_mesh(ici_axes={"dp": 1, "tp": 16}, dcn_axes={"dp": 2, "tp": 1})
        # -> Mesh {"dp": 2, "tp": 16}, dp over DCN, tp over ICI
    """
    import jax
    from jax.experimental import mesh_utils

    if set(ici_axes) != set(dcn_axes):
        raise ValueError(f"ici_axes and dcn_axes must name the same axes, got {set(ici_axes)} vs {set(dcn_axes)}")
    names = [n for n in CANONICAL_AXIS_ORDER if n in ici_axes] + \
            [n for n in ici_axes if n not in CANONICAL_AXIS_ORDER]
    ici_shape = tuple(ici_axes[n] for n in names)
    dcn_shape = tuple(dcn_axes[n] for n in names)
    mesh_devices = mesh_utils.create_hybrid_device_mesh(
        ici_shape, dcn_shape, devices=jax.devices())
    return Mesh(mesh_devices, tuple(names))


def bound_axis_size(name) -> int:
    """Size of a manual/collective axis bound in the CURRENT trace (a
    shard_map/pmap body)."""
    return jax.lax.axis_size(name)


def axis_size(mesh: Mesh, axis) -> int:
    """Product of sizes of (possibly multiple) mesh axes."""
    if axis is None:
        return math.prod(mesh.shape.values())
    if isinstance(axis, str):
        return mesh.shape[axis] if axis in mesh.shape else 1
    return math.prod(mesh.shape[a] for a in axis if a in mesh.shape)


def data_parallel_axes(mesh: Mesh) -> List[str]:
    """Axes over which the batch is sharded (dp + fsdp when present)."""
    return [ax for ax in ("dp", "fsdp") if ax in mesh.shape and mesh.shape[ax] > 1] or \
           [ax for ax in ("dp", "fsdp") if ax in mesh.shape]
