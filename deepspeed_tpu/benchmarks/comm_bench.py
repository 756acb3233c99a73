"""Collective micro-benchmarks (reference ``benchmarks/communication/`` +
``bin/ds_bench``).

Sweeps message sizes through the comm facade's collectives on the active
mesh and reports latency / algorithmic BW / bus BW per op+size — the same
table ``ds_bench`` prints. Sync is a host fetch of a reduction.
"""

from __future__ import annotations

import argparse
import time
from typing import List

import numpy as np


def _bw_factor(op: str, n: int) -> float:
    """algbw→busbw correction factor (ring-collective cost model, matches
    the reference's utils in benchmarks/communication/utils.py)."""
    if n <= 1:
        return 1.0
    if op == "all_reduce":
        return 2.0 * (n - 1) / n
    if op in ("all_gather", "reduce_scatter", "all_to_all"):
        return (n - 1) / n
    return 1.0


def run_op(op: str, size_bytes: int, mesh, trials: int = 20) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import deepspeed_tpu.comm as dist

    n = mesh.devices.size
    numel = max(n, (size_bytes // 4 // n) * n)
    # stacked-rank layout: dim0 indexes ranks (the facade's eager contract)
    x = jnp.arange(numel, dtype=jnp.float32).reshape(n, numel // n)
    axis = mesh.axis_names[0]
    x = jax.device_put(x, NamedSharding(mesh, P(axis)))

    fns = {
        "all_reduce": lambda t: dist.all_reduce(t),
        "all_gather": lambda t: dist.all_gather(t),
        "reduce_scatter": lambda t: dist.reduce_scatter(t),
        "all_to_all": lambda t: dist.all_to_all_single(t),
        "broadcast": lambda t: dist.broadcast(t, src=0),
    }
    # the facade compiles + caches the shard_map program internally; do NOT
    # jit here (collectives need the facade's eager path outside shard_map)
    fn = fns[op]
    out = fn(x)
    float(jnp.sum(out))  # warm + sync

    t0 = time.perf_counter()
    for _ in range(trials):
        out = fn(x)
    float(jnp.sum(out))
    dt = (time.perf_counter() - t0) / trials

    algbw = size_bytes / dt / 1e9
    busbw = algbw * _bw_factor(op, n)
    return {"op": op, "size": size_bytes, "latency_us": dt * 1e6,
            "algbw_GBps": algbw, "busbw_GBps": busbw}


def main(argv: List[str] = None):
    parser = argparse.ArgumentParser(description="collective micro-benchmarks")
    parser.add_argument("--ops", type=str,
                        default="all_reduce,all_gather,reduce_scatter,all_to_all,broadcast")
    parser.add_argument("--minsize", type=int, default=1 << 12)
    parser.add_argument("--maxsize", type=int, default=1 << 26)
    parser.add_argument("--trials", type=int, default=20)
    args = parser.parse_args(argv)

    import deepspeed_tpu.comm as dist

    if not dist.has_mesh():
        dist.init_mesh()
    mesh = dist.get_mesh()
    n = mesh.devices.size
    print(f"comm bench over mesh {dict(zip(mesh.axis_names, mesh.devices.shape))} ({n} devices)")
    print(f"{'op':<16}{'size':>12}{'latency(us)':>14}{'algbw(GB/s)':>13}{'busbw(GB/s)':>13}")

    for op in args.ops.split(","):
        size = args.minsize
        while size <= args.maxsize:
            r = run_op(op, size, mesh, args.trials)
            print(f"{r['op']:<16}{r['size']:>12}{r['latency_us']:>14.1f}"
                  f"{r['algbw_GBps']:>13.3f}{r['busbw_GBps']:>13.3f}")
            size *= 8


if __name__ == "__main__":
    main()
