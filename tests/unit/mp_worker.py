"""Multi-process worker: launched N-way by ``launcher/launch.py`` from
``test_multiprocess.py`` (the reference's ``DistributedTest`` capability,
``tests/unit/common.py:124-210`` — real processes, real backend).

Each rank: joins the distributed JAX runtime via the comm facade, proves a
cross-process collective, runs engine train steps over the global mesh, and
round-trips a checkpoint. Prints ``MP_OK rank=<r> loss=<l>`` on success —
the launching test asserts the marker (with identical loss) per rank.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("DS_ACCELERATOR", "cpu")

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(__file__))


def main():
    out_dir = sys.argv[1]

    import jax

    import deepspeed_tpu
    import deepspeed_tpu.comm as dist
    from simple_model import SimpleModel, random_batch

    dist.init_distributed()
    nproc = jax.process_count()
    assert nproc >= 2, f"expected a multi-process world, got {nproc}"
    rank = jax.process_index()
    assert rank == int(os.environ["RANK"]), (rank, os.environ["RANK"])

    # ---- cross-process collective: the global sum needs every shard ----
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = jax.make_mesh((jax.device_count(),), ("x",))
    local = np.full((1, 4), 1.0 + rank, np.float32)
    garr = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("x")), local)
    total = float(jax.jit(
        lambda a: a.sum(),
        out_shardings=NamedSharding(mesh, P()))(garr))
    expect = 4.0 * sum(1.0 + r for r in range(nproc))
    assert total == expect, (total, expect)

    # ---- engine training step over the global (cross-process) mesh ----
    hidden = 16
    model = SimpleModel(hidden_dim=hidden)
    params = model.init_params(jax.random.key(0))
    dist.set_mesh(None)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
            "zero_optimization": {"stage": 1},
            "mesh": {"dp": -1},
            "steps_per_print": 0,
        })
    dp_world = dist.get_world_size(dist.data_parallel_axes(engine.mesh))
    assert dp_world == jax.device_count(), (dp_world, jax.device_count())

    # identical batch on every rank: numpy jit inputs are replicated-global
    losses = [float(engine.train_batch(random_batch(2 * dp_world, hidden, seed=i)))
              for i in range(3)]
    assert all(np.isfinite(l) for l in losses), losses

    # ---- checkpoint save/load across processes ----
    engine.save_checkpoint(out_dir, tag="mp")
    dist.barrier()
    engine.load_checkpoint(out_dir, tag="mp")
    loss = float(engine.train_batch(random_batch(2 * dp_world, hidden, seed=7)))
    assert np.isfinite(loss), loss

    print(f"MP_OK rank={rank} loss={loss:.6f}", flush=True)


if __name__ == "__main__":
    main()
