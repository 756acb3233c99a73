"""Test harness setup.

The unit suite runs on a virtual 8-device CPU mesh (the TPU analogue of the
reference's multi-process single-node NCCL harness, tests/unit/common.py).
This must happen before any backend initializes: we append
``--xla_force_host_platform_device_count=8`` and force the cpu platform. The
TPU tier (``DS_TPU_TESTS=1 pytest -m tpu``) keeps the real device instead,
and the accelerator selection follows the same switch.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = _flags + " --xla_force_host_platform_device_count=8"

import jax

_TPU_TIER = os.environ.get("DS_TPU_TESTS") == "1"
if not _TPU_TIER:
    os.environ.setdefault("DS_ACCELERATOR", "cpu")
    jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache, placed by the same rule as every entry point
# (utils/compile_cache.py). On the CPU mesh it is OPT-IN (DS_TEST_JAX_CACHE=1):
# this box's XLA:CPU reloads a cached executable with "machine type used for
# compilation doesn't match the machine type for execution ... could lead to
# SIGILL" (re-checked under jaxlib 0.9.0), and earlier suites died at random
# tests on deserialized train steps. The TPU tier keeps the cache on.
if _TPU_TIER or os.environ.get("DS_TEST_JAX_CACHE") == "1":
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

import numpy as np
import pytest


def pytest_collection_modifyitems(config, items):
    """Runtime tier guards. pytest's ``-m`` is last-wins: the tier-1
    driver's ``-m 'not slow'`` REPLACES the addopts exclusion of
    tpu/nightly, which would unleash hardware tests onto the CPU mesh and
    nightly sweeps into the timed budget. A tier therefore only runs when
    POSITIVELY requested — by naming its marker in ``-m`` (the documented
    ``pytest -m tpu`` / ``-m nightly`` opt-ins keep working) or via its
    env var — and an ``-m`` that merely stops excluding it (``'not
    slow'``) does not accidentally enable it."""
    import re
    expr = config.getoption("-m") or ""
    gates = [
        ("tpu", "DS_TPU_TESTS", "needs a real TPU (-m tpu / DS_TPU_TESTS=1)"),
        ("nightly", "DS_NIGHTLY_TESTS",
         "nightly tier (-m nightly / DS_NIGHTLY_TESTS=1)"),
        ("slow", "DS_SLOW_TESTS", "slow tier (-m slow / DS_SLOW_TESTS=1)"),
    ]
    for marker, env, reason in gates:
        if os.environ.get(env) == "1":
            continue
        if re.search(rf"(?<!not ){marker}\b", expr):
            continue  # positively selected on the command line
        skip = pytest.mark.skip(reason=reason)
        for item in items:
            if marker in item.keywords:
                item.add_marker(skip)


@pytest.fixture(autouse=True)
def _no_kv_block_leaks(request):
    """Serving suites must not leak KV pool blocks: every scheduler that
    DRAINED (all requests retired) must leave its allocator with zero live
    references — a nonzero ref count at teardown is a ref-count/double-free
    bug in the prefix-cache sharing logic (cold cached blocks are fine).
    Schedulers a test intentionally abandoned mid-flight are skipped."""
    if not os.path.basename(str(request.node.fspath)).startswith(
            "test_serving"):
        yield
        return
    from deepspeed_tpu.inference import scheduler as _sched_mod
    created = []
    orig_init = _sched_mod.ContinuousBatchingScheduler.__init__

    def tracking_init(self, *a, **k):
        orig_init(self, *a, **k)
        created.append(self)

    _sched_mod.ContinuousBatchingScheduler.__init__ = tracking_init
    try:
        yield
    finally:
        _sched_mod.ContinuousBatchingScheduler.__init__ = orig_init
    for sched in created:
        if not sched.all_done():
            continue
        leaked = sched.allocator.leak_report()
        assert not leaked, (
            f"KV pool blocks leaked after all requests retired "
            f"(block -> refcount): {leaked}")
        # tiered KV cache: a drained scheduler must also leave the host
        # tier consistent — LRU within bound, byte accounting exact, and
        # no chain key resident in BOTH tiers (demoted blocks are cache
        # copies, never leaks; a double-tier key means a promote/discard
        # hand-off was dropped)
        host_probs = sched.allocator.host_consistency()
        assert not host_probs, (
            "KV host-tier inconsistency after all requests retired: "
            + "; ".join(host_probs))


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual cpu devices, got {len(devs)}"
    return devs


@pytest.fixture
def mesh_1d(devices):
    from jax.sharding import Mesh
    return Mesh(np.array(devices[:8]), ("dp",))


@pytest.fixture
def mesh_2d(devices):
    from jax.sharding import Mesh
    return Mesh(np.array(devices[:8]).reshape(4, 2), ("dp", "tp"))
