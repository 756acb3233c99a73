"""The examples/ scripts stay runnable (nightly: each spawns a subprocess)."""

import os
import subprocess
import sys

import pytest

_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))

pytestmark = pytest.mark.nightly


def _run(args, timeout=600):
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               DS_ACCELERATOR="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=_REPO)
    r = subprocess.run([sys.executable] + args, env=env, cwd=_REPO,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, (r.stdout + r.stderr)[-3000:]
    return r.stdout


def test_train_zero3_example():
    out = _run(["examples/train_zero3.py", "--steps", "3", "--seq", "64"])
    assert "loss" in out


def test_train_pipeline_example():
    out = _run(["examples/train_pipeline.py", "--pp", "2", "--steps", "2"])
    assert "loss" in out


def test_serve_hf_example(tmp_path):
    transformers = pytest.importorskip("transformers")
    pytest.importorskip("torch")
    from .hf_fixtures import save_hf
    cfg = transformers.GPT2Config(vocab_size=96, n_positions=64, n_embd=32,
                                  n_layer=2, n_head=2)
    save_hf(transformers.GPT2LMHeadModel(cfg), cfg, tmp_path)
    text = _run(["examples/serve_hf.py", str(tmp_path), "--dtype", "fp32",
                 "--prompt-len", "8", "--gen", "4"])
    assert "generated" in text
