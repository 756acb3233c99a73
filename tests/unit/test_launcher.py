"""Launcher / CLI tests (reference tests/unit/launcher/: arg parsing,
hostfile, filters, multinode cmd construction — all hardware-free), plus a
real 2-process local launch smoke test and elasticity planning tests."""

import json
import os
import subprocess
import sys
import tempfile

import pytest

from deepspeed_tpu.elasticity import (ElasticityIncompatibleWorldSize, compute_elastic_config,
                                      get_candidate_batch_sizes, get_valid_gpus)
from deepspeed_tpu.launcher import launch as ds_launch
from deepspeed_tpu.launcher import runner as ds_runner
from deepspeed_tpu.launcher.multinode_runner import (IMPIRunner, MPICHRunner, MVAPICHRunner,
                                                     OpenMPIRunner, PDSHRunner, SlurmRunner)


_REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def _hostfile(tmp_path, text):
    p = tmp_path / "hostfile"
    p.write_text(text)
    return str(p)


class TestHostfile:

    def test_parse(self, tmp_path):
        hf = _hostfile(tmp_path, "worker-0 slots=4\nworker-1 slots=8\n# comment\n\n")
        pool = ds_runner.fetch_hostfile(hf)
        assert pool == {"worker-0": 4, "worker-1": 8}

    def test_bad_format(self, tmp_path):
        hf = _hostfile(tmp_path, "worker-0 gpus=4\n")
        with pytest.raises(ValueError):
            ds_runner.fetch_hostfile(hf)

    def test_duplicate_host(self, tmp_path):
        hf = _hostfile(tmp_path, "w slots=4\nw slots=2\n")
        with pytest.raises(ValueError):
            ds_runner.fetch_hostfile(hf)

    def test_missing_returns_none(self):
        assert ds_runner.fetch_hostfile("/nonexistent/hostfile") is None


class TestResourceFilter:

    POOL = {"worker-0": 4, "worker-1": 4}

    def test_no_filter(self):
        out = ds_runner.parse_resource_filter(self.POOL)
        assert out == {"worker-0": [0, 1, 2, 3], "worker-1": [0, 1, 2, 3]}

    def test_include_host(self):
        out = ds_runner.parse_resource_filter(self.POOL, include_str="worker-1")
        assert out == {"worker-1": [0, 1, 2, 3]}

    def test_include_slots(self):
        out = ds_runner.parse_resource_filter(self.POOL, include_str="worker-0:0,2")
        assert out == {"worker-0": [0, 2]}

    def test_exclude_host(self):
        out = ds_runner.parse_resource_filter(self.POOL, exclude_str="worker-0")
        assert out == {"worker-1": [0, 1, 2, 3]}

    def test_exclude_slots(self):
        out = ds_runner.parse_resource_filter(self.POOL, exclude_str="worker-1:1,3")
        assert out == {"worker-0": [0, 1, 2, 3], "worker-1": [0, 2]}

    def test_mutually_exclusive(self):
        with pytest.raises(ValueError):
            ds_runner.parse_resource_filter(self.POOL, include_str="worker-0",
                                            exclude_str="worker-1")

    def test_unknown_host(self):
        with pytest.raises(ValueError):
            ds_runner.parse_resource_filter(self.POOL, include_str="worker-9")


class TestWorldInfo:

    def test_roundtrip(self):
        info = {"worker-0": [0, 1], "worker-1": [0, 1, 2]}
        enc = ds_runner.encode_world_info(info)
        assert ds_runner.decode_world_info(enc) == info

    def test_rank_env(self):
        info = {"a": [0, 1], "b": [0, 1]}
        env = ds_launch.build_rank_env(info, node_rank=1, local_rank_idx=1,
                                       master_addr="10.0.0.1", master_port=29500)
        assert env["RANK"] == "3"
        assert env["LOCAL_RANK"] == "1"
        assert env["WORLD_SIZE"] == "4"
        assert env["COORDINATOR_ADDRESS"] == "10.0.0.1:29500"
        assert env["PROCESS_ID"] == "3"


class _Args:
    def __init__(self, **kw):
        self.hostfile = kw.get("hostfile", "/job/hostfile")
        self.master_addr = kw.get("master_addr", "worker-0")
        self.master_port = kw.get("master_port", 29500)
        self.include = kw.get("include", "")
        self.exclude = kw.get("exclude", "")
        self.num_nodes = kw.get("num_nodes", -1)
        self.user_script = kw.get("user_script", "train.py")
        self.user_args = kw.get("user_args", ["--foo", "bar"])
        self.launcher_args = ""


class TestMultinodeRunners:

    RESOURCES = {"worker-0": [0, 1], "worker-1": [0, 1]}

    def test_pdsh_cmd(self):
        runner = PDSHRunner(_Args(), "WORLDINFO")
        runner.add_export("JAX_FOO", "1")
        env = {}
        cmd = runner.get_cmd(env, self.RESOURCES)
        assert cmd[0] == "pdsh"
        assert "worker-0,worker-1" in cmd
        assert env["PDSH_RCMD_TYPE"] == "ssh"
        joined = " ".join(cmd)
        assert "--world_info=WORLDINFO" in joined
        assert "deepspeed_tpu.launcher.launch" in joined
        assert "export JAX_FOO=1" in joined
        assert "train.py" in cmd and "--foo" in cmd

    def test_openmpi_cmd(self):
        runner = OpenMPIRunner(_Args(), "WORLDINFO")
        runner.add_export("DS_X", "y")
        cmd = runner.get_cmd({}, self.RESOURCES)
        assert cmd[:3] == ["mpirun", "-n", "4"]
        assert "-x" in cmd and "DS_X=y" in cmd
        assert cmd[-4:] == ["-u", "train.py", "--foo", "bar"]

    def test_mpich_cmd(self):
        runner = MPICHRunner(_Args(), "WORLDINFO")
        cmd = runner.get_cmd({}, self.RESOURCES)
        assert cmd[:5] == ["mpirun", "-n", "4", "-ppn", "2"]

    def test_slurm_cmd(self):
        runner = SlurmRunner(_Args(num_nodes=2), "WORLDINFO")
        runner.add_export("A", "b")
        cmd = runner.get_cmd({}, self.RESOURCES)
        assert cmd[:3] == ["srun", "-n", "4"]
        assert "--nodes" in cmd
        assert "--export" in cmd
        export_val = cmd[cmd.index("--export") + 1]
        assert export_val.startswith("ALL,") and "A=b" in export_val
        assert "MASTER_ADDR=worker-0" in export_val  # coordinator rides along

    def test_mvapich_cmd(self, tmp_path, monkeypatch):
        monkeypatch.setattr(MVAPICHRunner, "HOSTFILE", str(tmp_path / "hosts"))
        runner = MVAPICHRunner(_Args(), "WORLDINFO")
        cmd = runner.get_cmd({}, self.RESOURCES)
        assert cmd[:5] == ["mpirun", "-np", "4", "-ppn", "2"]
        assert "-env" in cmd and "MV2_SUPPORT_DL=1" in cmd
        assert "MASTER_ADDR=worker-0" in cmd
        hosts = (tmp_path / "hosts").read_text().split()
        assert hosts == ["worker-0", "worker-1"]
        assert cmd[-4:] == ["-u", "train.py", "--foo", "bar"]

    def test_mvapich_rejects_uneven_nodes(self, tmp_path, monkeypatch):
        monkeypatch.setattr(MVAPICHRunner, "HOSTFILE", str(tmp_path / "hosts"))
        runner = MVAPICHRunner(_Args(), "WORLDINFO")
        with pytest.raises(ValueError, match="same number"):
            runner.get_cmd({}, {"worker-0": [0, 1], "worker-1": [0]})

    def test_impi_cmd(self):
        runner = IMPIRunner(_Args(), "WORLDINFO")
        cmd = runner.get_cmd({}, self.RESOURCES)
        assert cmd[:5] == ["mpirun", "-ppn", "2", "-n", "4"]
        assert "-hosts" in cmd and "worker-0,worker-1" in cmd
        assert "-genv" in cmd and "MASTER_PORT" in cmd
        assert cmd[-4:] == ["-u", "train.py", "--foo", "bar"]


class TestLocalLaunch:
    """Real 2-process spawn (the reference's DistributedTest analogue for the
    launcher itself)."""

    @pytest.mark.slow
    def test_two_process_launch(self, tmp_path):
        script = tmp_path / "worker.py"
        script.write_text(
            "import os, json, sys\n"
            "out = {k: os.environ[k] for k in ('RANK','LOCAL_RANK','WORLD_SIZE','MASTER_ADDR')}\n"
            "open(os.path.join(os.path.dirname(__file__), f'out_{os.environ[\"RANK\"]}.json'), 'w')"
            ".write(json.dumps(out))\n")
        info = ds_runner.encode_world_info({"localhost": [0, 1]})
        env = os.environ.copy()
        env["PYTHONPATH"] = _REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
        # workers must not grab the TPU or spin up jax
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [sys.executable, "-m", "deepspeed_tpu.launcher.launch",
             f"--world_info={info}", "--node_rank=0",
             "--master_addr=127.0.0.1", "--master_port=29511", str(script)],
            env=env, capture_output=True, timeout=120)
        assert proc.returncode == 0, proc.stderr.decode()
        for rank in (0, 1):
            data = json.loads((tmp_path / f"out_{rank}.json").read_text())
            assert data["WORLD_SIZE"] == "2"
            assert data["RANK"] == str(rank)

    @pytest.mark.nightly
    def test_failing_rank_kills_job(self, tmp_path):
        script = tmp_path / "worker.py"
        script.write_text(
            "import os, sys, time\n"
            "if os.environ['RANK'] == '1': sys.exit(3)\n"
            "time.sleep(30)\n")
        info = ds_runner.encode_world_info({"localhost": [0, 1]})
        env = os.environ.copy()
        env["PYTHONPATH"] = _REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [sys.executable, "-m", "deepspeed_tpu.launcher.launch",
             f"--world_info={info}", "--node_rank=0",
             "--master_addr=127.0.0.1", "--master_port=29512", str(script)],
            env=env, capture_output=True, timeout=60)
        assert proc.returncode == 3


class TestElasticity:

    CONFIG = {
        "elasticity": {
            "enabled": True,
            "max_train_batch_size": 2000,
            "micro_batch_sizes": [2, 4, 6],
            "min_gpus": 1,
            "max_gpus": 10000,
            "version": 0.1,
        }
    }

    def test_candidates(self):
        # reference HCN scaling: every base lands on 24 (base * largest
        # HCN <= 32/base), collapsing to one maximally-divisible candidate
        c = get_candidate_batch_sizes([2, 4, 6], 32)
        assert c == [24]

    def test_valid_gpus(self):
        assert get_valid_gpus(24, [2, 4, 6], 1, 12) == [1, 2, 3, 4, 6, 12]

    def test_compute_plan(self):
        batch, valid = compute_elastic_config(self.CONFIG)
        assert batch <= 2000
        assert len(valid) > 0
        # every valid world size must evenly decompose the batch
        for g in valid[:20]:
            assert any(batch % (g * m) == 0 for m in [2, 4, 6])

    def test_world_size_resolution(self):
        batch, micro, gas = compute_elastic_config(self.CONFIG, world_size=4)
        assert batch == micro * gas * 4

    def test_incompatible_world_size(self):
        cfg = {"elasticity": {**self.CONFIG["elasticity"], "micro_batch_sizes": [2],
                              "max_train_batch_size": 4}}
        with pytest.raises(ElasticityIncompatibleWorldSize):
            compute_elastic_config(cfg, world_size=3)


class TestElasticPlannerReferenceParity:
    """Table-driven reproduction of the reference planner's outputs
    (deepspeed/elasticity/elasticity.py:25-80 HCN candidate enumeration +
    factor-based valid-GPU search; expected values from the reference's own
    tests/unit/elasticity/test_elastic.py)."""

    TEN_K = {"elasticity": {"enabled": True, "max_train_batch_size": 10000,
                            "micro_batch_sizes": [8, 12, 16, 17],
                            "min_gpus": 32, "max_gpus": 1500, "min_time": 20,
                            "version": 0.1}}

    def test_basic_10k(self):
        batch, valid = compute_elastic_config(self.TEN_K)
        assert batch == 9792
        assert len(valid) == 23
        for g in valid:
            assert batch % g == 0
            assert any((batch // g) % m == 0
                       for m in self.TEN_K["elasticity"]["micro_batch_sizes"])

    def test_world_size_micro_batch_selection(self):
        _, micro, _ = compute_elastic_config(self.TEN_K, world_size=64)
        assert micro == 17

    def test_incompatible_world_size_128(self):
        with pytest.raises(ElasticityIncompatibleWorldSize):
            compute_elastic_config(self.TEN_K, world_size=128)

    def test_proper_mbsz(self):
        cfg = {"elasticity": {**self.TEN_K["elasticity"],
                              "max_train_batch_size": 32,
                              "micro_batch_sizes": [1, 2, 3, 7],
                              "min_gpus": 1}}
        _, micro, _ = compute_elastic_config(cfg, world_size=7)
        assert micro == 3

    def test_hcn_candidates(self):
        # base 8 with max 10000: largest HCN <= 1250 is 840 -> 6720; etc.
        assert get_candidate_batch_sizes([8], 10000) == [6720]
        assert get_candidate_batch_sizes([8, 12, 16, 17], 10000) == \
            sorted({840 * 8, 720 * 12, 360 * 16, 360 * 17})


class TestDscliSsh:
    """``dscli ssh`` (reference bin/ds_ssh): pdsh broadcast over the
    hostfile's hosts."""

    def test_ssh_invokes_pdsh_with_hosts(self, tmp_path, monkeypatch):
        hf = tmp_path / "hostfile"
        hf.write_text("nodeA slots=4\nnodeB slots=4\n")
        fake = tmp_path / "pdsh"
        log = tmp_path / "pdsh.log"
        fake.write_text(f"#!/bin/sh\necho \"$@\" > {log}\n")
        fake.chmod(0o755)
        monkeypatch.setenv("PATH", f"{tmp_path}:{os.environ['PATH']}")

        from deepspeed_tpu.cli import _ssh
        rc = _ssh(["-f", str(hf), "hostname", "-f"])
        assert rc == 0
        assert log.read_text().strip() == "-w nodeA,nodeB hostname -f"

    def test_ssh_missing_hostfile(self, tmp_path, monkeypatch):
        fake = tmp_path / "pdsh"
        fake.write_text("#!/bin/sh\n")
        fake.chmod(0o755)
        monkeypatch.setenv("PATH", f"{tmp_path}:{os.environ['PATH']}")
        from deepspeed_tpu.cli import _ssh
        with pytest.raises(RuntimeError, match="hostfile"):
            _ssh(["-f", str(tmp_path / "nope"), "true"])


@pytest.mark.slow
def test_bin_scripts_run_from_checkout(tmp_path):
    """bin/dscli and bin/ds_report work straight from a checkout with no
    install and no PYTHONPATH (they bootstrap the repo root)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", DS_ACCELERATOR="cpu")
    env.pop("PYTHONPATH", None)
    for args, marker in ((["bin/ds_report"], "device count"),
                         (["bin/dscli", "report"], "device count")):
        r = subprocess.run([sys.executable] + [os.path.join(_repo_root(), a)
                                               for a in args[:1]] + args[1:],
                           env=env, cwd=str(tmp_path), capture_output=True,
                           text=True, timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]
        assert marker in r.stdout


def _repo_root():
    return os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
