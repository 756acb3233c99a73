"""The chip entry points refuse to run without the chip, and the pieces
they stand on (device guard, compile-cache placement, peaks table, dispatch
records, the stamped native build) behave. Fast: nothing here compiles a
jax program except the ``slow``-marked dry run of ``chip_smoke.py``."""

import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)


class TestDeviceGuard:

    def test_chip_smoke_exits_nonzero_without_a_chip(self, capsys):
        import chip_smoke
        with pytest.raises(SystemExit) as exc:
            chip_smoke.main([])
        assert exc.value.code not in (0, None)
        assert "no TPU" in str(exc.value.code)
        out = capsys.readouterr().out
        assert '"ok"' not in out          # no result line of any kind

    def test_result_line_has_exactly_the_contract_keys(self):
        import chip_smoke
        line = chip_smoke.result_line(
            {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
        assert "\n" not in line
        assert json.loads(line) == {"ok": True, "device": {
            "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}

    def test_stray_accelerator_override_is_an_error(self, monkeypatch):
        """jax on a TPU with DS_ACCELERATOR=cpu is a mixed state, not a
        fallback: the guard names it."""
        from deepspeed_tpu.accelerator import real_accelerator as ra

        class Dev:
            platform, device_kind = "tpu", "TPU v5 lite"
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
        with pytest.raises(RuntimeError, match="selected accelerator"):
            ra.require_tpu()          # conftest selected the cpu accelerator
        Dev.device_kind = "TPU v9 imaginary"
        with pytest.raises(RuntimeError, match="peaks table"):
            ra.require_tpu()

    def test_unknown_device_kind_has_no_peak(self):
        from deepspeed_tpu.accelerator import get_accelerator
        with pytest.raises(LookupError, match="device_kind 'cpu'"):
            get_accelerator().peak_tflops()


class TestCompileCachePlacement:

    def test_environment_placement_is_left_alone(self, monkeypatch):
        from deepspeed_tpu.utils import compile_cache as cc
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        updates = []
        monkeypatch.setattr(jax.config, "update",
                            lambda *a: updates.append(a))
        assert cc.enable_compile_cache() == "/some/dir"
        assert updates == []              # no directory set in code

    def test_default_is_one_fixed_path_in_the_checkout(self, monkeypatch,
                                                       tmp_path):
        from deepspeed_tpu.utils import compile_cache as cc
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        updates = []
        monkeypatch.setattr(jax.config, "update",
                            lambda *a: updates.append(a))
        seen = set()
        for cwd in (tmp_path, tmp_path / "elsewhere"):
            cwd.mkdir(exist_ok=True)
            monkeypatch.chdir(cwd)
            seen.add(cc.enable_compile_cache())
        assert seen == {os.path.join(REPO, ".jax_cache")}
        assert set(updates) == {("jax_compilation_cache_dir",
                                 os.path.join(REPO, ".jax_cache"))}


class TestDispatchRecords:

    def test_record_counts_and_interpret_on_tpu_is_an_error(self, monkeypatch):
        from deepspeed_tpu.ops import dispatch
        before = dispatch.selected().get("kernel/probe=interpret", 0)
        assert dispatch.resolve_interpret("probe", None) is True   # cpu
        assert dispatch.selected()["kernel/probe=interpret"] == before + 1
        monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
        assert dispatch.resolve_interpret("probe", None) is False
        with pytest.raises(RuntimeError, match="interpret=True on a TPU"):
            dispatch.resolve_interpret("probe", True)


def test_launcher_and_cli_parents_stay_off_jax():
    """One process per chip: the launcher parent and ``dscli`` must not
    initialise a backend (a parent that holds the chip starves its
    workers)."""
    code = ("import deepspeed_tpu, deepspeed_tpu.cli, "
            "deepspeed_tpu.launcher.launch, deepspeed_tpu.launcher.runner\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, xla_bridge._backends\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]


def test_native_library_is_rebuilt_unless_stamped_for_this_host(monkeypatch,
                                                                tmp_path):
    """csrc/build is not in git and is compiled -march=native: a library
    without a matching stamp (copied in, or built from older sources) must
    not be loaded as-is."""
    from deepspeed_tpu.ops import native
    lib = tmp_path / "libdstpu.so"
    builds = []
    monkeypatch.setattr(native, "lib_path", lambda: str(lib))
    monkeypatch.setattr(native, "build_library",
                        lambda: builds.append(1) or str(lib))
    native.ensure_library()                      # no file, no stamp
    lib.write_bytes(b"\x7fELF from another machine")
    native.ensure_library()                      # file without a stamp
    (tmp_path / "libdstpu.so.stamp").write_text("other sources, other cpu")
    native.ensure_library()                      # stale stamp
    assert len(builds) == 3
    (tmp_path / "libdstpu.so.stamp").write_text(native._build_stamp())
    assert native.ensure_library() == str(lib) and len(builds) == 3


@pytest.mark.slow
def test_chip_smoke_dry_run_end_to_end():
    """The whole command at toy size on the CPU backend (interpreted
    kernels, four virtual devices so the four-chip legs run too)."""
    r = subprocess.run([sys.executable, "chip_smoke.py", "--dry-run"],
                       cwd=REPO, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    # the driver's contract for the last line: exactly these keys
    assert json.loads(lines[-1]) == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 4}}
    assert lines[-2].startswith("summary {")
    summary = json.loads(lines[-2].split(" ", 1)[1])
    assert summary["ok"] and "DRY RUN" in summary["dry_run"]
    assert summary["device"]["platform"] == "cpu"
    assert all(leg["ok"] for leg in summary["legs"].values())
    assert not os.path.exists(os.path.join(REPO, ".jax_cache"))
