"""``chip_smoke.py`` on the CPU: its train, serve and study phases at the
registry's smoke size (so the script's control flow is guarded without
a chip), its refusal to run anywhere but a TPU, and the one-process
rule of ``launch/sweep.py`` that the study phase relies on."""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cfg(smoke):
    from repro.configs import get_smoke
    return get_smoke(smoke.ARCH)


@pytest.fixture
def no_compile_cache(monkeypatch):
    """Keep ``sweep.main`` from turning on the persistent cache."""
    from repro.launch import mesh
    monkeypatch.setattr(mesh, "enable_compile_cache", lambda: None)


def _cpu_env():
    return {**os.environ, "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": os.pathsep.join(
                [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")])}


class TestPhases:
    def test_train(self, smoke, cfg):
        from repro.launch.mesh import make_local_mesh
        rec = smoke.phase_train(cfg, make_local_mesh(), steps=3, batch=2,
                                seq=32)
        assert len(rec["losses"]) == 3
        assert rec["compile_s"] > 0 and rec["steady_step_s"] > 0
        assert rec["peak_bytes_in_use"] == [None]   # the CPU reports none

    def test_train_with_cut_depth(self, smoke, cfg):
        cut = smoke.cut_depth(cfg, 1)
        assert cut.n_layers == 1 and cut.layer_types == ("swa",)
        assert (cut.d_model, cut.d_ff) == (cfg.d_model, cfg.d_ff)

    def test_serve(self, smoke, cfg):
        rec = smoke.phase_serve(cfg, slots=4, max_len=64, n_requests=4,
                                prompt_len=(4, 9), max_new=4)
        assert rec["requests"] == 4 and rec["tokens"] == 16
        assert rec["logit_rel_err"] <= smoke.SERVE_REL_TOL

    def test_serve_needs_a_slot_per_request(self, smoke, cfg):
        with pytest.raises(ValueError):
            smoke.phase_serve(cfg, slots=2, max_len=64, n_requests=4,
                              prompt_len=(4, 9), max_new=4)

    def test_study(self, smoke, tmp_path, no_compile_cache):
        rec = smoke.phase_study(tmp_path, steps=2, batch=2, seq=16)
        assert rec["gang"]["ok"] == rec["inline"]["ok"] == 8
        assert rec["gang"]["dispatches"] == 1
        assert rec["inline"]["dispatches"] == 8

    def test_sharded_on_four_cpu_devices(self):
        """The ``--chips 4`` phase on four virtual CPU devices."""
        code = (
            "import importlib.util, jax\n"
            f"spec = importlib.util.spec_from_file_location('cs', "
            f"{str(ROOT / 'chip_smoke.py')!r})\n"
            "cs = importlib.util.module_from_spec(spec)\n"
            "spec.loader.exec_module(cs)\n"
            "from repro.configs import get_smoke\n"
            "cfg = get_smoke(cs.ARCH)\n"
            "assert len(jax.devices()) == 4\n"
            "rec = cs.phase_sharded(cs.cut_depth(cfg, 1), cfg, jax.devices(),"
            " steps=2, batch=4, seq=32)\n"
            "assert rec['max_rel_diff'] <= cs.SHARDED_LOSS_REL_TOL\n")
        env = {**_cpu_env(),
               "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=600)
        assert r.returncode == 0, r.stderr[-3000:]
        assert '"phase": "sharded_vs_one"' in r.stdout


class TestNoChip:
    @pytest.mark.parametrize("alone", [False, True])
    def test_refuses_to_run_on_cpu(self, tmp_path, alone):
        script = ROOT / "chip_smoke.py"
        if alone:   # a directory holding the script and nothing else
            script = Path(shutil.copy(script, tmp_path))
        r = subprocess.run([sys.executable, str(script)], cwd=tmp_path,
                           env=_cpu_env(), capture_output=True, text=True,
                           timeout=300)
        assert r.returncode != 0
        assert "no TPU found" in r.stderr
        assert '"ok"' not in r.stdout


class TestSweepOneProcess:
    STUDY = ("sweep:\n  args:\n    lr: [0.001, 0.002]\n"
             "    arch: [h2o-danube-1.8b]\n  command: train\n")

    def test_process_pool_refuses_train_tasks(self, tmp_path, capsys):
        from repro.launch import sweep
        wdl = tmp_path / "s.yaml"
        wdl.write_text(self.STUDY)
        with pytest.raises(SystemExit) as e:
            sweep.main([str(wdl), "--pool", "process", "--slots", "2",
                        "--root", str(tmp_path / "root")])
        assert e.value.code == 2
        assert "belongs to one process" in capsys.readouterr().err

    def test_shell_study_never_imports_jax(self, tmp_path):
        wdl = tmp_path / "s.yaml"
        wdl.write_text('work:\n  args:\n    n: ["1:3"]\n'
                       '  command: sh -c "echo ${args:n}"\n')
        code = ("import sys\nfrom repro.launch import sweep\n"
                f"out = sweep.main([{str(wdl)!r}, '--root', "
                f"{str(tmp_path / 'root')!r}])\n"
                "assert len(out['results']) == 3, out\n"
                "assert out['dispatches'] == 0, out\n"
                "assert 'jax' not in sys.modules\n")
        r = subprocess.run([sys.executable, "-c", code], env=_cpu_env(),
                           capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr
