"""``chip_smoke.py`` off the chip: ``main()`` refuses any platform but a
TPU (non-zero exit, no result line), also from a directory that holds the
script alone, and every phase passes at the reduced payload widths and
short receptor lengths — the four-device phases on four virtual CPU
devices in a child process (the device count is fixed when JAX starts)."""

import importlib.util
import os
import shutil
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")
_spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
cs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cs)

SHORT = {"receptor_len": (12, 16), "peptide_len": 4}


def test_main_refuses_cpu(capsys, tmp_path):
    assert jax.devices()[0].platform != "tpu"
    assert cs.main([]) != 0
    assert cs.main(["--chips", "4"]) != 0
    assert '"ok"' not in capsys.readouterr().out
    # the script alone, without the program beside it
    shutil.copy(SCRIPT, tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


@pytest.mark.parametrize("phase", ["kernel", "campaign", "gateway"])
def test_phases_at_reduced_widths(phase):
    if phase == "kernel":
        out = cs.run_phase(phase, cs.kernel_parity, reduced=True, rows=8,
                           max_new=32)
        assert out["interpret"] is True
        assert max(out["max_abs_err"].values()) <= cs.KERNEL_TOL
    elif phase == "campaign":
        out = cs.run_phase(phase, cs.campaign, reduced=True, **SHORT)
        assert out["paged_dispatches"] >= 1
        assert out["generator_version"] >= 1
        assert {"generate", "generate_batch", "predict", "predict_batch",
                "backbone_batch", "finetune"} <= set(out["tasks_completed"])
        assert all(c == {"admit": 1, "step": 1}
                   for c in out["engine_trace_counts"].values())
    else:
        out = cs.run_phase(phase, cs.gateway, reduced=True, **SHORT)
        assert set(out["states"].values()) == {"COMPLETED"}
    assert out["wall_s"] > 0 and out["compiles"] >= 0


def test_four_device_phases_at_reduced_widths():
    code = (
        "import jax, chip_smoke as cs\n"
        "d = jax.devices()\n"
        "assert len(d) == 4, d\n"
        "cs.run_phase('p', cs.sharded_predict, reduced=True, devices=d)\n"
        "cs.run_phase('f', cs.sharded_finetune, reduced=True, devices=d)\n"
        "cs.run_phase('c', cs.spanning_campaign, reduced=True, devices=d,"
        " receptor_len=12, peptide_len=4)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "granted_device_ids\": [0, 1, 2, 3]" in out.stdout
