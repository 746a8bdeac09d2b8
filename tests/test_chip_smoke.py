"""CPU rehearsal of ``chip_smoke.py``: its phase functions at smoke size
(reduced configs, interpret-mode kernels), and its refusal to run — or
print a result — without a TPU."""
import importlib.util
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_kernel_phase_matches_oracles(chip_smoke):
    errors = chip_smoke.kernel_phase(smoke=True, interpret=True)
    assert sorted(errors) == sorted([
        "decode_attention", "decode_attention_paged", "prefill_attention",
        "prefill_attention_paged", "decode_attention_span_past_cache",
        "prefill_attention_span_past_cache"])
    assert all(e <= chip_smoke.BF16_TOL for e in errors.values())


def test_serve_phase_finishes_every_request(chip_smoke):
    res = chip_smoke.serve_phase(smoke=True)
    assert res["requests"] == 8
    assert [len(s) for s in res["streams"]] == [4] * 8


def test_serve_steps_compile_without_kernels_on_cpu(chip_smoke):
    # the CPU backend takes the reference path: no Mosaic call is lowered
    steps = chip_smoke.serve_step_kernels(smoke=True)
    assert sorted(steps) == ["decode", "prefill_chunk"]
    assert not any(s["tpu_custom_call"] for s in steps.values())


def test_train_phase_losses_finite(chip_smoke, tmp_path):
    res = chip_smoke.train_phase(str(tmp_path), smoke=True)
    assert res["steps"] == 6 and len(res["losses"]) == len(res["step_s"]) == 6
    assert res["state_devices"] == [0]


def test_four_chip_phases_on_emulated_devices(spawned):
    out = spawned("chip_smoke_four.py", devices=4)
    assert "CHIP SMOKE FOUR-CHIP PHASES PASS" in out


def test_script_refuses_without_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300, env=env,
                       cwd=tmp_path)
    assert r.returncode != 0
    assert "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout


def test_script_alone_fails(tmp_path):
    """Copied away from the repo, the script has no program to run."""
    lone = tmp_path / "chip_smoke.py"
    lone.write_text(open(os.path.join(REPO, "chip_smoke.py")).read())
    r = subprocess.run([sys.executable, str(lone)], capture_output=True,
                       text=True, timeout=300, cwd=tmp_path,
                       env=dict(os.environ, JAX_PLATFORMS="cpu",
                                PYTHONPATH=""))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
