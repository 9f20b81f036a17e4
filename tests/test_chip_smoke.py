"""``chip_smoke.py``'s control flow, rehearsed on the CPU: each phase at
smoke size with Pallas in interpret mode, and the refusal to run (no JSON
result, non-zero exit) where JAX finds no TPU."""
import importlib.util
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serve_phase_smoke(chip_smoke):
    checks = chip_smoke.Checks()
    chip_smoke.phase_serve(checks, smoke=True, batch=2, prompt_len=16, gen=4)
    assert checks.failed == []


def test_kernel_phase_interpret(chip_smoke):
    checks = chip_smoke.Checks()
    chip_smoke.phase_kernels(checks, n=1 << 14, grid=64, mm=256,
                             attn_shape=(1, 128, 4, 2, 64), interpret=True,
                             reps=1)
    assert checks.failed == []


def test_train_phase_smoke(chip_smoke):
    checks = chip_smoke.Checks()
    chip_smoke.phase_train(checks, smoke=True, steps=2, batch=2, seq=16,
                           model_axis=1)
    assert checks.failed == []


def test_refuses_without_tpu(chip_smoke, capsys):
    assert chip_smoke.main([]) == 1
    out, err = capsys.readouterr()
    assert out == ""
    assert "no TPU" in err
