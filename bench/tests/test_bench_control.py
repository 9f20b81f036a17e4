"""The control comes out as not correct, at a size a test run can hold.

The control is the plain reference put in the program's place with every
matmul operand rounded to float8 (e4m3), one precision step below the
configurations' bfloat16 compute.  On a 4-layer model of GLM-4's kind,
over three seeds, the program's served tokens stay within the small
cell's limit and the control's top tokens do not.
"""
from __future__ import annotations

import time

import jax
import pytest

from bench import harness
from bench.tests.conftest import (TINY_CONFIG, TINY_TRAFFIC, TINY_WORKLOAD,
                                  write_checkout)

SMALL = {**TINY_CONFIG, "n_layers": 4, "d_model": 256, "n_heads": 8,
         "head_dim": 32, "n_kv_heads": 2, "d_ff": 512, "vocab": 1024}
#: set from this size's readings on the CPU: over seeds 1, 2, 3 and
#: 2^40 + 3 the program's widest gap was 0.008, the control's smallest 0.158
LIMIT = 0.05


@pytest.mark.parametrize("seed", [1, 2, 2**40 + 3])
def test_control_fails_where_the_program_passes(tmp_path, seed):
    bench = write_checkout(tmp_path, {"small": {
        "config": SMALL, "traffic": {**TINY_TRAFFIC, "rate_per_s": 200.0},
        "workload": {**TINY_WORKLOAD, "limits": {"logit_gap": LIMIT}}}})
    spec = harness.resolve(bench, "small", tmp_path)
    driver = harness.load_module(harness.BENCH / "drivers"
                                 / "serve_static.py")
    cell = harness.Cell(spec, seed, 0.1, False, jax.devices(), None,
                        time.perf_counter())
    out = driver.run(cell, control=True)
    assert out.checks["logit_gap"][0] <= LIMIT
    assert out.checks["control_logit_gap"][0] > LIMIT
