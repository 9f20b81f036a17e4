"""The step counts and the peaks table, pinned to hand arithmetic."""
from __future__ import annotations

import json
import math
from pathlib import Path

import pytest

from bench.counts import lm as counts
from bench.reference import lm as ref

BENCH = Path(__file__).resolve().parents[1]


def _cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_internlm2_layer_parameters_and_kv_bytes():
    cfg = _cfg("internlm2-1.8b")
    # per layer: Wq, Wo 2048x2048; Wk, Wv 2048x1024; 3 MLP 2048x8192
    assert counts.layer_matmul_params(cfg) == (
        2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 8192)
    assert counts.matmul_params(cfg) == 1_509_949_440          # 1.510e9
    # K and V, 8 heads of 128, 24 layers, 2 bytes
    assert counts.kv_bytes_per_token(cfg) == 98_304


def test_glm4_layer_parameters():
    cfg = _cfg("glm4-9b")
    assert counts.layer_matmul_params(cfg) == 203_948_032
    # with the QKV biases and the two norms, as the reference holds them
    total = sum(math.prod(s) for s, _, _ in ref.layer_shapes(cfg).values())
    assert total == 203_960_832                                 # 203.96e6


def test_decode_step_bytes_and_flops_by_hand():
    cfg = _cfg("internlm2-1.8b")
    flops, nbytes = counts.decode_step(cfg, batch=16, context=543)
    weights = 2 * (1_509_949_440 + 2048 * 92544)
    kv = 16 * 544 * 98_304
    assert nbytes == weights + kv + 2 * 2048 * 16
    attn = 4 * 24 * 16 * 128 * 16 * 544
    assert flops == 2 * 1_509_949_440 * 16 + attn + 2 * 2048 * 92544 * 16
    # 4.26 GB at 819 GB/s: 5.2 ms, bound by bytes
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert counts.roofline_s(flops, nbytes, peak) == pytest.approx(
        nbytes / 819e9)
    assert nbytes / 819e9 == pytest.approx(5.2e-3, rel=0.01)


def test_prefill_flops_by_hand():
    cfg = _cfg("internlm2-1.8b")
    flops, _ = counts.prefill(cfg, batch=4, prompt=4096)
    matmul = 2 * 1_509_949_440 * 4 * 4096
    causal = 4 * 24 * 16 * 128 * 4 * 4096 * 4097 / 2
    assert flops == pytest.approx(matmul + causal + 2 * 2048 * 92544 * 4)
    assert flops == pytest.approx(5.6e13, rel=0.02)


def test_decode_sums_its_steps():
    cfg = _cfg("glm4-9b")
    f, b = counts.decode(cfg, 2, 8192, 3)
    steps = [counts.decode_step(cfg, 2, 8192 + i) for i in range(3)]
    assert f == sum(s[0] for s in steps) and b == sum(s[1] for s in steps)


def test_peaks_table_is_keyed_by_device_kind():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    assert "TPU v5e" in peaks["source"]
    row = peaks["devices"]["TPU v5 lite"]
    assert row == {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                   "hbm_bytes": 16e9}


@pytest.mark.parametrize("metric,time_key", [("mfu.prefill", "prefill_s"),
                                             ("mfu.decode", "decode_s")])
def test_roofline_readers_count_real_requests_not_padding(metric, time_key):
    from bench.harness import load_module

    cfg = _cfg("internlm2-1.8b")
    peak = json.loads((BENCH / "peaks.json").read_text())[
        "devices"]["TPU v5 lite"]
    reader = load_module(BENCH / "metrics" / f"{metric}.py")
    batch = {"batch": 4, "rows": 3, "prompt": 4096, "steps": 8,
             "prefill_s": 0.78, "decode_s": 0.25}
    if metric == "mfu.prefill":
        work = counts.prefill(cfg, 3, 4096)
    else:
        work = counts.decode(cfg, 3, 4096, 8)
    want = 100.0 * counts.roofline_s(*work, peak) / batch[time_key]
    got = reader.read({"batches": [batch], "config": cfg, "peak": peak})
    assert got == pytest.approx(want)
    padded = reader.read({"batches": [{**batch, "rows": 4}], "config": cfg,
                          "peak": peak})
    assert got < padded
