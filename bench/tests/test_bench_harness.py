"""The harness end to end on the CPU at a tiny size.

The test hands the harness the CPU's devices in place of its look for a
chip; everything else is a real run: set-up, window, reference check and
the result line.
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import harness
from bench.tests.conftest import (TINY_CONFIG, TINY_TRAFFIC, TINY_WORKLOAD,
                                  write_checkout)

REPO = Path(__file__).resolve().parents[2]
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def _run(bench, root, name="tiny.serve", seed=2**33 + 11, seconds=0.6):
    args = argparse.Namespace(workload=name, seed=seed, seconds=seconds,
                              trace=0)
    return harness.run_cell(bench, args, time.perf_counter(), root=root,
                            devices=jax.devices())


def test_serve_driver_prints_the_contract_line(tiny_checkout):
    root, bench = tiny_checkout
    out = _run(bench, root)
    line = json.loads(json.dumps(out))
    assert list(line) == KEYS
    assert line["correct"] is True
    assert line["attempted"] == int(0.6 * TINY_TRAFFIC["rate_per_s"])
    assert line["failed"] == 0
    assert set(line["metrics"]) == {"latency_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["count"] == 1
    assert line["checks"]["logit_gap"]["limit"] == 0.25


def test_a_new_workload_is_found_by_name(tmp_path):
    traffic = {**TINY_TRAFFIC, "batch": 2, "output_len": 2}
    bench = write_checkout(tmp_path, {
        "tiny.serve": {"config": TINY_CONFIG, "traffic": TINY_TRAFFIC,
                       "workload": TINY_WORKLOAD},
        "tiny.other": {"config": {**TINY_CONFIG, "n_layers": 1},
                       "traffic": traffic, "workload": TINY_WORKLOAD}})
    spec = harness.resolve(bench, "tiny.other", tmp_path)
    assert spec.traffic["batch"] == 2 and spec.config["n_layers"] == 1
    assert _run(bench, tmp_path, "tiny.other", seconds=0.3)["correct"]
    with pytest.raises(KeyError):
        harness.resolve(bench, "tiny.missing", tmp_path)


def test_per_layer_metrics_follow_the_metric_they_move():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        e2e = {m["name"] for m in harness.cell_metrics(bench, w["name"],
                                                       False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        per_layer = harness.cell_metrics(bench, w["name"], True)
        assert per_layer and all(m["moves"] in e2e for m in per_layer)
        for m in per_layer:
            assert (REPO / "bench" / "metrics" / f"{m['name']}.py").exists()


def test_no_tpu_means_no_result():
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench" / "run.py"), "--workload",
         json.loads((REPO / "BENCHMARK.json").read_text())["workloads"][0][
             "name"], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=REPO, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode == harness.EXIT_NO_CHIP, proc.stderr
    assert proc.stdout == ""
    assert "no TPU" in proc.stderr


class _Faulty:
    """The program's ``generate`` with a fault planted where it works."""

    def __init__(self, monkeypatch, fault):
        from repro.launch import serve

        real = serve.generate

        def generate(arch, steps, params, batch, gen):
            if fault == "state_unchanged":
                prefill, decode = steps

                def stale(p, cache, b):
                    logits, _ = decode(p, cache, b)
                    return logits, cache
                return real(arch, (prefill, stale), params, batch, gen)
            if fault == "half_batch":
                half = batch["tokens"].shape[0] // 2
                out = real(arch, steps, params,
                           {"tokens": batch["tokens"][:half]}, gen)
                out["tokens"] = np.concatenate([out["tokens"]] * 2)
                return out
            out = real(arch, steps, params, batch, gen)
            out["tokens"] = out["tokens"].copy()
            out["tokens"][:, 2] = (out["tokens"][:, 2] + 1) % TINY_CONFIG[
                "vocab"]
            return out

        monkeypatch.setattr(serve, "generate", generate)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
def test_a_broken_timed_path_is_not_correct(tmp_path, monkeypatch, fault):
    # arrivals faster than the server, so that every batch is full
    bench = write_checkout(tmp_path, {"tiny.serve": {
        "config": TINY_CONFIG, "workload": TINY_WORKLOAD,
        "traffic": {**TINY_TRAFFIC, "rate_per_s": 1000.0}}})
    _Faulty(monkeypatch, fault)
    out = _run(bench, tmp_path, seconds=0.1)
    print(out["checks"])
    assert out["correct"] is False
    assert out["checks"]["logit_gap"]["value"] > 0.25
