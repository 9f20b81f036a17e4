"""A checkout of the benchmark at a size the CPU runs in seconds.

``tiny_checkout`` writes a ``BENCHMARK.json`` and the files it names (a
2-layer model with GLM-4's mechanisms: QKV bias, partial RoPE, 4:1 GQA)
under a temporary root; the harness's drivers and metric readers are the
repository's own.
"""
from __future__ import annotations

import json
from pathlib import Path

import pytest

TINY_CONFIG = {
    "registry": "glm4-9b", "source": "test",
    "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 1,
    "head_dim": 16, "d_ff": 128, "vocab": 256, "qkv_bias": True,
    "rope_fraction": 0.5, "rope_theta": 10000.0, "norm_eps": 1e-5,
    "reduced": [],
}
TINY_TRAFFIC = {"batch": 4, "prompt_len": 16, "output_len": 4,
                "arrival": "uniform", "rate_per_s": 40.0}
TINY_WORKLOAD = {"driver": "serve_static", "limits": {"logit_gap": 0.25},
                 "reference_sample": 6}


def write_checkout(root: Path, cells: dict[str, dict]) -> dict:
    """Write a benchmark with ``cells`` (name -> {config, traffic, workload})
    under ``root``; returns the BENCHMARK.json object."""
    bench = {"configs": [], "workloads": [], "end_to_end": [
        {"name": "latency_p95_ms", "unit": "ms", "better": "lower",
         "bound": 0.05, "source": "host_clock"},
        {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
         "source": "host_clock"}], "per_layer": [
        {"name": "tpot_p95_ms", "unit": "ms", "better": "lower",
         "source": "program_span", "layer": "serve",
         "moves": "latency_p95_ms"}]}
    for d in ("configs", "traffic", "workloads"):
        (root / "bench" / d).mkdir(parents=True, exist_ok=True)
    for name, c in cells.items():
        cfg_name, traffic_name = f"{name}-model", f"{name}-traffic"
        cfg_file = f"bench/configs/{cfg_name}.json"
        (root / cfg_file).write_text(json.dumps(c["config"]))
        (root / "bench" / "traffic" / f"{traffic_name}.json").write_text(
            json.dumps(c["traffic"]))
        (root / "bench" / "workloads" / f"{name}.json").write_text(
            json.dumps(c["workload"]))
        bench["configs"].append({"name": cfg_name, "source": "test",
                                 "file": cfg_file, "reduced": [],
                                 "why": "test"})
        bench["workloads"].append({"name": name, "config": cfg_name,
                                   "traffic": traffic_name, "chips": 1,
                                   "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return bench


@pytest.fixture
def tiny_checkout(tmp_path):
    cells = {"tiny.serve": {"config": TINY_CONFIG, "traffic": TINY_TRAFFIC,
                            "workload": TINY_WORKLOAD}}
    return tmp_path, write_checkout(tmp_path, cells)
