"""The serve path's readings script: its arithmetic by hand, its traced
reduction on the trace recorded on a TPU v5 lite (the 2-layer model of
``conftest.py``; see ``test_bench_trace_scopes.py``), its untraced window
on the CPU at a tiny size, and its refusal without a TPU."""
from __future__ import annotations

import json
import lzma
import re
import subprocess
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import harness, serve_readings, trace, trace_scopes
from bench.tests.test_bench_trace_scopes import HLO, POSITIONS, TRACE

REPO = Path(__file__).resolve().parents[2]


def test_queue_waits_by_hand():
    # two calls of 2 s and 1 s serve two requests each
    calls = [(10.0, 12.0, []), (20.0, 21.0, [])]
    waits = serve_readings.queue_waits(np.array([5.0, 6.0, 7.0, 3.0]),
                                       [2, 2], calls)
    assert waits == pytest.approx([3.0, 4.0, 6.0, 2.0])


def test_stall_record_by_hand():
    def call(start, prefill, step):
        spans = [("serve.prefill", start, start + prefill),
                 ("serve.decode", start + prefill, start + prefill + step),
                 ("serve.decode_step", start + prefill,
                  start + prefill + step / 2)]
        return (start, start + prefill + step + 1.0, spans)

    calls = [call(0.0, 2.0, 4.0), call(100.0, 2.0, 9.0), call(200.0, 3.0, 4.0)]
    r = serve_readings.stall_record(calls)
    assert r["slowest"]["call"] == 1 and r["slowest"]["s"] == 12.0
    assert r["slowest"]["by_span"] == pytest.approx({
        "serve.prefill": 2.0, "serve.decode": 9.0, "serve.decode_step": 4.5,
        "outside spans": 1.0})
    assert r["median"]["call"] == 2 and r["median"]["s"] == 8.0
    # serve.decode holds serve.decode_step, so it is not a leaf
    assert r["slowest"]["longest"] == [["serve.decode_step", 2.0, 4.5],
                                       ["serve.prefill", 0.0, 2.0]]


def test_traced_readings_on_the_recorded_trace():
    from jax.profiler import ProfileData

    profile = ProfileData.from_serialized_xspace(
        lzma.decompress(TRACE.read_bytes()))
    names = dict(trace_scopes.op_names(text) for text in re.split(
        r"\n(?=HloModule )", lzma.decompress(HLO.read_bytes()).decode()))
    r = serve_readings.traced_readings(profile, 1, names, POSITIONS)
    assert r["launches_per_token"] == pytest.approx(12.2)
    assert r["no_module_ms"] == 0.0
    for m, runs in (("jit_prefill", 2), ("jit_decode", 8)):
        step = r["steps"][m]
        assert step["runs"] == runs and step["unmatched_ms"] == 0.0
        assert sum(v for k, v in step.items() if k.endswith("_ms")
                   and k != "busy_ms") == pytest.approx(step["busy_ms"])
    # the program's spans name every gap, and leave the window as it was
    assert r["window_s"] == pytest.approx(trace.reduce(profile, 1)["window_s"])
    assert len(r["idle_gaps"]) == trace.TOP
    assert all(name.startswith("serve.") for name, _ in r["idle_gaps"])
    assert trace.SPAN_PREFIX == "bench."


def test_untraced_window_on_the_cpu(tiny_checkout):
    root, bench = tiny_checkout
    spec = harness.resolve(bench, "tiny.serve", root)
    cell = harness.Cell(spec, 2**33 + 5, 0.4, False, jax.devices(), None,
                        time.perf_counter())
    from repro.dist.sharding import get_profile, use_mesh_context
    from repro.launch.mesh import make_host_mesh

    from bench.programs import lm as program

    mesh = make_host_mesh(model=1)
    profile = get_profile(program.build_arch(spec.config).profile)
    with use_mesh_context(mesh, profile):
        setup = serve_readings.serve_static._setup(cell, mesh, profile)
        w = serve_readings.window(cell, setup, cell.seconds)
    n = int(0.4 * spec.traffic["rate_per_s"])
    assert len(w["queue_wait_s"]) == len(w["latency_s"]) == n
    assert len(w["calls"]) == len(w["batches"])
    assert np.all(w["queue_wait_s"] >= 0)
    assert np.all(w["queue_wait_s"] < w["latency_s"])
    assert all(spans[0][0] == "serve.prefill" for _, _, spans in w["calls"])


def test_no_tpu_means_no_readings(tmp_path):
    out = tmp_path / "r.json"
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench" / "serve_readings.py"),
         "--workload", json.loads((REPO / "BENCHMARK.json").read_text())[
             "workloads"][0]["name"], "--seed", "1", "--out", str(out)],
        capture_output=True, text=True, cwd=REPO, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode == harness.EXIT_NO_CHIP, proc.stderr
    assert proc.stdout == "" and not out.exists()
    assert "no TPU" in proc.stderr
