#!/usr/bin/env python3
"""Readings of the serve path that ``bench/run.py`` does not report yet.

    python bench/serve_readings.py --workload <cell> --seed <n> \\
        --seconds 40 --traced-seconds 20 --out <file.json>

One process on the cell's chips: the serving driver's set-up
(``drivers/serve_static.py``), then two windows through its ``serve``,
each with requests from the seed at the cell's rate:

- untraced, ``--seconds`` long, as a benchmark run's window: each
  request's queue wait, from its arrival until ``generate`` is called with
  it (its latency less the call's length, so it also holds the few
  microseconds between the call's return and the driver's clock), and the
  stall record: the slowest call's ``serve.*`` spans beside the median
  call's;
- traced (batches ``serve_static.TRACED_BATCHES``), ``--traced-seconds``
  long: programs run per generated position, device time per run of each
  compiled step by scope (``trace_scopes.reduce``, with the ``op_name``
  maps of ``jit_prefill`` and ``jit_decode`` compiled again after the
  window), and the idle gaps named by the innermost ``bench.`` or
  ``serve.`` span (``trace.reduce``).

Writes the readings to ``--out`` as JSON and a summary to standard output.
Checks nothing against the reference.  Without a TPU it exits 3.
"""
from __future__ import annotations

import argparse
import glob
import json
import sys
import tempfile
import time
from pathlib import Path
from unittest import mock

T_START = time.perf_counter()
if __name__ == "__main__":
    _checkout = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(_checkout), str(_checkout / "src")]

import numpy as np  # noqa: E402

from bench import harness, trace, trace_scopes  # noqa: E402
from bench import traffic as traffic_mod  # noqa: E402
from bench.drivers import serve_static  # noqa: E402
from bench.programs import lm as program  # noqa: E402

STEPS = ("jit_prefill", "jit_decode")


def window(cell, setup, seconds: float) -> dict:
    """One window of ``serve_static.serve``: its batches, each request's
    latency and queue wait (s), and each call's (start, end, spans)."""
    arch, _, params, steps, generate = setup
    reqs = traffic_mod.requests(cell.spec.traffic, cell.seed, seconds,
                                arch.cfg.vocab)
    calls = []

    def timed(*args):
        start = time.perf_counter()
        out = generate(*args)
        calls.append((start, time.perf_counter(), out["spans"]))
        return out

    _, finish, batches = serve_static.serve(cell, arch, params, steps, timed,
                                            reqs)
    latency = finish - reqs.arrivals
    rows = [b["rows"] for b in batches]
    return {"batches": batches, "calls": calls, "latency_s": latency,
            "queue_wait_s": queue_waits(latency, rows, calls)}


def queue_waits(latency: np.ndarray, rows: list[int], calls) -> np.ndarray:
    """Each served request's latency less the length of the ``generate``
    call that served it (requests are served in order, ``rows`` per
    call)."""
    call_s = np.repeat([end - start for start, end, _ in calls], rows)
    return latency[:len(call_s)] - call_s


def stall_record(calls) -> dict:
    """The slowest call beside the median one: each one's seconds, its
    seconds by span name (summed) and outside every span, and the slowest
    call's five longest spans that hold no other, as (name, seconds from
    the call's start, seconds)."""
    length = [end - start for start, end, _ in calls]
    order = np.argsort(length)

    def by_name(k):
        start, end, spans = calls[k]
        out = {}
        for name, s, e in spans:
            out[name] = out.get(name, 0.0) + e - s
        out["outside spans"] = (end - start) - sum(
            e - s for s, e in trace.merge((s, e) for _, s, e in spans))
        return out

    slow, median = int(order[-1]), int(order[len(order) // 2])
    start, _, spans = calls[slow]
    leaves = [sp for sp in spans if not any(
        o is not sp and sp[1] <= o[1] and o[2] <= sp[2] for o in spans)]
    return {
        "slowest": {"call": slow, "s": length[slow], "by_span": by_name(slow),
                    "longest": [[n, s - start, e - s] for n, s, e in sorted(
                        leaves, key=lambda sp: sp[1] - sp[2])[:5]]},
        "median": {"call": median, "s": length[median],
                   "by_span": by_name(median)},
    }


def op_names(steps, params, traffic: dict) -> dict[str, dict[str, str]]:
    """Each compiled step's ``op_name`` map, compiled at the cell's shape."""
    import jax.numpy as jnp

    prefill, decode = steps
    batch = {"tokens": jnp.zeros((traffic["batch"], traffic["prompt_len"]),
                                 jnp.int32)}
    token = {"tokens": jnp.zeros((traffic["batch"], 1), jnp.int32)}
    _, cache = prefill(params, batch)
    texts = (prefill.lower(params, batch).compile().as_text(),
             decode.lower(params, cache, token).compile().as_text())
    return dict(trace_scopes.op_names(t) for t in texts)


def traced_readings(profile, n_devices: int, names: dict,
                    positions: int) -> dict:
    """Programs run per generated position, each step's runs and device
    ms per run (busy, by scope, unmatched), device ms in no module run,
    and the idle gaps named by ``bench.`` and ``serve.`` spans (the
    program's spans lie inside ``bench.generate``, so the window is the
    benchmark's)."""
    r = trace_scopes.reduce(profile, n_devices, names)
    with mock.patch.object(trace, "SPAN_PREFIX", ("bench.", "serve.")):
        gaps = trace.reduce(profile, n_devices)["idle_gaps"]
    per_run = {}
    for m in STEPS:
        n = r["runs"].get(m, 0)
        per_run[m] = {"runs": n} | ({
            "busy_ms": 1e3 * r["busy_s"][m] / n,
            "unmatched_ms": 1e3 * r["unmatched_s"].get(m, 0.0) / n,
            **{f"{k}_ms": 1e3 * v / n
               for k, v in r["scope_s"].get(m, {}).items()}} if n else {})
    return {"window_s": r["window_s"], "runs": r["runs"],
            "launches_per_token": sum(r["runs"].values()) / positions,
            "steps": per_run,
            "no_module_ms": 1e3 * r["busy_s"].get("no module", 0.0),
            "idle_gaps": gaps}


def measure(cell, traced_seconds: float) -> dict:
    """Set-up, the untraced window, the traced window and its reduction."""
    from jax.profiler import ProfileData

    from repro.dist.sharding import get_profile, use_mesh_context
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(model=1)
    profile = get_profile(program.build_arch(cell.spec.config).profile)
    with use_mesh_context(mesh, profile):
        setup = serve_static._setup(cell, mesh, profile)
        cell.setup_done()
        plain = window(cell, setup, cell.seconds)
        cell.trace = True
        traced = window(cell, setup, traced_seconds)
        cell.trace = False
        names = op_names(setup[3], setup[2], cell.spec.traffic)
        memory = cell.memory_peak()
    (path,) = glob.glob(f"{cell.trace_dir}/**/*.xplane.pb", recursive=True)
    first, last = serve_static.TRACED_BATCHES
    positions = sum(b["steps"] + 1 for b in traced["batches"][first:last])
    wait, latency = plain["queue_wait_s"], plain["latency_s"]
    return {
        "cell": cell.spec.name, "seed": cell.seed, "setup_s": cell.t_setup,
        "memory_peak_bytes": memory, "device": cell.devices[0].device_kind,
        "untraced": {
            "seconds": cell.seconds, "requests": len(wait),
            "batches": len(plain["batches"]),
            "latency_p95_ms": 1e3 * float(np.percentile(latency, 95)),
            "queue_wait_p50_ms": 1e3 * float(np.percentile(wait, 50)),
            "queue_wait_p95_ms": 1e3 * float(np.percentile(wait, 95)),
            "call_s": [end - start for start, end, _ in plain["calls"]],
            "stall_record": stall_record(plain["calls"])},
        "traced": {"seconds": traced_seconds, "batches": [first, last],
                   "positions": positions,
                   **traced_readings(ProfileData.from_file(path),
                                     len(cell.devices), names, positions)},
    }


def _summary(r: dict) -> str:
    u, t = r["untraced"], r["traced"]
    stall = u["stall_record"]
    lines = [
        f"{r['cell']} seed {r['seed']}: setup {r['setup_s']:.3f} s",
        f"untraced {u['seconds']} s, {u['requests']} requests in "
        f"{u['batches']} calls: latency p95 {u['latency_p95_ms']:.2f} ms, "
        f"queue wait p50 {u['queue_wait_p50_ms']:.2f} p95 "
        f"{u['queue_wait_p95_ms']:.2f} ms",
        f"slowest call #{stall['slowest']['call']} "
        f"{stall['slowest']['s']:.6f} s, median "
        f"{stall['median']['s']:.6f} s; by span, slowest / median s: "
        + ", ".join(f"{k} {v:.6f} / {stall['median']['by_span'].get(k, 0):.6f}"
                    for k, v in stall["slowest"]["by_span"].items()),
        f"traced: launches per token {t['launches_per_token']:.4f} "
        f"({sum(t['runs'].values()):.0f} runs / {t['positions']} positions)"
        f"; device ms in no module run {t['no_module_ms']:.4f}",
    ]
    for m, d in t["steps"].items():
        lines.append(f"{m}: " + ", ".join(
            f"{k} {v:.4f}" if isinstance(v, float) else f"{k} {v}"
            for k, v in d.items()))
    lines.append("idle gaps: " + ", ".join(
        f"{n} {1e3 * s:.3f} ms" for n, s in t["idle_gaps"]))
    return "\n".join(lines)


def main(argv) -> int:
    ap = argparse.ArgumentParser(prog="bench/serve_readings.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--traced-seconds", type=float, default=20.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    benchmark = harness.load_json(harness.CHECKOUT / "BENCHMARK.json")
    spec = harness.resolve(benchmark, args.workload)
    try:
        devices = harness.require_devices(spec.chips)
    except harness.NoChip as e:
        print(f"serve_readings: {e}", file=sys.stderr)
        return harness.EXIT_NO_CHIP
    harness.use_compile_cache()
    peaks = harness.load_json(harness.BENCH / "peaks.json")["devices"]
    with tempfile.TemporaryDirectory() as tdir:
        cell = harness.Cell(spec, args.seed, args.seconds, False, devices,
                            peaks.get(devices[0].device_kind), T_START,
                            trace_dir=tdir)
        result = measure(cell, args.traced_seconds)
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(result, indent=1, default=float))
    print(_summary(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
