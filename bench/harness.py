"""One benchmark run: one cell, one seed, one process.

Everything is found by name from ``BENCHMARK.json`` at the checkout's root:

- the cell's configuration: the file its ``configs`` entry names;
- its traffic: ``bench/traffic/<traffic>.json``, read by ``traffic.py``;
- its own settings: ``bench/workloads/<cell>.json``, which names the
  driver (``bench/drivers/<driver>.py``), the correctness limits and the
  reference's sample;
- each per-layer metric: a reader ``bench/metrics/<metric>.py`` whose
  ``read(readings)`` returns a number, or ``None`` where it finds nothing.

A driver's ``run(cell)`` sets up (calling ``cell.setup_done()`` when the
window may start), measures for ``cell.seconds``, checks what the timed
path produced against the plain reference, and returns an ``Outcome``.
With ``--trace 1`` it calls ``cell.trace_start()`` / ``cell.trace_stop()``
around part of its window; the harness reduces that trace (``trace.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared, with its limit.
The checks are also the last lines of standard error.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
#: exit codes for runs that print no result
EXIT_NO_CHIP = 3
EXIT_UNKNOWN_DEVICE = 4


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


class UnknownDevice(RuntimeError):
    """A TPU whose ``device_kind`` has no row in ``bench/peaks.json``."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file found by name (metric readers, drivers)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass(frozen=True)
class CellSpec:
    """A cell as its files describe it."""

    name: str
    chips: int
    config: dict
    traffic: dict
    workload: dict


def resolve(benchmark: dict, name: str, root: Path = CHECKOUT) -> CellSpec:
    """The cell ``name`` of ``benchmark`` with its files under ``root``."""
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    config = {c["name"]: c for c in benchmark["configs"]}[w["config"]]
    bench = root / "bench"
    return CellSpec(
        name=name, chips=w["chips"],
        config=load_json(root / config["file"]),
        traffic=load_json(bench / "traffic" / f"{w['traffic']}.json"),
        workload=load_json(bench / "workloads" / f"{name}.json"))


def cell_metrics(benchmark: dict, name: str, trace: bool) -> list[dict]:
    """The metrics a run of cell ``name`` reports: its end-to-end metrics,
    or with ``trace`` its per-layer ones."""
    e2e = [m for m in benchmark["end_to_end"]
           if name in m.get("workloads", [name])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in benchmark["per_layer"]
            if name in m.get("workloads", [name] if m["moves"] in moved
                             else [])]


def require_devices(chips: int) -> list:
    """The first ``chips`` TPU devices; no fallback to any other platform."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, found {len(devices)}")
    return devices[:chips]


def use_compile_cache() -> None:
    """JAX's persistent compilation cache where the program's launchers
    keep it (``$JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache/`` in the
    checkout), for every program, so that only the first run of a cell in
    a checkout compiles."""
    import jax

    from repro.launch.compile_cache import use_compile_cache as program_cache

    program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


@dataclass
class Outcome:
    """What a driver returns."""

    attempted: int
    failed: int
    #: end-to-end values by metric name, measured by the harness's clock
    metrics: dict[str, float]
    #: what the per-layer readers read (program spans, counts' inputs)
    readings: dict
    #: each number compared: name -> (value, limit); correct iff all hold
    checks: dict[str, tuple[float, float]]
    memory_peak_bytes: int

    @property
    def correct(self) -> bool:
        return self.failed == 0 and all(
            v <= lim for v, lim in self.checks.values())


@dataclass
class Cell:
    """The run's context, handed to the driver."""

    spec: CellSpec
    seed: int
    seconds: float
    trace: bool
    devices: list
    peak: dict
    t_start: float
    t_setup: float | None = None
    trace_dir: str | None = None

    def setup_done(self) -> None:
        self.t_setup = time.perf_counter() - self.t_start

    def span(self, name: str):
        """A host span in the profiler's trace (a no-op when not tracing)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(f"bench.{name}")

    def trace_start(self) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0   # only bench.* spans on the host
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)

    def trace_stop(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def memory_peak(self) -> int:
        """Peak bytes in use on the fullest chip so far."""
        return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in self.devices)


def _parse(argv):
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run_cell(benchmark: dict, args, t_start: float, root: Path = CHECKOUT,
             devices: list | None = None) -> dict:
    """One run; returns the result line's object.  ``devices`` skips the
    look for chips (tests pass the CPU's)."""
    spec = resolve(benchmark, args.workload, root)
    if devices is None:
        devices = require_devices(spec.chips)
    peaks = load_json(BENCH / "peaks.json")["devices"]
    kind = devices[0].device_kind
    if kind not in peaks and devices[0].platform == "tpu":
        raise UnknownDevice(f"device kind {kind!r} is not in bench/peaks.json")
    driver = load_module(BENCH / "drivers" / f"{spec.workload['driver']}.py")
    with tempfile.TemporaryDirectory() as tdir:
        cell = Cell(spec, args.seed, args.seconds, bool(args.trace), devices,
                    peaks.get(kind), t_start, trace_dir=tdir)
        out = driver.run(cell)
        reduced = None
        if cell.trace:
            from bench import trace as trace_mod

            reduced = trace_mod.reduce_dir(tdir, len(devices))
    wanted = cell_metrics(benchmark, spec.name, cell.trace)
    metrics = {}
    if cell.trace:
        readings = {**out.readings, "config": spec.config,
                    "traffic": spec.traffic, "peak": cell.peak,
                    "chips": len(devices), "trace": reduced}
        for m in wanted:
            value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(
                readings)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {**out.metrics, "setup_s": cell.t_setup}
        for m in wanted:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": out.memory_peak_bytes}
    result = {"correct": out.correct, "attempted": out.attempted,
              "failed": out.failed, "metrics": metrics, "device": device}
    if cell.trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in out.checks.items()}
    return result


def main(argv, t_start: float) -> int:
    args = _parse(argv)
    benchmark = load_json(CHECKOUT / "BENCHMARK.json")
    use_compile_cache()
    try:
        result = run_cell(benchmark, args, t_start)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    except UnknownDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return EXIT_UNKNOWN_DEVICE
    print(f"correct = {result['correct']}", file=sys.stderr)
    for k, c in result["checks"].items():
        print(f"check {k} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
