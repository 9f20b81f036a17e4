#!/usr/bin/env python3
"""Readings that a serving cell's ``logit_gap`` limit is set from.

    python bench/limits.py --workload <cell> --seeds 1,2,3 --seconds 8

For each seed, in this one process: a run of the cell's timed path at its
own load for ``--seconds`` (set-up, window, the reference's check), then
the control on the same served requests: the reference computed with
every matmul operand rounded to float8 (e4m3), one precision step below
the configurations' bfloat16 compute.  Prints one JSON line per seed with
the program's gap and the control's, then the largest program gap (the
lower reading) and the smallest control gap (the upper reading).

Runs on the chip only, like ``run.py``; the benchmark's own runs never run
the control.
"""
import sys
import time

if __name__ == "__main__":
    import argparse
    import json
    from pathlib import Path

    checkout = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(checkout), str(checkout / "src")]
    from bench import harness

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    benchmark = harness.load_json(checkout / "BENCHMARK.json")
    spec = harness.resolve(benchmark, args.workload)
    devices = harness.require_devices(spec.chips)
    harness.use_compile_cache()
    peak = harness.load_json(harness.BENCH / "peaks.json")["devices"][
        devices[0].device_kind]
    driver = harness.load_module(
        harness.BENCH / "drivers" / f"{spec.workload['driver']}.py")
    program, control = [], []
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = harness.Cell(spec, seed, args.seconds, False, devices, peak,
                            time.perf_counter())
        out = driver.run(cell, control=True)
        gap, ctl = out.checks["logit_gap"][0], out.checks[
            "control_logit_gap"][0]
        program.append(gap)
        control.append(ctl)
        print(json.dumps({"seed": seed, "logit_gap": gap,
                          "control_logit_gap": ctl,
                          "requests": out.attempted}), flush=True)
    print(json.dumps({"lower": max(program), "upper": min(control)}))
