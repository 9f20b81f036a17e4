"""Reduce a profiler trace to the device's busy time, its collectives, and
where the rest of the window went.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``:

- device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds
  one event per operation run on the device;
- the benchmark's own host spans are events named ``bench.<what>`` on the
  host plane (``Cell.span``); the traced window runs from the first of
  them to the end of the last.

Busy time is the union of a device's operation intervals inside the
window, averaged over the devices.  Collective time is the union of the
intervals of operations (``XLA Ops`` and ``Async XLA Ops``) whose HLO name
says all-reduce, all-gather, reduce-scatter, all-to-all or
collective-permute, averaged likewise.  The top operations are summed by
HLO name, leaving out control flow (``while``, ``conditional``, ``call``),
whose interval holds its body's.  Each idle gap of the first device is
named by the innermost ``bench.`` span that covers its middle.
"""
from __future__ import annotations

import glob
import re
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
#: ops that contain others on the same line (their time is their body's)
CONTAINERS = ("%while", "%conditional", "%call")
SPAN_PREFIX = "bench."
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
TOP = 10


def merge(intervals):
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _events(line):
    for e in line.events:
        yield e.name, e.start_ns, e.start_ns + e.duration_ns


def host_spans(profile) -> list[tuple[str, float, float]]:
    """The benchmark's spans, from every non-device plane."""
    return [ev for p in profile.planes if not p.name.startswith(DEVICE_PREFIX)
            for line in p.lines for ev in _events(line)
            if ev[0].startswith(SPAN_PREFIX)]


def _op_name(name: str) -> str:
    """An HLO op event's name without layouts, cut to 100 characters."""
    return re.sub(r"\{[^{}]*\}", "", name)[:100]


def device_ops(profile, lines=(OPS_LINE,)
               ) -> list[list[tuple[str, float, float]]]:
    """Each TPU device's operations on ``lines``, in device order."""
    planes = sorted((p for p in profile.planes
                     if p.name.startswith(DEVICE_PREFIX)),
                    key=lambda p: int(p.name[len(DEVICE_PREFIX):]))
    return [[ev for line in p.lines if line.name in lines
             for ev in _events(line)] for p in planes]


def reduce(profile, n_devices: int) -> dict:
    """Seconds busy, in collectives, and of window; the top device ops by
    time and the longest idle gaps by host span (each list at most 10)."""
    spans = host_spans(profile)
    devices = device_ops(profile)[:n_devices]
    if not spans or not devices or not any(devices):
        raise ValueError("trace holds no bench spans or no device ops")
    lo = min(s for _, s, _ in spans)
    hi = max(e for _, _, e in spans)

    def inside(ops):
        return [(n, max(s, lo), min(e, hi)) for n, s, e in ops
                if e > lo and s < hi]

    busy, coll, by_op, names = [], [], defaultdict(float), {}
    n = len(devices)
    for ops in devices:
        ops = inside(ops)
        busy.append(merge((s, e) for _, s, e in ops))
        for name, s, e in ops:
            if not name.startswith(CONTAINERS):
                key = name.split(" = ")[0]
                names.setdefault(key, _op_name(name))
                by_op[key] += (e - s) / n
    for ops in device_ops(profile, (OPS_LINE, ASYNC_LINE))[:n_devices]:
        coll.append(_length(merge(
            (s, e) for name, s, e in inside(ops)
            if any(c in name.split(" = ")[0] for c in COLLECTIVES))))
    gaps = []
    edges = [lo] + [x for iv in busy[0] for x in iv] + [hi]
    for s, e in zip(edges[::2], edges[1::2]):
        if e > s:
            mid = (s + e) / 2
            covering = [sp for sp in spans if sp[1] <= mid <= sp[2]]
            name = (max(covering, key=lambda sp: sp[1])[0] if covering
                    else "no span")
            gaps.append((name, (e - s) * 1e-9))
    return {
        "busy_s": sum(_length(b) for b in busy) / n * 1e-9,
        "collective_s": sum(coll) / n * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "device_ops": [[names[k], v * 1e-9] for k, v in sorted(
            by_op.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [list(g) for g in sorted(gaps, key=lambda g: -g[1])[:TOP]],
    }


def reduce_dir(trace_dir: str, n_devices: int) -> dict:
    """Reduce the one ``.xplane.pb`` that a traced run wrote."""
    from jax.profiler import ProfileData

    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one trace under {trace_dir}: {files}")
    return reduce(ProfileData.from_file(files[0]), n_devices)
