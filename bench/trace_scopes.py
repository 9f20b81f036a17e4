"""Reduce a serving trace by program launch and named scope.

``trace.py`` gives the device's busy and idle time over the traced window
and names its idle gaps; this module says which programs ran in that
window and where inside them the device's time went.  It reads the same
``.xplane.pb``, over the same window (``trace.host_spans``):

- the ``XLA Modules`` line of each ``/device:TPU:<n>`` plane: one event
  per program run on the device, named ``<module>(<fingerprint>)``;
- the ``XLA Ops`` line, whose events carry only the HLO instruction
  (``%fusion.170 = ...``): JAX's ``ProfileData`` exposes no ``op_name``,
  so the caller passes each module's ``op_name`` map, read from the
  compiled HLO text (``op_names``).

Device time is split as xprof's framework-op view splits it, by root op:
each instant of a device's busy time goes to the innermost operation
running then (a ``while`` keeps only what its body leaves uncovered), that
operation to the module run that contains it, and its instruction to the
innermost of the scopes ``attn``, ``mlp`` and ``head`` on its ``op_name``
path (``models/lm.py``), else ``other``.  An instruction that the module's
map lacks is ``unmatched``.  Within a module, scope and unmatched seconds
sum to its busy time.
"""
from __future__ import annotations

import bisect
import re
from collections import Counter, defaultdict

from bench.trace import OPS_LINE, device_ops, host_spans

MODULES_LINE = "XLA Modules"
SCOPES = ("attn", "mlp", "head")
OTHER = "other"

_INSTRUCTION = re.compile(r"\s*(?:ROOT )?%([\w.-]+) = ")
_OP_NAME = re.compile(r"metadata=\{[^}]*op_name=\"([^\"]*)\"")


def op_names(hlo_text: str) -> tuple[str, dict[str, str]]:
    """The module's name and each instruction's ``op_name`` (``""`` for
    one without), from ``jax.jit(f).lower(...).compile().as_text()``."""
    module = re.match(r"HloModule ([^\s,]+)", hlo_text).group(1)
    names = {}
    for line in hlo_text.splitlines():
        if m := _INSTRUCTION.match(line):
            name = _OP_NAME.search(line)
            names[m.group(1)] = name.group(1) if name else ""
    return module, names


def scope(op_name: str) -> str:
    """The innermost of ``SCOPES`` on an ``op_name`` path, else ``other``."""
    inner = [p for p in op_name.split("/") if p in SCOPES]
    return inner[-1] if inner else OTHER


def module_name(event: str) -> str:
    """``jit_decode(8666549578861363896)`` -> ``jit_decode``."""
    return re.sub(r"\(\d+\)$", "", event)


def self_times(ops) -> dict[int, float]:
    """Each op's share of the union of ``ops`` ((start, end) pairs): every
    instant goes to the op that started last among those running.  The
    shares sum to the union's length."""
    out = defaultdict(float)
    stack = []          # (index, end) of open ops, innermost last
    t = 0.0
    for i in sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1])):
        s, e = ops[i]
        while stack and stack[-1][1] <= s:
            j, end = stack.pop()
            out[j] += max(0.0, end - t)
            t = max(t, end)
        if stack:
            out[stack[-1][0]] += s - t
        t = s
        stack.append((i, e))
    while stack:
        j, end = stack.pop()
        out[j] += max(0.0, end - t)
        t = max(t, end)
    return out


def reduce(profile, n_devices: int,
           names: dict[str, dict[str, str]]) -> dict:
    """Per module (``names`` maps a module to its ``op_names`` map): runs
    in the window, busy seconds, seconds by scope and unmatched seconds,
    each averaged over the devices."""
    window = host_spans(profile)
    if not window:
        raise ValueError("trace holds no bench spans")
    lo = min(s for _, s, _ in window)
    hi = max(e for _, _, e in window)
    modules = device_ops(profile, (MODULES_LINE,))[:n_devices]
    devices = device_ops(profile, (OPS_LINE,))[:n_devices]
    if not devices or not any(devices):
        raise ValueError("trace holds no device ops")
    n = len(devices)
    runs, busy = Counter(), defaultdict(float)
    by_scope = defaultdict(lambda: defaultdict(float))
    unmatched = defaultdict(float)
    for runs_d, ops_d in zip(modules, devices):
        runs_d = sorted((s, e, module_name(m)) for m, s, e in runs_d
                        if lo <= s < hi)
        runs.update(m for _, _, m in runs_d)
        starts = [s for s, _, _ in runs_d]
        ops_d = [(name.split(" = ")[0].lstrip("%"), max(s, lo), min(e, hi))
                 for name, s, e in ops_d if e > lo and s < hi]
        shares = self_times([(s, e) for _, s, e in ops_d])
        for i, (op, s, _) in enumerate(ops_d):
            k = bisect.bisect_right(starts, s) - 1
            module = (runs_d[k][2] if k >= 0 and s < runs_d[k][1]
                      else "no module")
            t = shares.get(i, 0.0) / n
            busy[module] += t
            name = names.get(module, {}).get(op)
            if name is None:
                unmatched[module] += t
            else:
                by_scope[module][scope(name)] += t
    return {
        "window_s": (hi - lo) * 1e-9,
        "runs": {m: c / n for m, c in runs.items()},
        "busy_s": {m: t * 1e-9 for m, t in busy.items()},
        "scope_s": {m: {k: t * 1e-9 for k, t in d.items()}
                    for m, d in by_scope.items()},
        "unmatched_s": {m: t * 1e-9 for m, t in unmatched.items()},
    }
