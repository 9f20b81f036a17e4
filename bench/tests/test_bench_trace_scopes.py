"""The reduction by program launch and named scope: on a hand-made
profile, and on a trace recorded on a TPU v5 lite through the serving
driver (the 2-layer model of ``conftest.py``, two traced batches of 4
generated tokens; the ``.xplane.pb`` and the compiled HLO of its two steps
are committed compressed with xz)."""
from __future__ import annotations

import lzma
import re
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import trace_scopes

DATA = Path(__file__).parent / "data"
TRACE = DATA / "serve_scopes_trace.xplane.pb.xz"
HLO = DATA / "serve_scopes_hlo.txt.xz"
#: generated positions in the recorded window: 2 calls x (4 steps + 1)
POSITIONS = 10

HLO_TEXT = """HloModule jit_decode, is_scheduled=true, entry_computation_layout={()}

%fused_computation (param_0: f32[2]) -> f32[2] {
  %param_0 = f32[2]{0} parameter(0)
  ROOT %multiply.1 = f32[2]{0} multiply(%param_0, %param_0), metadata={op_name="jit(decode)/mlp/mul" source_file="lm.py" source_line=3}
}

ENTRY %main.5 (p: f32[2]) -> f32[2] {
  %p = f32[2]{0} parameter(0)
  ROOT %fusion.2 = f32[2]{0} fusion(%p), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(decode)/while/body/attn/mlp/mul" stack_frame_id=1}
}
"""


def _profile(planes):
    return NS(planes=[NS(name=n, lines=[NS(name=ln, events=[
        NS(name=e, start_ns=s, duration_ns=d) for e, s, d in evs])
        for ln, evs in lines.items()]) for n, lines in planes.items()])


NAMES = {
    "jit_prefill": {"while.3": "jit(prefill)/while",
                    "fusion.4": "jit(prefill)/while/body/attn/dot_general",
                    "convert.5": "jit(prefill)/while/body/mlp/convert",
                    "fusion.6": "jit(prefill)/head/dot_general",
                    "copy.7": ""},
    "jit_decode": {"fusion.9": "jit(decode)/while/body/attn/dot_general",
                   "fusion.10": "jit(decode)/while/body/attn/mlp/convert"},
}

HAND = {
    "/host:CPU": {"python": [
        ("bench.generate", 0, 1000), ("bench.inputs", 1000, 50),
        ("serve.prefill", 10, 290), ("serve.decode", 350, 550),
        ("serve.decode_step", 355, 64),
        # a program span outside every bench span widens nothing
        ("serve.to_host", 1100, 100), ("other", 0, 5000)]},
    "/device:TPU:0": {
        "XLA Modules": [("jit_prefill(111)", 50, 200), ("jit_less(222)", 300, 5),
                        ("jit_decode(333)", 420, 380),
                        ("jit_decode(333)", 2000, 100)],
        "XLA Ops": [
            ("%fusion.1 = f32[2] fusion()", 50, 10),      # not in the map
            ("%while.3 = (f32[2]) while()", 60, 140),
            ("%fusion.4 = f32[2] fusion()", 70, 50),
            ("%convert.5 = bf16[2] convert()", 130, 60),
            ("%fusion.6 = f32[2] fusion()", 200, 40),
            ("%copy.7 = f32[2] copy()", 240, 10),
            ("%less.1 = pred[] compare()", 300, 5),        # a module with no map
            ("%fusion.9 = f32[2] fusion()", 420, 180),
            ("%fusion.10 = f32[2] fusion()", 600, 200),
            ("%fusion.9 = f32[2] fusion()", 2000, 100)]},   # outside the window
}


def test_op_names_from_compiled_hlo():
    module, names = trace_scopes.op_names(HLO_TEXT)
    assert module == "jit_decode"
    assert names == {"param_0": "", "multiply.1": "jit(decode)/mlp/mul",
                     "p": "", "fusion.2": "jit(decode)/while/body/attn/mlp/mul"}


@pytest.mark.parametrize("op_name, scope", [
    ("jit(decode)/while/body/attn/dot_general", "attn"),
    ("jit(decode)/while/body/attn/mlp/convert", "mlp"),
    ("jit(prefill)/head/dot_general", "head"),
    ("jit(prefill)/while/body/dynamic_slice", "other"),
    ("jit(prefill)/attention/mlpx/dot", "other"),
    ("", "other"),
])
def test_scope_is_the_innermost(op_name, scope):
    assert trace_scopes.scope(op_name) == scope


@pytest.mark.parametrize("ops, shares", [
    ([(0, 10)], {0: 10}),
    ([(0, 10), (2, 4), (6, 9)], {0: 5, 1: 2, 2: 3}),      # nested
    ([(0, 10), (5, 15)], {0: 5, 1: 10}),                   # overlapping
    ([(0, 4), (6, 8)], {0: 4, 1: 2}),                      # disjoint
])
def test_self_times_partition_the_union(ops, shares):
    assert dict(trace_scopes.self_times(ops)) == shares


def test_module_name_drops_the_fingerprint():
    assert trace_scopes.module_name("jit_decode(8666549578861363896)") == \
        "jit_decode"


def test_reduce_by_hand():
    r = trace_scopes.reduce(_profile(HAND), 1, NAMES)
    # the window is the bench spans': serve.to_host does not widen it
    assert r["window_s"] == pytest.approx(1050e-9)
    assert r["runs"] == {"jit_prefill": 1, "jit_less": 1, "jit_decode": 1}
    assert r["busy_s"] == pytest.approx(
        {"jit_prefill": 200e-9, "jit_less": 5e-9, "jit_decode": 380e-9})
    # the while keeps only what its body leaves uncovered: 3 x 10
    assert r["scope_s"]["jit_prefill"] == pytest.approx(
        {"attn": 50e-9, "mlp": 60e-9, "head": 40e-9, "other": 40e-9})
    assert r["scope_s"]["jit_decode"] == pytest.approx(
        {"attn": 180e-9, "mlp": 200e-9})
    assert r["unmatched_s"] == pytest.approx(
        {"jit_prefill": 10e-9, "jit_less": 5e-9})


def test_reduce_refuses_a_trace_without_bench_spans():
    with pytest.raises(ValueError):
        trace_scopes.reduce(_profile({"/host:CPU": {"python": [
            ("serve.prefill", 0, 10)]}}), 1, {})


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    profile = ProfileData.from_serialized_xspace(
        lzma.decompress(TRACE.read_bytes()))
    hlo = lzma.decompress(HLO.read_bytes()).decode()
    names = dict(trace_scopes.op_names(text)
                 for text in re.split(r"\n(?=HloModule )", hlo))
    assert set(names) == {"jit_prefill", "jit_decode"}
    return trace_scopes.reduce(profile, 1, names)


@pytest.mark.parametrize("module", ["jit_prefill", "jit_decode"])
def test_recorded_chip_trace_by_scope(recorded, module):
    busy = recorded["busy_s"][module]
    by_scope = recorded["scope_s"][module]
    assert busy > 0
    assert module not in recorded["unmatched_s"]
    assert sum(by_scope.values()) == pytest.approx(busy)
    assert set(by_scope) == {"attn", "mlp", "head", "other"}
    assert all(t > 0 for t in by_scope.values())


def test_recorded_chip_trace_launches(recorded):
    # 2 prefills, 8 decodes, 11 eager programs per position, 2 joins
    assert recorded["runs"]["jit_prefill"] == 2
    assert recorded["runs"]["jit_decode"] == 8
    assert sum(recorded["runs"].values()) / POSITIONS == pytest.approx(12.2)
    # the programs outside the two steps have no map: all unmatched
    assert set(recorded["unmatched_s"]) <= set(recorded["runs"]) - {
        "jit_prefill", "jit_decode"}
