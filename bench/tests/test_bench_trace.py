"""The reduction from a profiler trace to busy time, collectives and idle
gaps: on a trace recorded on a TPU v5 lite through the serving driver
(the 2-layer model of ``conftest.py``, two traced batches; the
``.xplane.pb`` is committed compressed with xz), and on a hand-made one."""
from __future__ import annotations

import lzma
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import trace

FIXTURE = Path(__file__).parent / "data" / "serve_trace.xplane.pb.xz"


def _profile(planes):
    return NS(planes=[NS(name=n, lines=[NS(name=ln, events=[
        NS(name=e, start_ns=s, duration_ns=d) for e, s, d in evs])
        for ln, evs in lines.items()]) for n, lines in planes.items()])


def test_merge_unions_overlaps():
    assert trace.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def test_reduce_by_hand():
    host = {"/host:CPU": {"python": [("bench.generate", 0, 100),
                                     ("bench.inputs", 100, 20),
                                     ("other", 0, 500)]}}
    dev = {
        "/device:TPU:0": {"XLA Ops": [("fusion.1", 10, 30),
                                      ("all-reduce.2", 30, 20),
                                      ("fusion.1", 200, 50)]},
        "/device:TPU:1": {"XLA Ops": [("fusion.1", 10, 60)]},
    }
    r = trace.reduce(_profile({**host, **dev}), 2)
    assert r["window_s"] == pytest.approx(120e-9)
    # device 0 busy [10, 50], device 1 [10, 70]; the op at 200 is outside
    assert r["busy_s"] == pytest.approx((40 + 60) / 2 * 1e-9)
    assert r["collective_s"] == pytest.approx(20 / 2 * 1e-9)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(45e-9)]
    # device 0 idles [0, 10] and [50, 120], both inside bench.generate
    assert r["idle_gaps"] == [["bench.generate", pytest.approx(70e-9)],
                              ["bench.generate", pytest.approx(10e-9)]]


def test_reduce_refuses_a_trace_without_device_ops():
    with pytest.raises(ValueError):
        trace.reduce(_profile({"/host:CPU": {"python": [
            ("bench.generate", 0, 10)]}}), 1)


def test_recorded_chip_trace():
    from jax.profiler import ProfileData

    profile = ProfileData.from_serialized_xspace(
        lzma.decompress(FIXTURE.read_bytes()))
    r = trace.reduce(profile, 1)
    # a 2-layer model of width 64: the host, not the chip, sets the pace
    assert 0 < r["busy_s"] < 0.05 * r["window_s"]
    assert r["window_s"] == pytest.approx(0.0497, rel=0.01)
    assert r["collective_s"] == 0
    assert len(r["device_ops"]) == 10 and len(r["idle_gaps"]) == 10
    assert all(t > 0 for _, t in r["device_ops"] + r["idle_gaps"])
    assert not any(n.startswith("%while") for n, _ in r["device_ops"])
    assert {n for n, _ in r["idle_gaps"]} <= {
        "bench.generate", "bench.inputs", "bench.wait_for_requests"}
