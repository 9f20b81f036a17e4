"""Unit tests for HLO resource extraction and the TPU-ECM model."""
import pytest

from repro.core.hlo import (
    CollectiveOp,
    HLOResources,
    _shape_bytes,
    parse_collectives,
)
from repro.core.tpu_ecm import MeshSpec, TPUStepECM, from_resources, saturation_chips

HLO_SAMPLE = """\
HloModule jit_f, is_scheduled=true

%region_0.0.clone (x: f32[], y: f32[]) -> f32[] {
  ROOT %add = f32[] add(%x, %y)
}

ENTRY %main {
  %p0 = bf16[8,64]{1,0} parameter(0)
  %ag = bf16[8,512]{1,0} all-gather(%p0), channel_id=3, replica_groups=[2,8]<=[16], dimensions={1}
  %all-reduce = f32[1024]{0} all-reduce(%x), channel_id=1, replica_groups=[4,2]<=[8], use_global_device_ids=true, to_apply=%region_0.0.clone
  %rs = f32[256]{0} reduce-scatter(%y), channel_id=2, replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}, to_apply=%region_0.0.clone
  %a2a = (f32[16,16]{1,0}, f32[16,16]{1,0}) all-to-all(%a, %b), channel_id=4, replica_groups={{0,1}}
  %cp-start = bf16[32]{0} collective-permute-start(%z), channel_id=5, source_target_pairs={{0,1},{1,0}}
  %cp-done = bf16[32]{0} collective-permute-done(%cp-start)
  %ar2-start = f32[64]{0} all-reduce-start(%w), channel_id=6, replica_groups=[1,8]<=[8]
  %ar2-done = f32[64]{0} all-reduce-done(%ar2-start)
}
"""


def test_shape_bytes():
    assert _shape_bytes("f32[1024]{0}") == 4096
    assert _shape_bytes("bf16[8,64]{1,0}") == 1024
    assert _shape_bytes("(f32[16,16]{1,0}, f32[16,16]{1,0})") == 2048
    assert _shape_bytes("f32[]") == 4
    assert _shape_bytes("pred[8]") == 8


def test_parse_collectives_kinds_and_groups():
    ops = parse_collectives(HLO_SAMPLE, n_devices=16)
    kinds = sorted(o.kind for o in ops)
    assert kinds == ["all-gather", "all-reduce", "all-reduce",
                     "all-to-all", "collective-permute", "reduce-scatter"]
    ag = next(o for o in ops if o.kind == "all-gather")
    assert ag.out_bytes == 8 * 512 * 2
    assert ag.group_size == 8            # replica_groups=[2,8]
    ar = [o for o in ops if o.kind == "all-reduce"]
    assert {o.group_size for o in ar} == {2, 8}
    rs = next(o for o in ops if o.kind == "reduce-scatter")
    assert rs.group_size == 4            # {{0,1,2,3},{4,5,6,7}}
    a2a = next(o for o in ops if o.kind == "all-to-all")
    assert a2a.out_bytes == 2048 and a2a.group_size == 2
    cp = next(o for o in ops if o.kind == "collective-permute")
    assert cp.out_bytes == 64            # counted once (start only)


def test_wire_bytes_ring_multipliers():
    ar = CollectiveOp("all-reduce", out_bytes=100.0, group_size=4)
    assert ar.wire_bytes_per_chip == pytest.approx(2 * 0.75 * 100)
    ag = CollectiveOp("all-gather", out_bytes=100.0, group_size=4)
    assert ag.wire_bytes_per_chip == pytest.approx(0.75 * 100)
    cp = CollectiveOp("collective-permute", out_bytes=100.0, group_size=2)
    assert cp.wire_bytes_per_chip == 100.0


def test_real_jax_lowering_collectives():
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    if len(jax.devices()) != 1:
        pytest.skip("expects the default single-device test env")
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    f = lambda x: jnp.sum(x * 2.0)
    s = NamedSharding(mesh, P("data"))
    lowered = jax.jit(f, in_shardings=s).lower(
        jax.ShapeDtypeStruct((64,), jnp.float32))
    compiled = lowered.compile()
    from repro.core.hlo import analyze
    res = analyze(compiled, lowered, n_devices=1)
    assert res.flops > 0
    assert res.bytes_accessed > 0


def test_tpu_ecm_terms_and_dominance():
    res = HLOResources(flops=1e12, bytes_accessed=1e9)
    res.collectives = [CollectiveOp("all-reduce", out_bytes=2e8, group_size=16)]
    mesh = MeshSpec(shape=(16, 16), axes=("data", "model"), dcn_axes=())
    step = from_resources(res, mesh, flops_are_global=False, name="t")
    assert step.t_comp == pytest.approx(1e12 / 197e12)
    assert step.t_hbm == pytest.approx(1e9 / 819e9)
    # all-reduce wire bytes: 2*(15/16)*2e8 = 3.75e8 over 50GB/s
    assert step.t_ici == pytest.approx(3.75e8 / 50e9)
    assert step.dominant == "collective"
    assert step.t_roofline == pytest.approx(max(step.t_comp, step.t_hbm, step.t_ici))
    assert step.t_ecm >= step.t_roofline


def test_tpu_ecm_overlap_bounds():
    step = TPUStepECM(name="x", t_comp=1.0, t_hbm=0.5, t_ici=0.4,
                      exposed_ici_fraction=1.0, exposed_hbm_fraction=0.0)
    assert step.t_ecm == pytest.approx(1.4)     # compute + exposed ici
    full = TPUStepECM(name="x", t_comp=1.0, t_hbm=0.5, t_ici=0.4,
                      exposed_ici_fraction=0.0, exposed_hbm_fraction=0.0)
    assert full.t_ecm == pytest.approx(1.0)     # roofline limit


def test_multipod_dcn_split():
    res = HLOResources(flops=0.0, bytes_accessed=0.0)
    # group spanning both pods (512 chips)
    res.collectives = [CollectiveOp("all-reduce", out_bytes=1e9, group_size=512)]
    mesh = MeshSpec(shape=(2, 16, 16), axes=("pod", "data", "model"))
    step = from_resources(res, mesh, flops_are_global=False)
    assert step.t_dcn > 0
    # pod-local group: no DCN traffic
    res.collectives = [CollectiveOp("all-reduce", out_bytes=1e9, group_size=256)]
    step2 = from_resources(res, mesh, flops_are_global=False)
    assert step2.t_dcn == 0


def test_saturation_chips():
    step = TPUStepECM(name="x", t_comp=8.0, t_hbm=1.0, t_ici=2.0)
    assert saturation_chips(step, "collective") >= 1
