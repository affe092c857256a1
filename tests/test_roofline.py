"""Roofline analyzer: HLO collective parsing + term math."""
import pytest

from repro.configs import base
from repro.roofline import analysis as R

HLO = """
HloModule test
ENTRY main {
  %p0 = bf16[128,1024]{1,0} parameter(0)
  %ag = bf16[2048,1024]{1,0} all-gather(%p0), replica_groups={{0,1,2,3,4,5,6,7,8,9,10,11,12,13,14,15}}, dimensions={0}
  %ar = f32[256]{0} all-reduce-start(%x), replica_groups=[16,16]<=[256], to_apply=%add
  %ard = f32[256]{0} all-reduce-done(%ar)
  %rs = f32[64,8]{1,0} reduce-scatter(%y), replica_groups={{0,1,2,3}}, dimensions={0}
  %cp = s8[1024]{0} collective-permute-start(%z), source_target_pairs={{0,1},{1,0}}
  %cpd = s8[1024]{0} collective-permute-done(%cp)
  %a2a = bf16[32,32]{1,0} all-to-all(%w), replica_groups={{0,1}}, dimensions={0}
}
"""


def test_parse_collectives_kinds_and_counts():
    ops = R.parse_collectives(HLO, default_group=256)
    kinds = sorted(o.kind for o in ops)
    assert kinds == ["all-gather", "all-reduce", "all-to-all",
                     "collective-permute", "reduce-scatter"]


def test_group_size_parsing():
    ops = {o.kind: o for o in R.parse_collectives(HLO, default_group=99)}
    assert ops["all-gather"].group_size == 16      # brace list
    assert ops["all-reduce"].group_size == 16      # iota [16,16]
    assert ops["reduce-scatter"].group_size == 4
    assert ops["all-to-all"].group_size == 2


def test_wire_byte_math():
    ops = {o.kind: o for o in R.parse_collectives(HLO)}
    # all-gather: result 2048*1024*2B * (15/16)
    assert ops["all-gather"].wire_bytes == pytest.approx(
        2048 * 1024 * 2 * 15 / 16)
    # all-reduce: 2 * size * (g-1)/g
    assert ops["all-reduce"].wire_bytes == pytest.approx(
        2 * 256 * 4 * 15 / 16)
    # reduce-scatter: result * g * (g-1)/g
    assert ops["reduce-scatter"].wire_bytes == pytest.approx(
        64 * 8 * 4 * 4 * 3 / 4)
    # collective-permute: one hop, s8 => 1 byte/elem
    assert ops["collective-permute"].wire_bytes == pytest.approx(1024)


def test_async_pairs_counted_once():
    ops = R.parse_collectives(HLO)
    assert sum(1 for o in ops if o.kind == "all-reduce") == 1
    assert sum(1 for o in ops if o.kind == "collective-permute") == 1


def test_report_bottleneck_and_fraction():
    rep = R.analyze(
        arch="x", shape="train_4k", mesh_desc="16x16", chips=256,
        device_kind=R.V5E,
        cost={"flops": 1e12, "bytes accessed": 1e9}, hlo_text=HLO,
        model_flops_global=0.7 * 1e12 * 256)
    assert rep.compute_s == pytest.approx(1e12 / R.peaks(R.V5E).flops_bf16)
    assert rep.bottleneck == "compute"
    assert 0.6 < rep.roofline_fraction() <= 0.71
    assert rep.useful_flops_ratio == pytest.approx(0.7)


def test_model_flops_moe_counts_active_only():
    dense = base.get("granite-3-2b")
    moe = base.get("moonlight-16b-a3b")
    shape = base.SHAPES["train_4k"]
    f_moe = R.model_flops(moe, shape)
    # MoE: active params far fewer than total
    assert moe.n_active_params() < 0.5 * moe.n_params()
    assert f_moe < 6.0 * moe.n_params() * shape.global_batch * shape.seq_len


def test_decode_flops_use_one_token():
    cfg = base.get("granite-3-2b")
    tr = R.model_flops(cfg, base.SHAPES["train_4k"])
    dec = R.model_flops(cfg, base.SHAPES["decode_32k"])
    assert dec < tr / 100


def test_unknown_dtype_collectives_are_counted_not_dropped():
    """f8e8m0-style lines must surface in the report instead of silently
    undercounting wire bytes."""
    hlo = ("%ar = f8e8m0[4096]{0} all-reduce(%x), "
           "replica_groups={{0,1,2,3}}, to_apply=%add")
    ops = R.parse_collectives(hlo)
    assert len(ops) == 1
    assert ops[0].dtype == "f8e8m0"
    assert ops[0].elem_bytes == 0 and ops[0].wire_bytes == 0.0
    assert ops[0].shape == (4096,) and ops[0].group_size == 4
    rpt = R.analyze(arch="a", shape="s", mesh_desc="m", chips=4,
                    device_kind=R.V5E,
                    cost={"flops": 1e12, "bytes accessed": 1e9},
                    hlo_text=hlo, model_flops_global=1e12)
    assert "f8e8m0" in rpt.note and "lower bound" in rpt.note
    assert rpt.collectives_by_kind["all-reduce"]["count"] == 1
    assert rpt.collectives_by_kind["all-reduce"]["unknown_dtype"] == 1
    assert rpt.wire_bytes_per_device == 0.0


def test_known_dtype_report_has_no_unknown_note():
    rpt = R.analyze(arch="a", shape="s", mesh_desc="m", chips=4,
                    device_kind=R.V5E,
                    cost={"flops": 1e12, "bytes accessed": 1e9},
                    hlo_text=HLO, model_flops_global=1e12)
    assert rpt.note == ""
    assert all("unknown_dtype" not in e
               for e in rpt.collectives_by_kind.values())


def test_kernel_report_places_fn_on_roofline():
    import jax.numpy as jnp

    from repro.roofline.kernels import kernel_report

    def mm(a, b):
        return a @ b

    a = jnp.ones((256, 256), jnp.float32)
    rpt = kernel_report(mm, (a, a), name="mm", measure=True,
                        device_kind=R.V5E)
    assert rpt.name == "mm"
    assert rpt.bound in ("compute", "memory")
    assert rpt.roofline_s == max(rpt.compute_s, rpt.memory_s)
    assert rpt.ridge_intensity == R.peaks(R.V5E).ridge_intensity
    assert rpt.measured_s is not None and rpt.measured_s > 0
    assert rpt.achieved_fraction is not None
    d = rpt.to_dict()
    assert d["bound"] == rpt.bound and "flops" in d
    # overrides drive the placement when cost_analysis is not trusted
    # (interpret-mode pallas prices the interpreter, not the kernel)
    rpt2 = kernel_report(mm, (a, a), flops_override=1e9, bytes_override=1e6,
                         device_kind=R.V5E)
    assert rpt2.flops == 1e9 and rpt2.bytes_accessed == 1e6
    assert rpt2.intensity == pytest.approx(1e3)
    assert rpt2.bound == "compute"   # 5.1us of math vs 1.2us of HBM
    assert rpt2.measured_s is None


def test_peaks_table_v5e_row_and_source():
    v5e = R.peaks("TPU v5 lite")         # jax's device_kind for a v5e chip
    assert v5e.flops_bf16 == 197e12 and v5e.hbm_bw == 819e9
    assert "TPU v5e" in v5e.source
    assert v5e.ridge_intensity == pytest.approx(197e12 / 819e9)


def test_unknown_device_kind_raises_instead_of_assuming_v5e():
    import jax.numpy as jnp

    from repro.roofline.kernels import kernel_report
    with pytest.raises(KeyError, match="no published peaks"):
        R.peaks("cpu")
    with pytest.raises(KeyError, match="no published peaks"):
        R.analyze(arch="a", shape="s", mesh_desc="m", chips=1,
                  device_kind="TPU v9 imaginary", cost={"flops": 1.0},
                  hlo_text="", model_flops_global=1.0)
    # kernel_report defaults to the kind of the device it runs on: the
    # CPU test backend has no row, so pricing it must fail loudly
    a = jnp.ones((8, 8), jnp.float32)
    with pytest.raises(KeyError, match="no published peaks"):
        kernel_report(lambda x: x @ x, (a,), device_kind="cpu")
