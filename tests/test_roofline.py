"""Roofline analyzer: HLO text parsing on synthetic modules."""

from repro.roofline.analysis import (RooflineTerms, _loop_multipliers,
                                     _split_computations, _type_bytes,
                                     collective_bytes_from_hlo, hlo_costs)

SYNTH = """\
HloModule jit_step, is_scheduled=true

%cond.1 (p0: (s32[], f32[8,8])) -> pred[] {
  %p0 = (s32[], f32[8,8]) parameter(0)
  %gte = s32[] get-tuple-element(%p0), index=0
  %c = s32[] constant(12)
  ROOT %lt = pred[] compare(%gte, %c), direction=LT
}

%body.1 (p0: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p0 = (s32[], f32[8,8]) parameter(0)
  %x = f32[8,8]{1,0} get-tuple-element(%p0), index=1
  %ar = f32[8,8]{1,0} all-reduce(%x), channel_id=1
  %d = f32[8,8]{1,0} dot(%ar, %ar), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %i = s32[] get-tuple-element(%p0), index=0
  ROOT %t = (s32[], f32[8,8]) tuple(%i, %d)
}

ENTRY %main (a: f32[16,32], b: f32[32,8]) -> f32[8,8] {
  %a = f32[16,32]{1,0} parameter(0)
  %b = f32[32,8]{1,0} parameter(1)
  %ag = f32[32,8]{1,0} all-gather(%b), channel_id=2, dimensions={0}
  %d0 = f32[16,8]{1,0} dot(%a, %ag), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %init = (s32[], f32[8,8]) tuple-thing(%d0)
  %w = (s32[], f32[8,8]) while(%init), condition=%cond.1, body=%body.1
  ROOT %out = f32[8,8]{1,0} get-tuple-element(%w), index=1
}
"""


def test_type_bytes():
    assert _type_bytes("f32[16,32]{1,0}") == 16 * 32 * 4
    assert _type_bytes("bf16[8]") == 16
    assert _type_bytes("s8[4,4]") == 16
    assert _type_bytes("pred[]") == 1


def test_split_and_multipliers():
    comps = _split_computations(SYNTH)
    assert set(comps) == {"cond.1", "body.1", "main"}
    mult = _loop_multipliers(comps)
    assert mult["main"] == 1.0
    assert mult["body.1"] == 12.0


def test_collective_bytes_trip_weighted():
    coll = collective_bytes_from_hlo(SYNTH)
    # all-gather operand f32[32,8] = 1024 B once; all-reduce f32[8,8]=256 B
    # × 12 trips
    assert coll["all-gather"] == 1024
    assert coll["all-reduce"] == 256 * 12
    assert coll["total"] == 1024 + 256 * 12


def test_dot_flops_trip_weighted():
    costs = hlo_costs(SYNTH)
    # entry dot: 2*16*8*32 = 8192; body dot: 2*8*8*8 × 12 = 12288
    assert costs["flops"] == 8192 + 12288


def test_roofline_terms_dominance():
    t = RooflineTerms(flops=197e12, bytes_accessed=819e9 * 2,
                      collective_bytes=50e9 * 0.5, chips=1,
                      model_flops=197e12 / 2)
    assert abs(t.compute_s - 1.0) < 1e-9
    assert abs(t.memory_s - 2.0) < 1e-9
    assert t.dominant == "memory"
    assert abs(t.roofline_fraction - 0.25) < 1e-9


def test_analytic_costmodel_sanity():
    import repro.configs as C
    from repro.roofline.costmodel import cell_costs
    from repro.configs import SHAPES
    cfg = C.get_config("qwen25-05b")
    cc_q = cell_costs(cfg, SHAPES["decode_32k"], quant=True)
    cc_f = cell_costs(cfg, SHAPES["decode_32k"], quant=False)
    # quantization cuts weight traffic ≈ 16/4.5 on quantizable linears; the
    # fp16 embedding stays (paper Table III: overall ≈ 55% reduction)
    assert cc_q.weight_bytes < 0.55 * cc_f.weight_bytes
    # decode is cache+weight bound, not flop bound
    assert cc_q.total_bytes / 819e9 > cc_q.flops / 197e12
    # train flops >> decode flops
    cc_t = cell_costs(cfg, SHAPES["train_4k"], quant=False)
    assert cc_t.flops > 1000 * cc_f.flops


def test_peaks_are_keyed_by_device_kind():
    """Peaks come from a table keyed by `device_kind`; a chip that is not
    in it is an error, not a silent v5e default."""
    import pytest

    from repro.roofline.analysis import PEAK_FLOPS, peaks
    assert peaks("TPU v5 lite").flops == PEAK_FLOPS == 197e12
    assert peaks("TPU v5 lite").hbm_bw == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        peaks("cpu")
