"""Roofline tooling: HLO collective parsing (incl. while-loop trip-count
multiplication) and the three-term model arithmetic."""
import textwrap

import pytest

from repro.roofline.hlo import collective_bytes_nested


TOY_HLO = textwrap.dedent("""\
    HloModule toy

    %body (p: (s32[], f32[128])) -> (s32[], f32[128]) {
      %p = (s32[], f32[128]) parameter(0)
      %i = s32[] get-tuple-element(%p), index=0
      %x = f32[128] get-tuple-element(%p), index=1
      %ar = f32[128]{0} all-reduce(%x), replica_groups={}, to_apply=%sum
      %one = s32[] constant(1)
      %ni = s32[] add(%i, %one)
      ROOT %t = (s32[], f32[128]) tuple(%ni, %ar)
    }

    %cond (p: (s32[], f32[128])) -> pred[] {
      %p = (s32[], f32[128]) parameter(0)
      %i = s32[] get-tuple-element(%p), index=0
      %lim = s32[] constant(12)
      ROOT %lt = pred[] compare(%i, %lim), direction=LT
    }

    ENTRY %main (a: f32[128]) -> f32[128] {
      %a = f32[128] parameter(0)
      %ag = f32[256]{0} all-gather(%a), dimensions={0}
      %zero = s32[] constant(0)
      %init = (s32[], f32[128]) tuple(%zero, %a)
      %w = (s32[], f32[128]) while(%init), condition=%cond, body=%body
      ROOT %out = f32[128] get-tuple-element(%w), index=1
    }
""")


def test_collective_bytes_nested_multiplies_trip_count():
    out = collective_bytes_nested(TOY_HLO)
    # all-gather outside the loop: 256·4 bytes, once
    assert out.get("all-gather", 0) == 256 * 4
    # all-reduce inside a 12-trip while: 128·4·12
    assert out.get("all-reduce", 0) == 128 * 4 * 12


def test_roofline_terms_math():
    """Inputs are PER-DEVICE (verified: cost_analysis() of an SPMD module
    reports the per-device program), so the per-chip rates divide directly."""
    import dataclasses

    from repro.roofline.analysis import chip_peaks, roofline_terms
    terms = roofline_terms(flops=1.0e13, bytes_accessed=1.0e12,
                           collective_bytes=1.0e10, chips=256,
                           **dataclasses.asdict(chip_peaks("TPU v5 lite")))
    assert terms["t_compute_s"] == pytest.approx(1.0e13 / 197e12)
    assert terms["t_memory_s"] == pytest.approx(1.0e12 / 819e9)
    assert terms["t_collective_s"] == pytest.approx(1.0e10 / (2 * 50e9))
    assert terms["bottleneck"] == "memory"
    assert 0 < terms["roofline_fraction"] <= 1.0


def test_model_flops_formula():
    from repro.roofline.analysis import model_flops
    # dense: 6·N·D
    assert model_flops(1.0e9, 1.0e6) == pytest.approx(6e15)
