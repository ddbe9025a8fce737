"""Unit tests for the dry-run cost extraction (HLO collective parsing +
ring-model wire bytes + roofline terms)."""
import numpy as np
import pytest

from repro.launch.costs import (CostSummary, parse_collectives,
                                roofline_terms)

HLO = """
  %all-reduce.2 = f32[1,512,1024]{2,1,0} all-reduce(%x), channel_id=1, replica_groups=[32,16]<=[512], use_global_device_ids=true, to_apply=%add
  %all-gather.1 = bf16[16,4096]{1,0} all-gather(%y), channel_id=2, replica_groups=[16,32]<=[512], dimensions={0}
  %reduce-scatter.3 = f32[8,128]{1,0} reduce-scatter(%z), channel_id=3, replica_groups=[64,8]<=[512], dimensions={0}
  %all-to-all.9 = bf16[256,64]{1,0} all-to-all(%w), channel_id=4, replica_groups=[2,256]<=[512]
  %collective-permute.5 = f32[4,4]{1,0} collective-permute(%v), channel_id=5, source_target_pairs={{0,1}}
  %all-reduce.7 = f32[10]{0} all-reduce(%u), channel_id=6, replica_groups=[512,1]<=[512], to_apply=%add
"""


def test_parse_collectives_ring_model():
    out = parse_collectives(HLO)
    # group size 1 (last all-reduce) contributes nothing
    assert out["count"] == 5
    ar = 2 * 15 / 16 * (512 * 1024 * 4)  # f32[1,512,1024], g=16
    ag = 31 / 32 * (16 * 4096 * 2)  # bf16, g=32
    rs = 7 * (8 * 128 * 4)  # g=8, (g-1) * out
    a2a = 255 / 256 * (256 * 64 * 2)
    cp = 4 * 4 * 4
    want = ar + ag + rs + a2a + cp
    assert abs(out["wire_bytes"] - want) / want < 1e-9
    assert set(out["by_kind"]) == {"all-reduce", "all-gather",
                                   "reduce-scatter", "all-to-all",
                                   "collective-permute"}


def test_roofline_terms_dominance():
    c = CostSummary(flops=197e12, bytes_accessed=819e9 / 2,
                    coll_wire_bytes=50e9 / 4)
    t = roofline_terms(c, 256, device_kind="TPU v5 lite")
    assert abs(t["compute_s"] - 1.0) < 1e-9
    assert abs(t["memory_s"] - 0.5) < 1e-9
    assert abs(t["collective_s"] - 0.25) < 1e-9
    assert t["dominant"] == "compute"
    assert t["compute_fraction_of_bound"] == 1.0
    # the tpu estimate is half the HLO figure, floored by the analytic floor
    t2 = roofline_terms(c, 256, mem_floor_bytes=819e9,
                        device_kind="TPU v5 lite")
    assert abs(t2["memory_s_tpu_est"] - 1.0) < 1e-9
    # a kind without published peaks is an error, not a v5e default
    with pytest.raises(ValueError, match="cpu"):
        roofline_terms(c, 256, device_kind="cpu")


def test_scaled_add():
    a = CostSummary(flops=1.0, bytes_accessed=2.0, coll_wire_bytes=3.0,
                    coll_count=1, coll_by_kind={"all-reduce": 3.0})
    b = CostSummary()
    b.scaled_add(a, 5.0)
    assert b.flops == 5.0 and b.bytes_accessed == 10.0
    assert b.coll_by_kind["all-reduce"] == 15.0
