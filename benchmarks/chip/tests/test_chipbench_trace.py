"""Trace reduction, operation and byte counts, and the peaks table."""
import gzip
import json

import pytest

import tiny
from harness import counts, peaks, xplane

# recorded on a TPU v5e by tools/record_trace.py (the tiny cell, three
# requests), gzipped
TRACE = tiny.HERE / "tests" / "data" / "tiny.xplane.pb.gz"
QWEN = json.loads((tiny.HERE / "configs" / "qwen1.5-0.5b.json")
                  .read_text())["serve"]


def test_counts_at_qwen_widths_by_hand():
    # q, k, v, o: 4 x 1024 x 1024 (16 heads of 64, MHA); gate, up, down:
    # 3 x 1024 x 2816; 24 layers; tied head 1024 x 151936
    layer = 4 * 1024 * 1024 + 3 * 1024 * 2816
    assert counts.layer_matmul_params(QWEN) == layer == 12_845_056
    assert counts.matmul_params(QWEN) == 24 * layer + 1024 * 151936 \
        == 463_863_808
    # one token at context 512: 2 FLOPs a weight, and q.k plus p.v over
    # 512 positions of 16 heads of 64 in each of 24 layers
    assert counts.decode_flops(QWEN, 512) == \
        2 * 463_863_808 + 4 * 24 * 512 * 16 * 64 == 978_059_264
    # fused_select over 16 slots: bf16 logits read and masked logits
    # written, one mask row and the residue words (4748 words) per slot
    assert counts.fused_select_bytes(16, 151936, 2) == \
        16 * (2 * 151936 * 2 + 2 * 4748 * 4) == 10_331_648
    v5e = peaks.peaks("TPU v5 lite")
    least, bound = counts.roofline_s(0.0, 10_331_648, v5e)
    assert bound == "bytes" and least == pytest.approx(12.615e-6, rel=1e-3)
    least, bound = counts.roofline_s(197e12, 1.0, v5e)
    assert bound == "flops" and least == pytest.approx(1.0)


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_union_clip_and_gaps():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (6, 9)]) == \
        [[0, 3], [5, 9]]
    assert xplane.clip([(0, 4), (6, 12)], 2, 10) == [(2, 4), (6, 10)]
    spans = [("host_oracle", 1.0, 0.5), ("forward", 1.25, 0.1)]
    named = xplane.name_gaps([(1100, 1300), (1700, 1800), (100, 150)],
                             spans, lambda t: t * 1e3)
    assert [n for n, _ in named] == ["host_oracle", "no_host_span",
                                     "no_host_span"]
    assert named[0][1] == pytest.approx(200e-9)


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    return ProfileData.from_serialized_xspace(gzip.decompress(
        TRACE.read_bytes()))


def test_recorded_trace_reduces(recorded):
    mark = xplane.marker_ns(recorded, "bench_clock_mark")
    assert mark is not None
    planes = xplane.device_planes(recorded)
    assert planes and planes[0].name.startswith("/device:TPU:")
    lo = mark
    hi = max(e.start_ns + e.duration_ns for p in planes for line in p.lines
             for e in line.events)
    red = xplane.reduce(recorded, lo, hi)
    assert red["devices"] == len(planes) == 1
    assert 0 < red["busy_s"] <= red["window_s"]
    # busy time is the union of op intervals: at most their sum
    assert red["busy_s"] <= sum(v[0] for v in red["ops"].values()) + 1e-12
    progs = red["programs"]
    assert any("decode_step" in k for k in progs), sorted(progs)
    assert any("fused_select" in k for k in red["ops"]), sorted(red["ops"])
    assert all(v[1] >= 1 for k, v in progs.items()
               if "decode_step" in k)
    gaps = red["gaps"]
    assert sum(e - s for s, e in gaps) * 1e-9 == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6, abs=1e-9)
    assert all(a[1] - a[0] >= b[1] - b[0] for a, b in zip(gaps, gaps[1:]))
