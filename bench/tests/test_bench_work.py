"""Work per stream-hop and the table of peaks."""
import json

import pytest

from bench_testkit import BENCH

import peaks
import work


def _cfg():
    return json.loads((BENCH / "configs" / "pscnn_kws_w64.json").read_text())


def test_macs_per_stream_hop_at_hop_frames_2():
    g = work.geometry(_cfg())
    assert (g.hop_samples, g.prime_samples) == (128, 112)
    assert work.macs_per_hop(g) == {
        "conv": 2_804_736, "flush": 1_082_560, "classifier": 186_368,
        "total": 4_073_664}
    assert work.ops_per_hop(g) == 8_147_328


def test_bytes_and_least_time():
    g = work.geometry(_cfg())
    # 8-bit audio + state read and written at 1 bit (8 bits for layer 0's
    # tail and the GAP counters) + 16-bit logits
    # tails 17x1x8, 2x64, 4x128, 2x256 bits; b3's pool phase 1x352 bits
    assert work.bytes_per_hop(g) == 128 + 2 * (17 + 16 + 64 + 64 + 44) \
        + 2 * 352 + 24
    assert work.weight_bytes(g) == 646_336 * 2 / 8
    p = peaks.peak("TPU v5 lite")
    t, bound = work.least_step_s(g, 4096, p)
    assert bound == "compute"
    assert t == pytest.approx(4096 * 8_147_328 / 393e12)
    assert work.least_step_s(g, 1, p)[1] == "memory"


def test_unknown_device_kind_is_an_error():
    assert peaks.peak("TPU v5 lite")["source"] == "Google Cloud, TPU v5e"
    with pytest.raises(KeyError):
        peaks.peak("cpu")
