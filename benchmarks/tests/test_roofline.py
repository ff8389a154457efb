"""The FLOP and byte functions against shapes worked by hand."""

import json
import pathlib

import pytest

from benchmarks.roofline import (bound, flash_bwd, flash_fwd, flash_prefill,
                                 model_flops, paged_decode)

PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def test_paged_decode_one_lane():
    # one lane, 1000 tokens of context, 22 heads of 128, 1 K/V head:
    # QK^T 2*22*128*1000 and PV the same
    assert paged_decode.flops(1000, 22, 128) == 4 * 22 * 128 * 1000
    # K and V: 1000 * 128 * 2 B each; q and out: 22 * 128 * 2 B each
    assert paged_decode.bytes_moved(1000, 1, 22, 1, 128) == (
        2 * 1000 * 128 * 2 + 2 * 22 * 128 * 2)


def test_paged_decode_is_memory_bound_on_v5e():
    f = paged_decode.flops(32 * 1500, 22, 128)
    b = paged_decode.bytes_moved(32 * 1500, 32, 22, 1, 128)
    assert b / PEAKS["hbm_bytes_per_s"] > f / PEAKS["flops_bf16"]


def test_flash_prefill_first_chunk():
    # rows 0..3 see 1, 2, 3, 4 keys: 10 pairs
    assert flash_prefill.flops(0, 4, 2, 8) == 4 * 2 * 8 * 10
    # at offset 4: 5, 6, 7, 8 keys: 26 pairs
    assert flash_prefill.flops(4, 4, 2, 8) == 4 * 2 * 8 * 26
    assert flash_prefill.bytes_moved(4, 4, 2, 1, 8) == (
        2 * 8 * 1 * 8 * 2 + 2 * 4 * 2 * 8 * 2)


def test_flash_fwd_and_bwd_whole_sequences():
    pairs = 8 * 9 / 2
    assert flash_fwd.flops(3, 8, 2, 16) == 4 * 2 * 16 * 3 * pairs
    assert flash_bwd.flops(3, 8, 2, 16) == 2.5 * flash_fwd.flops(3, 8, 2, 16)
    # q, o at 2 heads; k, v at 1 head; lse f32
    assert flash_fwd.bytes_moved(1, 8, 2, 1, 16) == (
        2 * 8 * 2 * 16 * 2 + 2 * 8 * 1 * 16 * 2 + 8 * 2 * 4)


def test_train_flops_per_token_starcoder_1b_cut():
    # 8 layers of 2048: q 2048^2, kv 2048*256, proj 2048^2, mlp 2*2048*8192;
    # head 2048*49152
    layer = 2 * 2048 ** 2 + 2048 * 256 + 2 * 2048 * 8192
    matmul = 8 * layer + 2048 * 49152
    got = model_flops.train_flops_per_token(matmul, 8, 8192, 16, 128)
    attention = 3.5 * 4 * 16 * 128 * 8193 / 2 * 8
    assert got == pytest.approx(6 * matmul + attention)
    assert 3.5e9 < got < 3.7e9


def test_roofline_share_picks_the_larger_bound():
    assert bound.least_seconds(197e12, 1.0, PEAKS) == pytest.approx(1.0)
    assert bound.least_seconds(1.0, 819e9, PEAKS) == pytest.approx(1.0)
    assert bound.share(197e12, 1.0, 2.0, PEAKS) == pytest.approx(50.0)


def test_peaks_table_names_its_source_and_the_v5e():
    table = json.loads((pathlib.Path(__file__).parents[1] / "roofline"
                        / "peaks.json").read_text())
    assert "TPU v5e" in table["source"]
    v5e = table["devices"]["TPU v5 lite"]
    assert v5e["flops_bf16"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_is_an_error():
    from benchmarks.harness import common

    with pytest.raises(common.NoChip):
        common.peaks_for("cpu")
