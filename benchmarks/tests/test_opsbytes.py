"""The functions that count a kernel's operations and bytes, against counts
made by hand at one shape each, and the table of peaks."""

import json
from pathlib import Path

import pytest

from benchmarks import cellfiles
from benchmarks.opsbytes import flash_attention, paged_attention, train_step

BENCH = Path(__file__).resolve().parents[1]


def test_paged_decode_at_20_rows_of_512():
    # One layer, one decode step: 20 rows, 512 cached positions each,
    # 32 query heads in 8 KV groups, head size 128, bf16.
    got = paged_attention.decode_call(sum_ctx=20 * 512, rows=20, heads=32,
                                      kv_heads=8, head_dim=128)
    assert got["flops"] == 20 * 4 * 512 * 32 * 128 == 167_772_160
    assert got["bytes"] == 20 * (2 * 512 * 8 * 128 + 2 * 32 * 128) * 2 == 42_270_720
    # Bound by bytes on a v5e: 51.6 us against 0.85 us of matmul.
    assert got["bytes"] / 819e9 > 50 * got["flops"] / 197e12


def test_flash_forward_and_backward_at_2_rows_of_4096():
    got = flash_attention.layer(rows=2, seq_len=4096, heads=32, kv_heads=8, head_dim=128)
    assert got["flops"] == 2 * 32 * 7 * 4096 ** 2 * 128 == 962_072_674_304
    assert got["bytes"] == 2 * 4096 * 128 * 2 * ((2 * 32 + 2 * 8) + (4 * 32 + 4 * 8)) \
        == 503_316_480
    # Bound by FLOPs: 4.9 ms against 0.6 ms of memory traffic.
    assert got["flops"] / 197e12 > 5 * got["bytes"] / 819e9


def test_model_flops_per_token_is_the_programs_own_count():
    from dstack_tpu.workloads.config import ModelConfig

    for name in ("mistral-7b.chat", "mixtral-8x7b.train"):
        fields = cellfiles.Cell(name).model_fields
        config = ModelConfig(**fields)
        assert train_step.flops_per_token(fields, 4096) == config.flops_per_token(4096)
    # Mixtral, 2 layers, by hand: attention projections 2*4096*(32+16)*128 +
    # 2*32*128*4096, two of eight experts 3*2*4096*14336*2 + router 2*4096*8,
    # causal scores 2*4096*32*128, head 2*4096*32000; three times for backward.
    per_layer = (2 * 4096 * 48 * 128 + 2 * 4096 * 4096
                 + 12 * 4096 * 14336 + 2 * 4096 * 8 + 2 * 4096 * 4096)
    assert train_step.flops_per_token(fields, 4096) == 3 * (2 * per_layer + 2 * 4096 * 32000)


def test_peaks_have_the_v5e_row_with_its_source():
    row = cellfiles.Cell("mistral-7b.chat").peaks("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    assert row["ici_bits_per_s"] == 1600e9
    assert "Google Cloud" in row["source"] and "v5e" in row["source"]
    with pytest.raises(cellfiles.CellError, match="no published peaks"):
        cellfiles.Cell("mistral-7b.chat").peaks("TPU v9")
    assert json.loads((BENCH / "peaks.json").read_text())["notes"]
