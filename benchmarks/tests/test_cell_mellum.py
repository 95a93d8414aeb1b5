"""The files of the cell `mellum2-12b.codeassist`: what the catalog row
says, cut where `reduced` says, and a mix in whole blocks of the cut's size.
(`--rehearsal` cannot run this cell: `rehearsal.json`'s 1,024 positions leave
no bucket for the mix's shrunk 1,024-token prompt, and its server of 4 slots
and 16 pending places refuses the probe's 32 prompts; PERF.md section 7.)"""

from benchmarks import cellfiles

CELL = "mellum2-12b.codeassist"


def test_the_files_hold_the_published_widths_and_list_every_cut():
    cell = cellfiles.Cell(CELL)
    f = cell.model_fields
    assert (f["d_model"], f["n_heads"], f["n_kv_heads"], f["head_size"]) == (2304, 32, 4, 128)
    assert (f["n_experts"], f["experts_per_token"], f["d_ff"]) == (64, 8, 896)
    assert f["vocab_size"] == 98304 and f["sliding_window"] == 1024 and f["norm_eps"] == 1e-6
    yarn = f["rope_parameters"]["full_attention"]
    assert (yarn["rope_type"], yarn["factor"], yarn["original_max_position_embeddings"]) == \
        ("yarn", 16, 8192)
    assert f["layer_types"] == ["sliding_attention"] * 3 + ["full_attention"] \
        + ["sliding_attention"] * 3 + ["full_attention"]
    assert f["n_layers"] == 8 and f["max_seq_len"] == 24576 and f["capacity_factor"] == 8.0
    published = cell.model["published"]
    assert published["num_hidden_layers"] == 28 and len(published["layer_types"]) == 28
    assert published["max_position_embeddings"] == 131072
    assert cell.model["reduced"] == ["num_hidden_layers", "layer_types", "max_position_embeddings"]
    # the mix: a head and one chunk of its own, in whole blocks of the cut's size
    block = cell.server_arg("--kv-block-size", 16)
    head = cell.mix["prompt"]["shared_head_tokens"]
    assert head % block == 0 and 1024 % block == 0 and f["max_seq_len"] % block == 0
    assert cell.mix["prompt"]["total_tokens"]["value"] - head == \
        cell.server_arg("--prefill-chunk-tokens", 0) == 512
