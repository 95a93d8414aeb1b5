"""The files of the cell `jamba2-3b.chat-burst`: the catalog row's keys, the
context the only cut, and a `ModelConfig` that derives the layer order, the
parameter count and the state's size from them."""

from benchmarks import cellfiles
from dstack_tpu.workloads.config import FULL, MAMBA, ModelConfig

CELL = "jamba2-3b.chat-burst"
# The catalog row's `config` (model-configs guide, architectures.jsonl).
CATALOG = {
    "attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1,
    "expert_layer_period": 2, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 8192, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_state": 16, "mamba_dt_rank": 160, "mamba_expand": 2,
    "mamba_proj_bias": False, "max_position_embeddings": 262144, "model_type": "jamba",
    "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
    "num_hidden_layers": 28, "num_key_value_heads": 1, "num_logits_to_keep": 1,
    "rms_norm_eps": 1e-06, "sliding_window": None, "tie_word_embeddings": True,
    "use_mamba_kernels": True, "vocab_size": 65536,
}


def test_the_files_hold_the_catalog_rows_keys_and_cut_the_context_only():
    cell = cellfiles.Cell(CELL)
    assert cell.model["reduced"] == ["max_position_embeddings"] == cell.config_entry["reduced"]
    assert cell.model["published"] == {"max_position_embeddings": 262144}
    for key, value in CATALOG.items():
        if key not in cell.model["reduced"]:
            assert cell.model[key] == value, key
    assert cell.model["max_position_embeddings"] == 4608 == cell.cut["reduced"]["max_position_embeddings"]
    assert cell.config_entry["source"] == cell.model["source"]
    assert cell.chips == 1 and cell.generator == "open_loop"


def test_model_fields_build_the_model_of_the_issue():
    cell = cellfiles.Cell(CELL)
    c = ModelConfig(**cell.model_fields)
    period = (MAMBA,) * 7 + (FULL,) + (MAMBA,) * 6
    assert c.layer_types == period * 2 and c.layer_period == period
    assert c.param_count() == 3_029_337_472
    assert c.state_row_bytes() == 9_318_400
    assert (c.n_heads, c.n_kv_heads, c.head_dim, c.n_attn_layers) == (20, 1, 128, 2)
    assert c.tie_embeddings and not c.use_rope and c.n_experts == 0
    assert c.max_seq_len == 4608 and c.vocab_size == 65536 and c.d_ff == 8192


def test_the_mix_and_the_load_are_the_issues():
    cell = cellfiles.Cell(CELL)
    mix = cell.mix
    assert mix["sessions"] == {"arrival_cv": mix["sessions"]["arrival_cv"], "requests": 1,
                               "reask_gap_mean_s": 0.0}
    assert mix["sessions"]["arrival_cv"] in (2.5, 2.0)          # the ladder's last rung is 2.0
    assert mix["prompt"]["shared_head_tokens"] == 0
    lengths = mix["prompt"]["total_tokens"]
    assert lengths["values"] == [64, 128, 256, 512, 1024, 2048]
    assert lengths["weights"] == [0.15, 0.25, 0.25, 0.2, 0.1, 0.05]
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 128, "sigma": 0.7,
                                    "min": 16, "max": 512}
    assert (mix["lead_in_s"], mix["drain_s"], mix["trace_seed"]) == (10, 30, 3301)
    # at least 200 requests due in the window the driver measures
    assert cell.load["rate_rps"] * cell.bench["run_seconds"] >= 200
    block = cell.server_arg("--kv-block-size", 16)
    assert 4608 % block == 0 and cell.server_arg("--prefill-chunk-tokens", 0) == 512
    assert cell.server_arg("--slots", 0) >= 64
    probe = cell.cut["probe"]
    assert (probe["prompts"], probe["prompt_tokens"], probe["max_tokens"]) == (16, 1024, 8)
    # every metric the cell reports has a file, and the five new ones are its alone
    names = {m["name"] for m in cell.metrics("per_layer")}
    for name in ("kernels.ssm_decode_roofline", "kernels.ssm_prefill_roofline",
                 "programs.mamba_mixer_share", "programs.ssm_prefill_scan_share",
                 "kv.live_state_share"):
        assert name in names and cellfiles.metric_file("per_layer", name)["reads"]
    assert "serve_tok_s" not in {m["name"] for m in cell.metrics("end_to_end")}


def test_the_rehearsal_of_the_cell_holds_no_attention_layer():
    c = ModelConfig(**cellfiles.Cell(CELL, rehearsal=True).model_fields)
    assert c.layer_types == (MAMBA, MAMBA) and c.n_attn_layers == 0
