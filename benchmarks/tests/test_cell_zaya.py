"""The files of the cell `zaya1-8b.reasoning`: what the catalog row says, cut
where `reduced` says (depth with its per-layer list, the context), the
sibling rows' mechanisms under `assumed`, the cell's mix and load,
the metrics the cell reports, and a rehearsal of the cell on the CPU."""

import json
import os
import subprocess
import sys

from benchmarks import cellfiles
from dstack_tpu.workloads.config import HYBRID, ModelConfig

CELL = "zaya1-8b.reasoning"
REPO = cellfiles.REPO
# The catalog row's `config` (model-configs guide, architectures.jsonl) but
# for its 40-entry list, which is one kind over and over.
CATALOG = {
    "attention_bias": False, "cca_time0": 2, "cca_time1": 2, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "lm_head_bias": False,
    "max_position_embeddings": 131072, "model_type": "zaya",
    "moe_intermediate_size": 2048, "num_attention_heads": 8, "num_experts": 16,
    "num_experts_per_tok": 1, "num_hidden_layers": 40, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
    "rope_parameters": {
        "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                   "rope_type": "default"},
        "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000,
                           "rope_type": "default"},
        "rope_type": "default"},
    "router_hidden_size": 256, "sliding_window": None, "tie_word_embeddings": True,
    "vocab_size": 262272, "layer_types": [HYBRID] * 40,
}


def test_the_files_hold_the_published_keys_and_list_every_cut():
    cell = cellfiles.Cell(CELL)
    reduced = ["num_hidden_layers", "layer_types", "max_position_embeddings"]
    assert cell.model["reduced"] == cell.config_entry["reduced"] == reduced
    assert list(cell.cut["reduced"]) == reduced
    for key, value in CATALOG.items():
        if key in reduced:
            assert cell.model["published"][key] == value, key
        else:
            assert cell.model[key] == value, key
    assert cell.model["num_hidden_layers"] == 20 and cell.model["layer_types"] == [HYBRID] * 20
    assert cell.model["max_position_embeddings"] == 6144 == 2048 + 4096
    assert "stage 1 of a 2-stage pipeline" in cell.cut["stands_for"]
    assert cell.config_entry["source"] == cell.model["source"]
    assert cell.chips == 1 and cell.generator == "closed_loop" and cell.load == {"callers": 50}
    for key in ("cca", "zaya_use_eda", "zaya_use_mod", "scale_residual_merge"):
        assert cell.model["assumed"][key] is True and "ZAYA1-VL-8B" in cell.model["assumed"][key + "_why"]
    for key in ("router_mlp", "value_shift", "conv_order", "qk_mean", "qk_l2_norm",
                "capacity_factor", "weights", "dtype"):
        assert key in cell.model["assumed"], key


def test_model_fields_build_the_published_model_at_the_cut():
    c = ModelConfig(**cellfiles.Cell(CELL).model_fields)
    # no width differs from the source
    assert (c.d_model, c.n_heads, c.n_kv_heads, c.head_dim, c.d_ff) == (2048, 8, 2, 128, 2048)
    assert (c.n_experts, c.experts_per_token, c.router_hidden_size) == (16, 1, 256)
    assert (c.cca_time0, c.cca_time1, c.vocab_size, c.norm_eps) == (2, 2, 262272, 1e-5)
    assert c.cca and c.expert_skip and c.tie_embeddings and c.n_choices == 17
    assert c.layer_types == (HYBRID,) * 20 and c.capacity_factor == 16.0
    assert c.rope(HYBRID).theta == 5000000 and c.rope(HYBRID).rotary_dim(128) == 64
    assert c.kv_row_bytes() == 1024 and c.cca_tail_width() == 2688
    assert c.param_count() == 4_688_574_644                 # 9.38 GB in bf16
    assert c.with_(n_layers=40, layer_types=(HYBRID,) * 40).param_count() == 8_840_014_184


def test_the_mix_the_pool_and_the_metrics_of_the_cell():
    cell = cellfiles.Cell(CELL)
    mix = cell.mix
    assert mix["prompt"] == {"shared_head_tokens": 0, "total_tokens": {
        "dist": "choice", "values": [128, 256, 512, 1024, 2048],
        "weights": [0.2, 0.3, 0.25, 0.15, 0.1]}}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 1536, "sigma": 0.6,
                                    "min": 256, "max": 4096}
    assert mix["sessions"] == {"requests": 1}
    assert (mix["lead_in_s"], mix["drain_s"]) == (45, 90)
    others = [cellfiles.load_json(p).get("trace_seed")
              for p in (REPO / "benchmarks" / "traffic").glob("*.json") if p.stem != "reasoning"]
    assert mix["trace_seed"] not in others
    block, positions = cell.server_arg("--kv-block-size", 16), cell.model_fields["max_seq_len"]
    assert positions % block == 0 and 512 % block == 0
    assert cell.server_arg("--slots", 0) == 40 and cell.server_arg("--max-new-tokens", 0) == 4096
    assert cell.server_arg("--prefill-chunk-tokens", 0) == 512
    probe, rehearsal = cell.cut["probe"], cellfiles.load_json(cellfiles.BENCH_DIR / "rehearsal.json")
    assert probe["prompts"] <= 20 and probe["prompt_tokens"] == max(mix["prompt"]["total_tokens"]["values"])
    assert (probe["prompt_tokens"] // rehearsal["length_divisor"] + probe["max_tokens"]
            <= rehearsal["model"]["max_position_embeddings"])
    # the pool holds the traffic's peak (359 blocks) with room, the probe's
    # rows and one whole request, and less than every slot's whole context
    pool = cell.server_arg("--kv-pool-blocks", 0)
    assert 1.3 * 359 < pool < 40 * positions // block
    assert pool >= probe["prompts"] * -(-(probe["prompt_tokens"] + probe["max_tokens"]) // block)
    assert {m["name"] for m in cell.metrics("end_to_end")} == \
        {"tpot_p50_ms", "serve_tok_s", "setup_s"}
    names = {m["name"] for m in cell.metrics("per_layer")}
    for name in ("programs.decode_attn_conv_share", "moe.skip_share"):
        entry = next(m for m in cell.bench["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["moves"] == "tpot_p50_ms"
        assert cellfiles.metric_file("per_layer", name)["reads"]
    for name in ("programs.decode_experts_share", "programs.decode_router_share",
                 "moe.slot_fill_share", "moe.routed_launch_share", "device.hbm_peak_share",
                 "kernels.hybrid_attn_roofline", "programs.decode_attn_conv_share",
                 "moe.skip_share", "kv.pool_peak_share", "programs.decode_step_ms"):
        assert name in names, name
    # the file that reads a head as hidden / heads (256 here, not 128), and
    # the window and gate files, are not this cell's
    for name in ("kernels.paged_attn_roofline", "programs.window_attn_share",
                 "programs.decode_attn_gate_share", "programs.decode_mlp_share"):
        assert name not in names, name
    entry = next(w for w in cell.bench["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["traffic"] == "reasoning"
    four = sum(w["chips"] == 4 for w in cell.bench["workloads"])
    assert four <= max(1, len(cell.bench["workloads"]) // 4)


def test_the_rehearsal_of_the_cell_keeps_every_mechanism():
    c = ModelConfig(**cellfiles.Cell(CELL, rehearsal=True).model_fields)
    assert c.layer_types == (HYBRID, HYBRID) and c.cca and c.expert_skip
    assert c.n_experts == 16 and c.head_dim == 32


def test_a_rehearsal_of_the_cell_prints_the_contracts_line():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed", "3000000039",
         "--seconds", "4", "--trace", "0", "--rehearsal"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["rehearsal"] and line["device"]["platform"] == "cpu"
    assert {"setup_s", "tpot_p50_ms", "serve_tok_s"} <= set(line["metrics"])
