"""The files of the cell `laguna-s-2.1.agent`: what the catalog row says, cut
where `reduced` says (depth with its four per-layer lists, the experts HELD,
the vocabulary's slice, the context), the published count of experts reaching
the program beside the count held, and a rehearsal of the cell on the CPU."""

import json
import os
import subprocess
import sys

from benchmarks import cellfiles
from dstack_tpu.workloads.config import FULL, SLIDING, ModelConfig

CELL = "laguna-s-2.1.agent"
REPO = cellfiles.REPO
PER_LAYER_LISTS = ("layer_types", "mlp_layer_types", "gating_types",
                   "num_attention_heads_per_layer")
# The catalog row's `config` (model-configs guide, architectures.jsonl) but
# for its four 48-entry lists, which are one period over and over.
CATALOG = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
    "intermediate_size": 12288, "num_hidden_layers": 48, "num_attention_heads": 48,
    "num_key_value_heads": 8, "head_dim": 128, "max_position_embeddings": 1048576,
    "attention_bias": False, "rms_norm_eps": 1e-06, "num_experts": 256,
    "num_experts_per_tok": 10, "moe_intermediate_size": 1024,
    "shared_expert_intermediate_size": 1024, "norm_topk_prob": True,
    "decoder_sparse_step": 1, "mlp_only_layers": [0], "tie_word_embeddings": False,
    "gating": "per-head", "sliding_window": 512,
    "moe_apply_router_weight_on_input": False, "moe_routed_scaling_factor": 2.5,
    "moe_router_logit_softcapping": 0,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1, "beta_fast": 32,
            "attention_factor": 1.4852030263919618, "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "layer_types": [FULL, SLIDING, SLIDING, SLIDING] * 12,
    "mlp_layer_types": ["dense"] + ["sparse"] * 47,
    "gating_types": ["per_head"] * 48,
    "num_attention_heads_per_layer": [48, 72, 72, 72] * 12,
}


def test_the_files_hold_the_published_keys_and_list_every_cut():
    cell = cellfiles.Cell(CELL)
    reduced = ["num_hidden_layers", *PER_LAYER_LISTS, "num_experts", "vocab_size",
               "max_position_embeddings"]
    assert sorted(cell.model["reduced"]) == sorted(reduced)
    assert cell.model["reduced"] == cell.config_entry["reduced"] == list(cell.cut["reduced"])
    for key, value in CATALOG.items():
        if key in cell.model["reduced"]:
            assert cell.model["published"][key] == value, key
        else:
            assert cell.model[key] == value, key
    assert set(cell.model["published"]) == set(reduced)
    # the depth and the four lists that go with it: their first 5 entries
    assert cell.model["num_hidden_layers"] == 5
    for key in PER_LAYER_LISTS:
        assert cell.model[key] == CATALOG[key][:5] == cell.cut["reduced"][key], key
    # the share: 128 of 256 experts, 50,176 of 100,352 rows, and the deployment
    assert cell.model["num_experts"] == 128 and cell.model["vocab_size"] == 50176
    assert "2 chips share each layer" in cell.model["deployment"]
    assert "2 chips" in cell.cut["stands_for"] and "pipeline stages" in cell.cut["stands_for"]
    assert cell.config_entry["source"] == cell.model["source"]
    assert cell.chips == 1 and cell.generator == "closed_loop" and cell.load == {"callers": 20}
    for key in ("gate_activation", "scoring_func", "router_bias", "qk_norm",
                "rope_pairing", "yarn_truncate", "capacity_factor", "weights", "dtype"):
        assert key in cell.model["assumed"], key


def test_model_fields_build_the_model_of_the_issue():
    cell = cellfiles.Cell(CELL)
    f = cell.model_fields
    c = ModelConfig(**f)
    # no width differs from the source
    assert (c.d_model, c.head_dim, c.n_kv_heads, c.d_ff, c.dense_d_ff) == \
        (3072, 128, 8, 1024, 12288)
    assert (c.heads(FULL), c.heads(SLIDING), c.sliding_window) == (48, 72, 512)
    assert (c.experts_per_token, c.routed_scaling, c.router_score) == (10, 2.5, "sigmoid")
    assert c.norm_eps == 1e-6 and c.attn_gate == "softplus" and c.capacity_factor == 25.6
    # the router at its published width over a bank told which experts it holds
    assert c.n_experts == 256 == cell.model["published"]["num_experts"]
    assert c.held == (0, 128) and c.expert_share and c.vocab_size == 50176
    # what the key map cannot derive reaches the program through the cut,
    # and is what the published keys say
    assert c.n_dense_layers == len(cell.model["mlp_only_layers"]) == 1
    assert c.n_shared_experts * cell.model["moe_intermediate_size"] == \
        cell.model["shared_expert_intermediate_size"]
    assert set(cell.cut["assumed_fields"]) == set(cell.cut["assumed_fields_why"])
    assert c.stack_kinds == ((FULL,), (SLIDING, SLIDING, SLIDING, FULL))
    assert c.rope(FULL).partial_rotary_factor == 0.5 and c.rope(FULL).rope_type == "yarn"
    assert c.rope(SLIDING).theta == 10000 and c.rope(SLIDING).rotary_dim(128) == 128
    assert c.param_count() == 5_572_076_544 and c.kv_row_bytes() == 4096
    whole = c.with_(n_layers=48, vocab_size=100352, experts_held=0, max_seq_len=1048576,
                    layer_types=CATALOG["layer_types"],
                    heads_per_layer=CATALOG["num_attention_heads_per_layer"])
    assert whole.param_count() == 117_561_953_280


def test_the_mix_the_pool_and_the_metrics_are_the_issues():
    cell = cellfiles.Cell(CELL)
    mix = cell.mix
    assert mix["prompt"] == {"shared_head_tokens": 7680,
                             "total_tokens": {"dist": "const", "value": 8192}}
    assert mix["output_tokens"] == {"dist": "uniform_int", "min": 128, "max": 256}
    assert mix["sessions"] == {"requests": 16}
    assert (mix["lead_in_s"], mix["drain_s"], mix["trace_seed"]) == (30, 30, 2701)
    block = cell.server_arg("--kv-block-size", 16)
    positions = cell.model_fields["max_seq_len"]
    assert 7680 % block == 0 and 512 % block == 0 and positions % block == 0
    assert cell.server_arg("--prefill-chunk-tokens", 0) == 512 == 8192 - 7680
    slots = cell.server_arg("--slots", 0)
    assert slots == 16 and positions >= 8192 + 256
    # the pool holds every caller's head and the live rows' own blocks
    own = -(-(512 + 256) // block)
    assert slots * positions // block >= cell.load["callers"] * 7680 // block + slots * own
    # a rehearsal's server has 4 slots and 16 pending places for the probe
    assert cell.cut["probe"]["prompts"] <= 20 and cell.cut["probe"]["prompt_tokens"] == 2048
    assert {m["name"] for m in cell.metrics("end_to_end")} == \
        {"tpot_p50_ms", "serve_tok_s", "setup_s"}
    names = {m["name"] for m in cell.metrics("per_layer")}
    for name in ("programs.decode_attn_gate_share", "moe.local_route_share",
                 "kernels.kind_heads_attn_roofline"):
        entry = next(m for m in cell.bench["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["moves"] == "tpot_p50_ms"
        assert name in names and cellfiles.metric_file("per_layer", name)["reads"]
    for name in ("programs.window_attn_share", "programs.full_attn_share",
                 "kernels.window_attended_share", "kv.window_dead_share",
                 "moe.slot_fill_share", "programs.decode_mlp_share",
                 "programs.decode_experts_share", "programs.decode_router_share",
                 "kernels.paged_live_block_share", "device.hbm_peak_share"):
        assert name in names, name
    # the one file that counts one head count for every layer is not this cell's
    assert "kernels.hybrid_attn_roofline" not in names
    # eight cells (ISSUE 37 counted nine: BENCHMARK.json held seven, not
    # eight, before this one), one of them on four chips
    assert len(cell.bench["workloads"]) == 8
    assert sum(w["chips"] == 4 for w in cell.bench["workloads"]) == 1


def test_the_rehearsal_of_the_cell_keeps_the_share_and_both_stacks():
    c = ModelConfig(**cellfiles.Cell(CELL, rehearsal=True).model_fields)
    assert c.layer_types == (FULL, SLIDING) and c.heads_per_layer == (48, 72)
    assert c.n_dense_layers == 1 and c.held == (0, 128) and c.n_experts == 256


def test_a_rehearsal_of_the_cell_prints_the_contracts_line():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed", "3000000037",
         "--seconds", "4", "--trace", "0", "--rehearsal"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["rehearsal"] and line["device"]["platform"] == "cpu"
    assert {"setup_s", "tpot_p50_ms", "serve_tok_s"} <= set(line["metrics"])
