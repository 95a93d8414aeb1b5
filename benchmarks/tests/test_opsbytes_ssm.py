"""The state update's operations and bytes against a count made by hand, and
the update count taken from the trace's modules."""

from benchmarks.opsbytes import selective_scan

FIELDS = {"n_layers": 28, "attn_layer_period": 14, "attn_layer_offset": 7, "d_model": 2560,
          "mamba_expand": 2, "mamba_d_state": 16, "mamba_d_conv": 4}


def test_a_decode_update_over_64_live_slots_and_a_chunk_of_300_tokens():
    state = 16 * 5120 * 4 + 3 * 5120 * 2
    assert state == 327_680 + 30_720
    token = 8 * 5120 + 8 * 16 + 2 * 5120            # x, z, delta, B, C in; y out
    got = selective_scan.update(tokens=64, rows=64, d_inner=5120, d_state=16, d_conv=4)
    assert got["bytes"] == 64 * (2 * state + token) == 49_160_192
    assert got["flops"] == 64 * (9 * 81_920 + 8 * 5120) == 49_807_360
    # bound by bytes on a v5e by a factor of hundreds: 60 us against 0.25 us
    assert got["bytes"] / 819e9 > 200 * got["flops"] / 197e12
    chunk = selective_scan.update(tokens=300, rows=1, d_inner=5120, d_state=16, d_conv=4)
    assert chunk["bytes"] == 2 * state + 300 * token == 16_115_200
    assert selective_scan.state_layers(FIELDS) == 26
    assert selective_scan.state_layers({"n_layers": 12}) == 0


def test_updates_are_counted_from_the_modules_not_the_matched_ops():
    obs = {
        "trace_span": [10.0, 13.0],
        "requests": [{"first": 9.0, "last": 14.0, "tokens": 101, "prompt_tokens": 512}] * 5,
        "model_fields": FIELDS,
        "stats": {"before": {"prefill_tokens_computed_total": 1000, "prefill_chunks_total": 10},
                  "after": {"steps_per_sync": 4, "prefill_tokens_computed_total": 4000,
                            "prefill_chunks_total": 20}},
    }
    reduced = {"devices": 1, "modules": {
        "jit_decode_steps": {"count": 30, "total_s": 1.0, "durations_s": []},
        "jit_chunk_prefill": {"count": 7, "total_s": 1.0, "durations_s": []}}}
    decode = {"module": "jit_decode_steps"}
    fused = selective_scan.needed(obs, reduced, {"count": 9360, "self_s": 1.0}, decode)
    kernel = selective_scan.needed(obs, reduced, {"count": 3120, "self_s": 1.0}, decode)
    assert fused == kernel
    one = selective_scan.update(5.0, 5.0, 5120, 16, 4)
    assert fused["bytes"] == one["bytes"] * 30 * 4 * 26
    chunk = selective_scan.needed(obs, reduced, {"count": 1, "self_s": 1.0},
                                  {"module": "jit_chunk_prefill"})
    assert chunk["bytes"] == selective_scan.update(300.0, 1.0, 5120, 16, 4)["bytes"] * 7 * 26
    # A configuration without state-space layers has nothing to read here.
    obs["model_fields"] = {"n_layers": 12, "d_model": 4096}
    assert selective_scan.needed(obs, reduced, {"count": 1, "self_s": 1.0}, decode) is None
