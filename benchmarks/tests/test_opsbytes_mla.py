"""The latent-attention decode call's operations and bytes against a count
made by hand, and the call count taken from the trace's modules."""

from benchmarks.opsbytes import mla_attention


def test_latent_decode_at_16_rows_of_8192():
    # One layer, one decode step: 16 rows, 8192 cached positions each, 20
    # heads over one 512 + 64 row a position, bf16.
    got = mla_attention.decode_call(sum_ctx=16 * 8192, rows=16, heads=20,
                                    latent=512, rope=64)
    assert got["flops"] == 16 * 8192 * 20 * 2 * (576 + 512) == 5_704_253_440
    assert got["bytes"] == (16 * 8192 * 576 + 16 * 20 * (576 + 512)) * 2 == 151_691_264
    # Bound by bytes on a v5e: 185 us against 29 us of matmul. In GQA rows
    # at these widths (20 KV heads x (256 + 256)) the same call would read
    # 17.8 times as much.
    assert got["bytes"] / 819e9 > 6 * got["flops"] / 197e12
    gqa_row = 20 * (256 + 256)
    assert 17 < gqa_row / 576 < 18


def test_calls_are_counted_from_the_decode_modules_not_the_matched_ops():
    obs = {
        "trace_span": [10.0, 13.0],
        "requests": [{"first": 9.0, "last": 14.0, "tokens": 101, "prompt_tokens": 8192}] * 2,
        "model_fields": {"n_heads": 20, "kv_lora_rank": 512, "qk_rope_head_dim": 64,
                         "n_layers": 7},
        "stats": {"after": {"steps_per_sync": 4}},
    }
    reduced = {"devices": 1, "modules": {
        "jit_decode_steps": {"count": 30, "total_s": 1.0, "durations_s": []},
        "jit_chunk_prefill": {"count": 5, "total_s": 1.0, "durations_s": []}}}
    args = {"module": "jit_decode_steps"}
    one_impl = mla_attention.needed(obs, reduced, {"count": 840, "self_s": 1.0}, args)
    other_impl = mla_attention.needed(obs, reduced, {"count": 9000, "self_s": 1.0}, args)
    assert one_impl == other_impl
    live = mla_attention.live_context(obs)
    assert live["rows"] == 2.0
    per_call = mla_attention.decode_call(live["sum_ctx"], 2.0, 20, 512, 64)
    assert one_impl["bytes"] == per_call["bytes"] * 30 * 4 * 7
    # A configuration without a latent cache has nothing to read here.
    obs["model_fields"] = {"n_heads": 32, "n_layers": 4}
    assert mla_attention.needed(obs, reduced, {"count": 1, "self_s": 1.0}, args) is None
