"""The decode calls of a model that mixes window and full layers: operations
and bytes against a count made by hand, for a full layer, a window layer and
a context shorter than the window; the call count taken from the trace's
modules; nothing to read for a model of one kind."""

from benchmarks.opsbytes import window_paged_attention as wpa

SLIDING, FULL = "sliding_attention", "full_attention"
FIELDS = {"n_heads": 32, "n_kv_heads": 4, "d_model": 2304, "head_size": 128,
          "n_layers": 8, "sliding_window": 1024,
          "layer_types": [SLIDING, SLIDING, SLIDING, FULL] * 7}


def test_a_full_a_window_and_a_short_context_by_hand():
    # One layer, one decode step: 16 rows, 32 heads over 4 KV heads of 128, bf16.
    full = wpa.layer_call(sum_ctx=16 * 16500, rows=16, heads=32, kv_heads=4, head_dim=128)
    assert full["bytes"] == (2 * 16 * 16500 * 4 * 128 + 2 * 16 * 32 * 128) * 2 == 540_934_144
    assert full["flops"] == 4 * 16 * 16500 * 32 * 128 == 4_325_376_000
    window = wpa.layer_call(sum_ctx=16 * 1024, rows=16, heads=32, kv_heads=4, head_dim=128)
    assert window["bytes"] == (2 * 16 * 1024 * 4 * 128 + 131_072) * 2 == 33_816_576
    # A row inside the window reads what it has: 300 positions, not 1,024.
    short = wpa.layer_call(sum_ctx=300, rows=1, heads=32, kv_heads=4, head_dim=128)
    assert short["bytes"] == (2 * 300 * 4 * 128 + 2 * 32 * 128) * 2 == 630_784
    # Bound by bytes on a v5e, every one of them.
    for call in (full, window, short):
        assert call["bytes"] / 819e9 > 5 * call["flops"] / 197e12
    # A step of the cut's 8 layers (the pattern's first 8 entries: 6 window, 2
    # full) against 8 full layers: 1.28 GB, not 4.33.
    live = {"rows": 16.0, "sum_ctx": 16 * 16500.0, "sum_window_ctx": 16 * 1024.0}
    step = wpa.step_calls(live, FIELDS)
    assert step["bytes"] == 2 * 540_934_144 + 6 * 33_816_576 == 1_284_767_744
    every_layer_full = wpa.step_calls(live, {**FIELDS, "layer_types": [FULL] * 8})
    assert every_layer_full["bytes"] == 8 * 540_934_144 == 4_327_473_152
    # The published head size, not hidden / heads (72), where the file gives one.
    no_size = wpa.step_calls(live, {k: v for k, v in FIELDS.items() if k != "head_size"})
    assert no_size["bytes"] * 128 == step["bytes"] * 72


def obs(fields, prompt=16384, tokens=201):
    return {
        "trace_span": [10.0, 13.0],
        "requests": [{"first": 9.0, "last": 14.0, "tokens": tokens, "prompt_tokens": prompt}] * 2
        + [{"first": 9.0, "last": 14.0, "tokens": 101, "prompt_tokens": 512},
           {"first": None, "last": None, "tokens": None, "prompt_tokens": 16384}],
        "model_fields": fields,
        "stats": {"after": {"steps_per_sync": 4}},
    }


def test_live_contexts_cap_each_row_at_the_window():
    live = wpa.live_contexts(obs(FIELDS), 1024)
    assert live["rows"] == 3.0
    # Two rows grow 16,384 -> 16,584 over [9, 14]: 16,484 in the mean over
    # [10, 13]; the short row 512 -> 612: 562 in the mean, under the window.
    assert round(live["sum_ctx"]) == 2 * 16484 + 562
    assert round(live["sum_window_ctx"]) == 2 * 1024 + 562
    assert wpa.live_contexts({**obs(FIELDS), "trace_span": None}, 1024) is None


def test_calls_are_counted_from_the_decode_modules_not_the_matched_ops():
    reduced = {"devices": 1, "modules": {
        "jit_decode_steps": {"count": 30, "total_s": 1.0, "durations_s": []},
        "jit_chunk_prefill": {"count": 5, "total_s": 1.0, "durations_s": []}}}
    args = {"module": "jit_decode_steps"}
    one_impl = wpa.needed(obs(FIELDS), reduced, {"count": 960, "self_s": 1.0}, args)
    other_impl = wpa.needed(obs(FIELDS), reduced, {"count": 9000, "self_s": 1.0}, args)
    assert one_impl == other_impl
    live = wpa.live_contexts(obs(FIELDS), 1024)
    assert one_impl["bytes"] == wpa.step_calls(live, FIELDS)["bytes"] * 30 * 4
    # A configuration whose layers are of one kind has nothing to read here.
    plain = {"n_heads": 32, "n_kv_heads": 8, "d_model": 4096, "n_layers": 12}
    assert wpa.needed(obs(plain), reduced, {"count": 1, "self_s": 1.0}, args) is None
