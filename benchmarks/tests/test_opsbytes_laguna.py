"""`opsbytes/kind_heads_paged_attention.py` against a count by hand, and
beside the file that counts one head count for every layer."""

from benchmarks.opsbytes import kind_heads_paged_attention as kind_heads
from benchmarks.opsbytes import window_paged_attention as one_count

FULL, SLIDING = "full_attention", "sliding_attention"
FIELDS = {
    "n_layers": 5, "layer_types": [FULL, SLIDING, SLIDING, SLIDING, FULL],
    "heads_per_layer": [48, 72, 72, 72, 48], "n_heads": 48, "n_kv_heads": 8,
    "head_size": 128, "d_model": 3072, "sliding_window": 512,
}
LIVE = {"rows": 16.0, "sum_ctx": 16 * 8400.0, "sum_window_ctx": 16 * 512.0}


def test_a_step_counts_each_layer_at_its_own_heads():
    need = kind_heads.step_calls(LIVE, FIELDS)
    full = (2 * 16 * 8400 * 8 * 128 + 2 * 16 * 48 * 128) * 2
    window = (2 * 16 * 512 * 8 * 128 + 2 * 16 * 72 * 128) * 2
    assert (full, window) == (550_895_616, 34_144_256)
    assert need["bytes"] == 2 * full + 3 * window == 1_204_224_000
    assert need["flops"] == 4 * 16 * 128 * (2 * 8400 * 48 + 3 * 512 * 72) == 7_511_998_464
    # one head count for every layer leaves out the 24 more heads' rows in
    # and out of the three sliding layers, and a third of their FLOPs
    other = one_count.step_calls(LIVE, FIELDS)
    assert need["bytes"] - other["bytes"] == 3 * 2 * 16 * 24 * 128 * 2
    assert need["flops"] - other["flops"] == 3 * 4 * 16 * 512 * 24 * 128


def test_nothing_to_read_without_heads_by_layer():
    obs = {"model_fields": {**FIELDS, "heads_per_layer": []}}
    assert kind_heads.needed(obs, {}, {}, {}) is None
