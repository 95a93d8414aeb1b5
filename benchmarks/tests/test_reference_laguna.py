"""The plain reference of the laguna block (reference/laguna.py) against the
program's own forward at `tiny-laguna` on the CPU, and its tolerances against
copies that are wrong in the ways the tolerances exist to catch: the same
weights rounded to e4m3, without the per-head gate, with the gate's other
activation, with the whole head rotated on the full layers, with a full
layer's head count on the window layers. The readings behind each tolerance
are in the reference's note."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import laguna as ref
from dstack_tpu.workloads.config import FULL, PRESETS, SLIDING, RopeParams
from dstack_tpu.workloads.transformer import forward, init_params

TINY = PRESETS["tiny-laguna"]
# The widening the CPU readings come from: the cell's 256 experts top-10 with
# 128 held and no token dropped, hidden 256, vocabulary 8,192, window 16;
# contexts of 128 positions are eight windows and four of YaRN's original
# lengths long.
WIDE = TINY.with_(
    dtype="bfloat16", d_model=256, vocab_size=8192, n_experts=256, experts_held=128,
    experts_per_token=10, capacity_factor=25.6, d_ff=64, dense_d_ff=512,
    sliding_window=16,
)


def e4m3(params):
    """The same weights rounded to an 8-bit float and back."""
    return jax.tree_util.tree_map(
        lambda w: w.astype(jnp.float8_e4m3fn).astype(w.dtype)
        if w.dtype == jnp.bfloat16 else w, params)


def whole_head_rotated(c, p):
    ropes = dict(c.rope_parameters)
    ropes[FULL] = RopeParams(**{**vars(ropes[FULL]), "partial_rotary_factor": 1.0})
    return c.with_(rope_parameters=tuple(ropes.items())), p


def full_layers_heads_everywhere(c, p):
    cut = {"wq": lambda w: w[:, :, :4 * 32], "wo": lambda w: w[:, :4 * 32],
           "wg": lambda w: w[:, :, :4]}
    mixers = {**p["mixers"], SLIDING: {w: cut[w](a) for w, a in p["mixers"][SLIDING].items()}}
    return c.with_(heads_per_layer=(4,) * c.n_layers), {**p, "mixers": mixers}


COPIES = {
    "bf16": lambda c, p: (c, p),
    "e4m3_weights": lambda c, p: (c, e4m3(p)),
    "no_gate": lambda c, p: (c.with_(attn_gate=""), p),
    "sigmoid_gate": lambda c, p: (c.with_(attn_gate="sigmoid"), p),
    "whole_head_rotated": whole_head_rotated,
    "full_layers_heads_everywhere": full_layers_heads_everywhere,
}


def test_float32_forward_is_the_reference_on_the_whole_bank_and_on_a_share():
    c = TINY.with_(dtype="float32")
    params = init_params(c, jax.random.PRNGKey(1))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (12, 64), 0, c.vocab_size)
    want = ref.logits(c, params, tokens)
    assert float(jnp.max(jnp.abs(forward(c, params, tokens) - want))) < 1e-4
    held = c.with_(experts_held=4, experts_first=2)
    bank = {w: params["layers"][w][:, 2:6] for w in ("we_gate", "we_up", "we_down")}
    share = {**params, "layers": {**params["layers"], **bank}}
    mine = ref.logits(held, share, tokens)
    assert float(jnp.max(jnp.abs(forward(held, share, tokens) - mine))) < 1e-4
    assert float(jnp.max(jnp.abs(mine - want))) > 1.0             # another result
    batch = {"inputs": tokens[:, :-1], "targets": tokens[:, 1:]}
    loss, aux, _ = ref.loss(c, params, batch)
    logp = jax.nn.log_softmax(forward(c, params, batch["inputs"]), axis=-1)
    got = -jnp.mean(jnp.take_along_axis(logp, batch["targets"][..., None], axis=-1))
    assert float(loss) == pytest.approx(float(got), abs=1e-5) and float(aux) == 0.0


def test_the_sizes_are_read_from_the_fields_a_cell_resolves():
    c = TINY
    sizes = ref._sizes({**vars(c), "rope_parameters": {
        FULL: {"rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
               "original_max_position_embeddings": 32, "partial_rotary_factor": 0.5}}})
    assert sizes["heads"] == (4, 6, 6, 6, 4) and sizes["head_dim"] == 32
    assert sizes["layer_types"][0] == FULL and sizes["n_dense_layers"] == 1
    assert ref._sizes(c)["rope"] == ref._sizes(vars(c))["rope"]
    width, freqs, scale = ref.rope_frequencies(dict(dict(sizes["rope"])[FULL]), 32)
    assert width == 16 and len(freqs) == 8 and scale == pytest.approx(0.1 * np.log(4) + 1)
    plain = ref._sizes(vars(PRESETS["tiny"]))
    assert plain["heads"] == (4, 4) and plain["attn_gate"] == "" and plain["experts_first"] == 0


@pytest.mark.parametrize("seed", [1, 2])
def test_bf16_forward_is_inside_the_logit_tolerance(seed):
    params = init_params(WIDE, jax.random.PRNGKey(seed))
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 100), (8, 128), 0, WIDE.vocab_size)
    _, stats = ref.hidden(WIDE, params, tokens)
    good = ref.check_logits(forward(WIDE, params, tokens), ref.logits(WIDE, params, tokens),
                            stats["margin"])
    assert good["ok"] and good["median_error_sd"] < ref.LOGIT_MEDIAN_TOL / 2, good
    assert good["rms_error_sd"] < 0.6 * ref.LOGIT_RMS_TOL, good
    assert good["positions"] > 0.5 * tokens.size, good


@pytest.mark.parametrize("fault", sorted(set(COPIES) - {"bf16"}))
def test_a_fault_fails_the_logit_tolerance(fault):
    params = init_params(WIDE, jax.random.PRNGKey(1))
    tokens = jax.random.randint(jax.random.PRNGKey(101), (8, 128), 0, WIDE.vocab_size)
    _, stats = ref.hidden(WIDE, params, tokens)
    bad_c, bad_params = COPIES[fault](WIDE, params)
    bad = ref.check_logits(forward(bad_c, bad_params, tokens),
                           ref.logits(WIDE, params, tokens), stats["margin"])
    assert not bad["ok"], bad
    assert bad["median_error_sd"] > 2 * ref.LOGIT_MEDIAN_TOL, bad
    assert bad["rms_error_sd"] > 1.5 * ref.LOGIT_RMS_TOL, bad


def greedy(c, params, prompts, steps):
    seq, out = prompts, []
    step = jax.jit(lambda p, s: jnp.argmax(forward(c, p, s)[:, -1], axis=-1))
    for _ in range(steps):
        tok = step(params, seq)
        out.append(tok)
        seq = jnp.concatenate([seq, tok[:, None]], axis=1)
    return np.asarray(jnp.stack(out, axis=1))


@pytest.fixture(scope="module")
def probe():
    """32 prompts of 120 tokens x 8 new tokens through the reference."""
    params = init_params(WIDE, jax.random.PRNGKey(1))
    prompts = jax.random.randint(jax.random.PRNGKey(301), (32, 120), 0, 256)
    return params, prompts, jax.device_get(ref.greedy_path(WIDE, params, prompts, 8))


@pytest.mark.parametrize("copy, ok", [
    ("bf16", True), ("e4m3_weights", False), ("no_gate", False), ("sigmoid_gate", False)])
def test_the_share_rule_tells_the_copies_from_the_program(copy, ok, probe):
    """The share rule's readings on the CPU at the widening above (32 rows x
    8 tokens, seeds 1-3, share of checked positions WITHIN LOGIT_TOL): the
    bf16 program 0.90-0.91; e4m3 weights 0.40-0.46; no gate and the sigmoid
    gate under 0.1. A row is checked until its first token off the
    reference's path, so at most one position a row is outside: the rule
    counts rows that END outside against positions checked. PASS_SHARE lies
    between the program and the e4m3 copy with room on both sides; the
    chip's readings at the cell's widths are in the reference's note."""
    params, prompts, ref_out = probe
    c, p = COPIES[copy](WIDE, params)
    result = ref.check_tokens(greedy(c, p, prompts, 8), *ref_out)
    assert result["ok"] == ok and result["checked"] >= ref.MIN_CHECKED, result
    if ok:
        assert result["pass_share"] > ref.PASS_SHARE + 0.2, result
        assert result["followed_reference"] > 90, result
    else:
        assert result["pass_share"] < ref.PASS_SHARE - 0.1, result


def test_too_few_positions_fail_whatever_they_read():
    logits = np.zeros((1, 2, 8), np.float32)
    logits[..., 3] = 1.0
    result = ref.check_tokens([[3, 3]], [[3, 3]], logits, np.ones((1, 2), np.float32))
    assert result["pass_share"] == 1.0 and result["checked"] == 2 and not result["ok"]
