"""The plain reference of the mellum block (reference/mellum.py) against the
program's own forward at `tiny-window` on the CPU, and its tolerances against
copies that are wrong in the ways the tolerances exist to catch: the same
weights with every layer full (the window ignored), with plain RoPE on the
full layers (YaRN ignored), and rounded to e4m3. The readings behind each
tolerance are beside the constants in the reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import mellum as ref
from dstack_tpu.workloads.config import FULL, PRESETS, SLIDING
from dstack_tpu.workloads.transformer import forward, init_params

TINY = PRESETS["tiny-window"]
# The widening the share rule's CPU readings come from: 64 experts top-8 with
# no token dropped, hidden 256, vocabulary 8,192, 8 layers wwwf wwwf, window
# 16, YaRN factor 16 over 32 positions; contexts of 128 positions are eight
# windows and four original lengths long.
WIDE = TINY.with_(
    dtype="bfloat16", n_experts=64, experts_per_token=8, capacity_factor=8.0,
    d_model=256, d_ff=64, vocab_size=8192, sliding_window=16,
    rope_parameters={
        FULL: {"rope_type": "yarn", "rope_theta": 10000.0, "factor": 16.0,
               "original_max_position_embeddings": 32},
        SLIDING: {"rope_type": "default", "rope_theta": 10000.0}},
)


def e4m3(params):
    """The same weights rounded to an 8-bit float and back."""
    return jax.tree_util.tree_map(
        lambda w: w.astype(jnp.float8_e4m3fn).astype(w.dtype)
        if w.dtype == jnp.bfloat16 else w, params)


COPIES = {
    "bf16": lambda c, p: (c, p),
    "every_layer_full": lambda c, p: (c.with_(layer_types=(), sliding_window=0), p),
    "plain_rope_on_full_layers": lambda c, p: (
        c.with_(rope_parameters={}, rope_theta=10000.0), p),
    "e4m3_weights": lambda c, p: (c, e4m3(p)),
    # not asked for by name, caught all the same:
    "every_layer_a_window": lambda c, p: (c.with_(layer_types=(SLIDING,) * c.n_layers), p),
    "head_of_another_size": None,     # see test_the_head_size_is_the_published_one
}


def test_float32_forward_is_the_reference():
    c = TINY.with_(dtype="float32")
    params = init_params(c, jax.random.PRNGKey(1))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (20, 64), 0, c.vocab_size)
    got, aux = forward(c, params, tokens, return_aux=True)
    assert float(jnp.max(jnp.abs(got - ref.logits(c, params, tokens)))) < 1e-4
    batch = {"inputs": tokens[:, :-1], "targets": tokens[:, 1:]}
    loss, ref_aux, _ = ref.loss(c, params, batch)
    logits, aux = forward(c, params, batch["inputs"], return_aux=True)
    logp = jax.nn.log_softmax(logits, axis=-1)
    mine = -jnp.mean(jnp.take_along_axis(logp, batch["targets"][..., None], axis=-1))
    assert float(loss) == pytest.approx(float(mine), abs=1e-5)
    # the load-balance term over 20 rows, gathered over three row groups
    assert float(ref_aux) == pytest.approx(float(aux), rel=1e-5)


def test_the_head_size_is_the_published_one():
    """A reference handed no head size takes hidden / heads: another model."""
    c = TINY.with_(dtype="float32")
    fields = {**vars(c), "head_size": 0}
    assert ref._sizes(c)["head_dim"] == 32 and ref._sizes(fields)["head_dim"] == 24
    assert ref._sizes(vars(PRESETS["tiny"]))["layer_types"] == (FULL,) * 2


@pytest.mark.parametrize("seed", [1, 2])
def test_bf16_forward_is_inside_the_logit_tolerance(seed):
    params = init_params(WIDE, jax.random.PRNGKey(seed))
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 200), (8, 128), 0, WIDE.vocab_size)
    _, stats = ref.hidden(WIDE, params, tokens)
    good = ref.check_logits(forward(WIDE, params, tokens), ref.logits(WIDE, params, tokens),
                            stats["margin"])
    assert good["ok"] and good["median_error_sd"] < ref.LOGIT_MEDIAN_TOL / 2, good
    assert good["rms_error_sd"] < 0.6 * ref.LOGIT_RMS_TOL, good
    assert good["positions"] > 0.25 * tokens.size, good


@pytest.mark.parametrize("fault", ["every_layer_full", "plain_rope_on_full_layers",
                                   "e4m3_weights", "every_layer_a_window"])
def test_a_fault_fails_the_logit_tolerance(fault):
    params = init_params(WIDE, jax.random.PRNGKey(1))
    tokens = jax.random.randint(jax.random.PRNGKey(201), (8, 128), 0, WIDE.vocab_size)
    _, stats = ref.hidden(WIDE, params, tokens)
    bad_c, bad_params = COPIES[fault](WIDE, params)
    bad = ref.check_logits(forward(bad_c, bad_params, tokens),
                           ref.logits(WIDE, params, tokens), stats["margin"])
    assert not bad["ok"], bad
    assert bad["median_error_sd"] > 1.5 * ref.LOGIT_MEDIAN_TOL, bad
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
    """48 prompts of 124 tokens x 4 new tokens through the reference."""
    params = init_params(WIDE, jax.random.PRNGKey(1))
    prompts = jax.random.randint(jax.random.PRNGKey(301), (48, 124), 0, 256)
    return params, prompts, jax.device_get(ref.greedy_path(WIDE, params, prompts, 4))


@pytest.mark.parametrize("copy, ok", [
    ("bf16", True), ("every_layer_full", False),
    ("plain_rope_on_full_layers", False), ("e4m3_weights", False)])
def test_the_share_rule_tells_the_three_copies_from_the_program(copy, ok, probe):
    """The share rule's two readings on the CPU at the widening above (64
    rows x 4 tokens, seeds 1-4, share of checked positions OUTSIDE LOGIT_TOL):
    the bf16 program 0.4-1.4%; e4m3 weights 17-21%; plain RoPE on the full
    layers 26-31%; every layer full 100%. A row is checked until its first
    token off the reference's path, so at most one position a row is
    outside: the rule counts rows that END outside (bf16: 1-3 of 64; e4m3:
    24-28 of 64). PASS_SHARE lies between the two with room on both sides."""
    params, prompts, ref_out = probe
    c, p = COPIES[copy](WIDE, params)
    result = ref.check_tokens(greedy(c, p, prompts, 4), *ref_out)
    assert result["ok"] == ok and result["checked"] >= ref.MIN_CHECKED, result
    if ok:
        assert result["pass_share"] > ref.PASS_SHARE + 0.02 and result["followed_reference"] > 120, result
    else:
        assert result["pass_share"] < ref.PASS_SHARE - 0.05, result


def test_flipped_positions_are_a_minority_and_too_few_positions_fail():
    rows, steps = 24, 4
    logits = np.zeros((rows, steps, 16), np.float32)
    logits[..., 5] = 4.0
    ref_tokens = np.full((rows, steps), 5)
    margins = np.full((rows, steps), 1.0, np.float32)
    margins[0, 1] = 0.0005
    tokens = ref_tokens.copy()
    tokens[0, 1] = tokens[1, 0] = 3          # two rows leave the path early, far outside
    result = ref.check_tokens(tokens, ref_tokens, logits, margins)
    assert result["checked"] == 22 * 4 + 2 + 1 == 91 and result["passed"] == 89 and result["ok"]
    assert result["router_near_ties"] == 1 and result["followed_reference"] == 89
    tokens[:8, 0] = 3                         # a third of the rows outside at once
    result = ref.check_tokens(tokens, ref_tokens, logits, margins)
    assert result["checked"] == 16 * 4 + 8 and not result["ok"]
    # too few checked positions fail whatever their share
    few = ref.check_tokens(ref_tokens[:1], ref_tokens[:1], logits[:1], margins[:1])
    assert few["pass_share"] == 1.0 and few["checked"] == 4 < ref.MIN_CHECKED and not few["ok"]
    # and near-ties are left out of the logits' comparison, not failed
    sys_logits = logits.copy()
    sys_logits[0, 1] += 3.0 * np.arange(16)
    assert ref.check_logits(sys_logits, logits, margins)["router_ties_skipped"] == 1


def test_yarn_moves_every_position_not_only_the_far_ones():
    """Why the probe need not reach past the original context length: the
    blended frequencies differ from position 1 on."""
    plain, _ = ref.rope_frequencies({"rope_type": "default", "rope_theta": 500000}, 128)
    group = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
             "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1,
             "attention_factor": 1.2772588722239782}
    scaled, factor = ref.rope_frequencies(group, 128)
    assert factor == 1.2772588722239782 and scaled[:19] == plain[:19]
    assert all(s < p for s, p in zip(scaled[19:], plain[19:]))
    assert scaled[63] == pytest.approx(plain[63] / 16)
