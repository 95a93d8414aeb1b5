"""The plain reference (reference/mistral.py) against the program's own
forward at `tiny` and `tiny-moe` on the CPU, and its tolerances against
copies that are wrong in the ways the tolerances exist to catch."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import mistral as ref
from dstack_tpu.workloads.config import PRESETS
from dstack_tpu.workloads.train import loss_fn
from dstack_tpu.workloads.transformer import forward, init_params


def setup(name: str, dtype: str, layers: int = 2):
    c = PRESETS[name].with_(dtype=dtype, n_layers=layers)
    if c.n_experts:
        # experts / experts per token: the dispatch drops nothing, as the cells run it
        c = c.with_(capacity_factor=c.n_experts / c.experts_per_token)
    params = init_params(c, jax.random.PRNGKey(1))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (4, 128), 0, c.vocab_size)
    return c, params, tokens


def e4m3(params):
    """The same weights rounded to an 8-bit float and back."""
    return jax.tree_util.tree_map(
        lambda w: w.astype(jnp.float8_e4m3fn).astype(w.dtype)
        if w.dtype == jnp.bfloat16 else w, params)


@pytest.mark.parametrize("name", ["tiny", "tiny-moe"])
def test_float32_forward_is_the_reference(name):
    c, params, tokens = setup(name, "float32")
    got = forward(c, params, tokens)
    want = ref.logits(c, params, tokens)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4
    batch = {"inputs": tokens[:, :-1], "targets": tokens[:, 1:]}
    loss, aux = loss_fn(c, params, batch)
    ref_loss, ref_aux, _ = ref.loss(c, params, batch)
    assert float(loss) == pytest.approx(float(ref_loss), abs=1e-5)
    assert float(aux) == pytest.approx(float(ref_aux), abs=1e-5)


@pytest.mark.parametrize("name,layers", [("tiny", 2), ("tiny", 8), ("tiny-moe", 2), ("tiny-moe", 8)])
def test_bf16_forward_is_inside_the_logit_tolerance_and_8_bit_is_not(name, layers):
    c, params, tokens = setup(name, "bfloat16", layers)
    _, stats = ref.hidden(c, params, tokens)
    want = ref.logits(c, params, tokens)
    good = ref.check_logits(forward(c, params, tokens), want, stats["margin"])
    assert good["ok"] and good["rms_error_sd"] < ref.LOGIT_RMS_TOL / 2, good
    bad = ref.check_logits(forward(c, e4m3(params), tokens), want, stats["margin"])
    assert not bad["ok"] and bad["rms_error_sd"] > 1.5 * ref.LOGIT_RMS_TOL, bad


def test_a_dropped_expert_and_dropped_tokens_fail_the_logit_tolerance():
    c, params, tokens = setup("tiny-moe", "bfloat16")
    _, stats = ref.hidden(c, params, tokens)
    want = ref.logits(c, params, tokens)
    layers = dict(params["layers"])
    layers["we_down"] = layers["we_down"].at[:, 0].set(0)
    dropped = ref.check_logits(forward(c, {**params, "layers": layers}, tokens),
                               want, stats["margin"])
    assert not dropped["ok"] and dropped["rms_error_sd"] > 0.3
    tight = ref.check_logits(forward(c.with_(capacity_factor=1.25), params, tokens),
                             want, stats["margin"])
    assert not tight["ok"]


def greedy(c, params, prompts, steps):
    seq, out = prompts, []
    for _ in range(steps):
        tok = jnp.argmax(forward(c, params, seq)[:, -1], axis=-1)
        out.append(tok)
        seq = jnp.concatenate([seq, tok[:, None]], axis=1)
    return np.asarray(jnp.stack(out, axis=1))


@pytest.mark.parametrize("name", ["tiny", "tiny-moe"])
def test_greedy_tokens_are_held_to_the_reference_logits(name):
    c, params, _ = setup(name, "bfloat16")
    prompts = jax.random.randint(jax.random.PRNGKey(3), (8, 128), 0, 256)
    ref_tokens, ref_logits, ref_margins = jax.device_get(
        ref.greedy_path(c, params, prompts, 4))
    good = ref.check_tokens(greedy(c, params, prompts, 4), ref_tokens, ref_logits,
                            ref_margins)
    assert good["ok"] and good["checked"] >= ref.MIN_CHECKED, good
    # Another model's tokens (other weights) are far outside the tolerance.
    other = init_params(c, jax.random.PRNGKey(9))
    bad = ref.check_tokens(greedy(c, other, prompts, 4), ref_tokens, ref_logits,
                           ref_margins)
    assert not bad["ok"] and bad["worst_gap_sd"] > 1.0, bad


def test_router_near_ties_are_left_out_not_failed():
    tokens = np.array([[5, 7]])
    ref_tokens = np.array([[5, 7]])
    logits = np.zeros((1, 2, 16), np.float32)
    logits[0, 0, 5] = logits[0, 1, 3] = 4.0      # the second token is "wrong" ...
    margins = np.array([[1.0, 0.01]], np.float32)  # ... where the router was tied
    result = ref.check_tokens(tokens, ref_tokens, logits, margins)
    assert result["checked"] == 1 and result["router_ties_skipped"] == 1
    assert result["passed"] == 1 and not result["ok"]  # under MIN_CHECKED positions
