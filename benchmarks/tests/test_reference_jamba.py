"""The Jamba reference against a recurrence worked by hand, its causality, its
greedy path and the token rule on a tiny model."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import jamba as ref
from dstack_tpu.workloads.config import PRESETS
from dstack_tpu.workloads.transformer import init_params

CFG = PRESETS["tiny-mamba"].with_(dtype="float32")


def test_the_scan_is_the_recurrence_worked_by_hand():
    """Two channels, one state value each, three tokens: h_t = exp(delta_t a)
    h_{t-1} + delta_t u_t B_t, y_t = h_t C_t + D u_t."""
    delta = np.array([[0.5, 0.1], [1.0, 0.2], [0.25, 0.4]], np.float32)
    u = np.array([[1.0, -2.0], [0.5, 1.0], [-1.0, 3.0]], np.float32)
    b_in = np.array([[2.0], [1.0], [-1.0]], np.float32)
    c_out = np.array([[1.0], [0.5], [2.0]], np.float32)
    a = np.array([[-1.0], [-3.0]], np.float32)
    d_skip = np.array([1.0, 0.5], np.float32)
    want, h = [], np.zeros(2)
    for t in range(3):
        h = np.exp(delta[t] * a[:, 0]) * h + delta[t] * u[t] * b_in[t, 0]
        want.append(h * c_out[t, 0] + d_skip * u[t])
    # the first token by hand: h = [0.5 * 1 * 2, 0.1 * -2 * 2] = [1, -0.4]
    np.testing.assert_allclose(want[0], [1.0 + 1.0, -0.4 - 1.0], rtol=1e-6)
    got = ref.selective_scan(*map(jnp.asarray, (delta, u, b_in, c_out, a, d_skip)))
    np.testing.assert_allclose(got, np.stack(want), rtol=1e-6)


def test_a_position_sees_nothing_after_it_and_layers_are_of_both_kinds():
    params = init_params(CFG, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, CFG.vocab_size)
    full = ref.logits(CFG, params, tokens)
    changed = ref.logits(CFG, params, tokens.at[:, 16:].set(3))
    np.testing.assert_allclose(full[:, :16], changed[:, :16], atol=1e-5)
    assert float(jnp.max(jnp.abs(full[:, 16:] - changed[:, 16:]))) > 0.1
    assert ref._sizes(CFG)["layer_types"] == CFG.layer_types
    # the same sizes from the fields a cell resolves (a mapping, not a ModelConfig)
    fields = {k: getattr(CFG, k) for k in (
        "d_model", "n_heads", "n_kv_heads", "n_layers", "norm_eps", "mamba_d_state",
        "mamba_d_conv", "mamba_expand", "mamba_dt_rank", "attn_layer_period",
        "attn_layer_offset")}
    np.testing.assert_array_equal(ref.logits(fields, params, tokens), full)
    # no router: nothing is ever left out of a comparison
    margins = ref.hidden(CFG, params, tokens)[1]["margin"]
    assert margins.shape == tokens.shape and bool(jnp.all(jnp.isinf(margins)))


def test_greedy_path_and_the_token_rule():
    params = init_params(CFG, jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(2), (4, 12), 0, CFG.vocab_size)
    tokens, logits, margins = jax.device_get(ref.greedy_path(CFG, params, prompts, 4))
    assert tokens.shape == (4, 4) and logits.shape == (4, 4, CFG.vocab_size)
    # the path is the argmax of a full forward over prompt + path
    seq = jnp.concatenate([prompts, jnp.asarray(tokens)], axis=1)
    again = ref.logits(CFG, params, seq)[:, 11:-1]
    np.testing.assert_array_equal(np.argmax(np.asarray(again), axis=-1), tokens)
    good = ref.check_tokens(tokens.tolist(), tokens, logits, margins)
    assert good["ok"] and good["checked"] == 16 and good["followed_reference"] == 16
    # a wrong first token in every row: checked once a row, all outside
    worst = np.argmin(logits[:, 0], axis=-1)
    bad = [[int(w)] + row[1:] for w, row in zip(worst, tokens.tolist())]
    result = ref.check_tokens(bad, tokens, logits, margins)
    assert not result["ok"] and result["checked"] == 4 and result["passed"] == 0
    # logits a third of an sd off fail the logit rule, a thousandth passes
    noise = np.random.default_rng(0).standard_normal(logits.shape).astype(np.float32)
    assert ref.check_logits(logits + 1e-3 * noise, logits)["ok"]
    assert not ref.check_logits(logits + 0.3 * noise, logits)["ok"]
