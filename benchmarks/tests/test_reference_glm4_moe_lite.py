"""The plain reference of the glm4_moe_lite block (reference/glm4_moe_lite.py)
against the program's own forward at `tiny-latent` on the CPU, and its
tolerances against copies that are wrong in the ways the tolerances exist to
catch. The readings behind each tolerance are in the reference's header."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import glm4_moe_lite as ref
from dstack_tpu.workloads import moe
from dstack_tpu.workloads.attention import plain_attention
from dstack_tpu.workloads.config import PRESETS
from dstack_tpu.workloads.transformer import forward, init_params


def setup(dtype: str, seed: int = 1):
    c = PRESETS["tiny-latent"].with_(dtype=dtype)
    params = init_params(c, jax.random.PRNGKey(seed))
    # A selection bias that matters: published models start it at zero.
    bias = 0.1 * jax.random.normal(
        jax.random.PRNGKey(seed + 100), params["layers"]["router_bias"].shape)
    params = with_layers(params, router_bias=bias)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 200), (4, 128), 0, c.vocab_size)
    return c, params, tokens


def with_layers(params, **leaves):
    return {**params, "layers": {**params["layers"], **leaves}}


def e4m3(params):
    """The same weights rounded to an 8-bit float and back."""
    return jax.tree_util.tree_map(
        lambda w: w.astype(jnp.float8_e4m3fn).astype(w.dtype)
        if w.dtype == jnp.bfloat16 else w, params)


def one_row_late(q, k, v):
    """Every query reads the cache one position late."""
    return plain_attention(q, jnp.roll(k, 1, axis=1), jnp.roll(v, 1, axis=1))


def test_float32_forward_is_the_reference():
    c, params, tokens = setup("float32")
    got = forward(c, params, tokens)
    want = ref.logits(c, params, tokens)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4
    batch = {"inputs": tokens[:, :-1], "targets": tokens[:, 1:]}
    loss, aux, _ = ref.loss(c, params, batch)
    logp = jax.nn.log_softmax(forward(c, params, batch["inputs"]), axis=-1)
    mine = -jnp.mean(jnp.take_along_axis(logp, batch["targets"][..., None], axis=-1))
    assert float(loss) == pytest.approx(float(mine), abs=1e-5) and float(aux) == 0.0


def one_expert_zeroed(params):
    return with_layers(params, we_down=params["layers"]["we_down"].at[:, 0].set(0))


def not_renormalised(route_assignments):
    """The router with the chosen scores left as they are."""
    def raw(c, h, router, bias=None):
        _, idx, slot, sel, aux = route_assignments(c, h, router, bias)
        scores = jax.nn.sigmoid(jnp.einsum(
            "bsd,de->bse", h, router, preferred_element_type=jnp.float32))
        vals = c.routed_scaling * jnp.take_along_axis(scores, idx, axis=-1)
        return vals, idx, slot, sel, aux
    return raw


FAULTS = {
    "e4m3_weights": lambda c, p: (c, e4m3(p), None),
    "one_expert_zeroed": lambda c, p: (c, one_expert_zeroed(p), None),
    "shared_expert_dropped": lambda c, p: (
        c, with_layers(p, ws_down=jnp.zeros_like(p["layers"]["ws_down"])), None),
    "selection_bias_ignored": lambda c, p: (
        c, with_layers(p, router_bias=jnp.zeros_like(p["layers"]["router_bias"])), None),
    "routed_scaling_left_out": lambda c, p: (c.with_(routed_scaling=1.0), p, None),
    "top_k_not_renormalised": lambda c, p: (c, p, None),  # the test patches the router
    "cache_row_off_by_one": lambda c, p: (c, p, one_row_late),
    "tokens_dropped_at_capacity_1.25": lambda c, p: (c.with_(capacity_factor=1.25), p, None),
}


@pytest.mark.parametrize("seed", [1, 2])
def test_bf16_forward_is_inside_the_logit_tolerance(seed):
    c, params, tokens = setup("bfloat16", seed)
    _, stats = ref.hidden(c, params, tokens)
    want = ref.logits(c, params, tokens)
    good = ref.check_logits(forward(c, params, tokens), want, stats["margin"])
    assert good["ok"] and good["median_error_sd"] < ref.LOGIT_MEDIAN_TOL / 2, good
    assert good["rms_error_sd"] < ref.LOGIT_RMS_TOL / 2
    assert good["positions"] > 0.8 * tokens.size


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_fails_the_logit_tolerance(fault, monkeypatch):
    c, params, tokens = setup("bfloat16")
    if fault == "top_k_not_renormalised":
        monkeypatch.setattr(moe, "route_assignments",
                            not_renormalised(moe.route_assignments))
    _, stats = ref.hidden(c, params, tokens)
    want = ref.logits(c, params, tokens)
    bad_c, bad_params, attention_fn = FAULTS[fault](c, params)
    bad = ref.check_logits(
        forward(bad_c, bad_params, tokens, attention_fn=attention_fn), want,
        stats["margin"])
    assert not bad["ok"], bad
    assert (bad["median_error_sd"] > 1.5 * ref.LOGIT_MEDIAN_TOL
            or bad["rms_error_sd"] > 1.3 * ref.LOGIT_RMS_TOL), bad


def greedy(c, params, prompts, steps, attention_fn=None):
    seq, out = prompts, []
    for _ in range(steps):
        tok = jnp.argmax(forward(c, params, seq, attention_fn=attention_fn)[:, -1], axis=-1)
        out.append(tok)
        seq = jnp.concatenate([seq, tok[:, None]], axis=1)
    return np.asarray(jnp.stack(out, axis=1))


def test_greedy_tokens_are_held_to_the_reference_logits():
    c, params, _ = setup("bfloat16")
    prompts = jax.random.randint(jax.random.PRNGKey(3), (8, 96), 0, 256)
    ref_tokens, ref_logits, ref_margins = jax.device_get(
        ref.greedy_path(c, params, prompts, 4))
    good = ref.check_tokens(greedy(c, params, prompts, 4), ref_tokens, ref_logits,
                            ref_margins)
    assert good["ok"] and good["checked"] >= ref.MIN_CHECKED, good
    assert good["pass_share"] >= 0.9, good
    # What moves every position is outside: another model, a late cache row,
    # no shared expert.
    other = init_params(c, jax.random.PRNGKey(9))
    for tokens in (
        greedy(c, other, prompts, 4),
        greedy(c, params, prompts, 4, attention_fn=one_row_late),
        greedy(c, with_layers(params, ws_down=jnp.zeros_like(params["layers"]["ws_down"])),
               prompts, 4),
    ):
        bad = ref.check_tokens(tokens, ref_tokens, ref_logits, ref_margins)
        assert not bad["ok"] and bad["pass_share"] < ref.PASS_SHARE, bad


@pytest.mark.parametrize("copy, ok", [
    ("bf16", True), ("e4m3_weights", False), ("one_expert_zeroed", True)])
def test_tokens_tell_the_precision_below_apart_at_64_experts(copy, ok):
    """The share rule at the widening its readings come from (64 experts
    top-4, 1 + 6 layers, hidden 256, vocabulary 8,192; 32 rows x 4 tokens).
    CPU readings, share of checked positions outside LOGIT_TOL over seeds
    1-3: bf16 17-19%, e4m3 weights 57-70%, one of 64 experts zeroed 23-27%.
    The last is pinned as what tokens do NOT catch: only `check_logits`
    does, and no serving cell reads logits."""
    c = PRESETS["tiny-latent"].with_(
        dtype="bfloat16", n_experts=64, experts_per_token=4, capacity_factor=16.0,
        n_layers=7, d_model=256, vocab_size=8192)
    params = init_params(c, jax.random.PRNGKey(1))
    prompts = jax.random.randint(jax.random.PRNGKey(4), (32, 64), 0, 256)
    served = {"bf16": params, "e4m3_weights": e4m3(params),
              "one_expert_zeroed": one_expert_zeroed(params)}[copy]
    result = ref.check_tokens(
        greedy(c, served, prompts, 4),
        *jax.device_get(ref.greedy_path(c, params, prompts, 4)))
    assert result["ok"] == ok and result["checked"] >= 32, result
    if copy == "bf16":
        assert result["pass_share"] > 0.75, result
    elif not ok:
        assert result["pass_share"] < ref.PASS_SHARE - 0.05, result


def test_flipped_positions_are_a_minority_and_too_few_positions_fail():
    logits = np.zeros((4, 4, 16), np.float32)
    logits[..., 5] = 4.0
    ref_tokens = np.full((4, 4), 5)
    margins = np.full((4, 4), 1.0, np.float32)
    margins[0, 1] = 0.0005
    tokens = ref_tokens.copy()
    tokens[0, 1] = tokens[1, 0] = 3          # two rows leave the path early, far outside
    result = ref.check_tokens(tokens, ref_tokens, logits, margins)
    assert result["checked"] == 11 and result["passed"] == 9 and result["ok"]
    assert result["router_near_ties"] == 1 and result["followed_reference"] == 9
    tokens[:, 0] = 3                          # every row outside at once
    result = ref.check_tokens(tokens, ref_tokens, logits, margins)
    assert result["checked"] == 4 and not result["ok"]
    # and near-ties are left out of the logits' comparison, not failed
    sys_logits = logits.copy()
    sys_logits[0, 1] += 3.0 * np.arange(16)
    assert ref.check_logits(sys_logits, logits, margins)["router_ties_skipped"] == 1
