"""The plain reference of the zaya block (reference/zaya.py) against the
program's own forward at `tiny-zaya` on the CPU, and its tolerances against
copies that are wrong in the ways the tolerances exist to catch: the same
weights rounded to e4m3, and the reference with one mechanism taken out (the
convolutions, the value shift, the router's depth carry, the skip). The
readings behind each tolerance are in the reference's note."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import zaya as ref
from dstack_tpu.workloads.config import HYBRID, PRESETS
from dstack_tpu.workloads.transformer import forward, init_params

TINY = PRESETS["tiny-zaya"]
# The widening the CPU readings come from: 16 experts top-1 beside the skip,
# experts as wide as the hidden size (as published), 8 query and 2 KV heads,
# a router of 64, 4 layers.
WIDE = TINY.with_(
    dtype="bfloat16", d_model=256, n_heads=8, n_kv_heads=2, head_size=32,
    vocab_size=8192, n_experts=16, capacity_factor=16.0, d_ff=256,
    layer_types=(HYBRID,) * 4, n_layers=4, router_hidden_size=64,
)


def e4m3(params):
    """The same weights rounded to an 8-bit float and back."""
    return jax.tree_util.tree_map(
        lambda w: w.astype(jnp.float8_e4m3fn).astype(w.dtype)
        if w.dtype == jnp.bfloat16 else w, params)


def copy_logits(copy, c, params, tokens):
    if copy == "bf16":
        return forward(c, params, tokens)
    if copy == "e4m3_weights":
        return forward(c, e4m3(params), tokens)
    return ref.logits(c, params, tokens, (copy.removeprefix("without_"),))


COPIES = ["bf16", "e4m3_weights"] + [f"without_{w}" for w in ref.WITHOUT]


def test_float32_forward_is_the_reference_and_so_is_its_loss():
    c = TINY.with_(dtype="float32")
    params = init_params(c, jax.random.PRNGKey(1))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (6, 48), 0, c.vocab_size)
    want = ref.logits(c, params, tokens)
    with jax.default_matmul_precision("highest"):
        assert float(jnp.max(jnp.abs(forward(c, params, tokens) - want))) < 1e-4
        batch = {"inputs": tokens[:, :-1], "targets": tokens[:, 1:]}
        logp = jax.nn.log_softmax(forward(c, params, batch["inputs"]), axis=-1)
    loss, aux, _ = ref.loss(c, params, batch)
    got = -jnp.mean(jnp.take_along_axis(logp, batch["targets"][..., None], axis=-1))
    assert float(loss) == pytest.approx(float(got), abs=1e-5) and float(aux) == 0.0


def test_the_sizes_are_read_from_the_fields_a_cell_resolves():
    published = {"hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                            "rope_type": "default"}, "rope_type": "default"}
    sizes = ref._sizes({**vars(TINY), "rope_parameters": published})
    assert sizes["rope_theta"] == 5000000 and sizes["partial_rotary_factor"] == 0.5
    assert sizes["head_dim"] == 16 and (sizes["cca_time0"], sizes["cca_time1"]) == (2, 2)
    assert ref._sizes(TINY)["rope_theta"] == ref._sizes(vars(TINY))["rope_theta"] == 10000.0


@pytest.mark.parametrize("seed", [1, 2])
def test_bf16_forward_is_inside_the_logit_tolerance(seed):
    params = init_params(WIDE, jax.random.PRNGKey(seed))
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 100), (8, 128), 0, WIDE.vocab_size)
    _, stats = ref.hidden(WIDE, params, tokens)
    good = ref.check_logits(forward(WIDE, params, tokens), ref.logits(WIDE, params, tokens),
                            stats["margin"])
    assert good["ok"] and good["median_error_sd"] < 0.7 * ref.LOGIT_MEDIAN_TOL, good
    assert good["rms_error_sd"] < 0.8 * ref.LOGIT_RMS_TOL, good
    assert good["positions"] > 0.8 * tokens.size, good


@pytest.mark.parametrize("fault", [c for c in COPIES if c != "bf16"])
def test_a_fault_fails_the_logit_tolerance(fault):
    params = init_params(WIDE, jax.random.PRNGKey(1))
    tokens = jax.random.randint(jax.random.PRNGKey(101), (8, 128), 0, WIDE.vocab_size)
    _, stats = ref.hidden(WIDE, params, tokens)
    bad = ref.check_logits(copy_logits(fault, WIDE, params, tokens),
                           ref.logits(WIDE, params, tokens), stats["margin"])
    assert not bad["ok"], bad
    assert bad["median_error_sd"] > 1.4 * ref.LOGIT_MEDIAN_TOL, bad
    assert bad["rms_error_sd"] > 1.2 * ref.LOGIT_RMS_TOL, bad


def greedy(step, prompts, steps):
    seq, out = prompts, []
    for _ in range(steps):
        tok = jnp.argmax(step(seq)[:, -1], axis=-1)
        out.append(tok)
        seq = jnp.concatenate([seq, tok[:, None]], axis=1)
    return np.asarray(jnp.stack(out, axis=1))


@pytest.fixture(scope="module")
def probe():
    """32 prompts of 120 tokens x 8 new tokens; the reference's half of the
    probe run first, as the harness runs it."""
    params = init_params(WIDE, jax.random.PRNGKey(1))
    prompts = jax.random.randint(jax.random.PRNGKey(301), (32, 120), 0, 256)
    return params, prompts, jax.device_get(ref.greedy_path(WIDE, params, prompts, 8))


def copy_tokens(copy, params, prompts):
    step = jax.jit(lambda p, s: forward(WIDE, p, s))
    if copy == "bf16":
        return greedy(lambda s: step(params, s), prompts, 8)
    if copy == "e4m3_weights":
        rounded = e4m3(params)
        return greedy(lambda s: step(rounded, s), prompts, 8)
    without = (copy.removeprefix("without_"),)
    return greedy(lambda s: ref.logits(WIDE, params, s, without), prompts, 8)


@pytest.mark.parametrize("copy", [c for c in COPIES if c != "without_skip"])
def test_the_share_rule_tells_the_copies_from_the_program(copy, probe):
    """The share rule's readings on the CPU at the widening above and on the
    chip at the cell's widths are in the reference's note. The bf16 program
    passes, every copy but one fails: at this widening's 4 layers the skip's
    absence turns too few greedy tokens for the rule (the logit tolerance
    catches it: `test_a_fault_fails_the_logit_tolerance`); at the cell's
    widths on the chip the rule fails it too."""
    params, prompts, first = probe
    ref.greedy_path(WIDE, params, prompts, 8)
    result = ref.check_tokens(copy_tokens(copy, params, prompts), *first)
    assert result["checked"] == 32 * 8, result
    assert result["ok"] == (copy == "bf16"), result
    if copy == "bf16":
        # a quarter of the positions the rule lets fall outside, at most
        assert 1 - result["pass_share"] <= (1 - ref.PASS_SHARE) / 4, result
        assert result["first_tokens_greedy"] >= 30, result


def test_one_teacher_forced_pass_scores_every_served_position():
    """Served tokens that leave the reference's greedy path at once (random
    ones) are scored at every position, each against the reference fed the
    same tokens before it: the gaps and greedy tokens are those of the full
    logits, which the pass never holds whole."""
    params = init_params(WIDE, jax.random.PRNGKey(3))
    prompts = jax.random.randint(jax.random.PRNGKey(303), (3, 40), 0, 256)
    served = jax.random.randint(jax.random.PRNGKey(304), (3, 6), 0, WIDE.vocab_size)
    scored = jax.device_get(ref.teacher_forced(WIDE, params, prompts, served))
    full = np.asarray(ref.logits(WIDE, params, jnp.concatenate([prompts, served[:, :-1]], 1)))
    full = full[:, prompts.shape[1] - 1:]
    picked = np.take_along_axis(full, np.asarray(served)[..., None], axis=-1)[..., 0]
    np.testing.assert_allclose(scored["gaps"], (full.max(-1) - picked) / full.std(-1),
                               atol=1e-4)
    assert (scored["picks"] == full.argmax(-1)).all() and scored["margin"].shape == (3, 6)


def test_too_few_positions_fail_whatever_they_read():
    result = ref.share_rule(np.zeros((1, 2), np.float32), np.ones((1, 2), np.float32))
    assert result["checked"] == 2 and result["pass_share"] == 1.0 and not result["ok"]
