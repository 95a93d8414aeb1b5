"""The one sampling law (workloads/sampling.py): the decode step's
selection, the prefill's first token and speculation's distributions
agree with each other, and the module sits below the programs."""

import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from dstack_tpu.workloads.sampling import (
    _sampling_probs,
    _select_next_token,
    sample_logits_row,
)

REPO = Path(__file__).resolve().parents[1]
B, V = 3, 64


def _logits():
    return jax.random.normal(jax.random.PRNGKey(7), (B, V), jnp.float32) * 2.0


def _state(temps, top_ps):
    return SimpleNamespace(
        active=jnp.ones((B,), bool),
        temperature=jnp.asarray(temps, jnp.float32),
        top_p=jnp.asarray(top_ps, jnp.float32),
    )


@pytest.mark.parametrize("use", [
    "from dstack_tpu.workloads import sampling",
    # Building the programs is what used to pull the engine in.
    "from dstack_tpu.workloads import kv_blocks as kb\n"
    "from dstack_tpu.workloads.config import PRESETS as P\n"
    "c = P['tiny']\n"
    "kb.make_paged_decode_step(c), kb.make_chunk_prefill(c, 16)\n"
    "kb.make_spec_draft(c, 2), kb.make_spec_verify(c, 2)",
], ids=["sampling", "kv_blocks"])
def test_programs_layer_does_not_import_the_engine(use):
    """serving -> kv_blocks -> {paged_attention, sampling, transformer}:
    using the lower layers in a fresh interpreter leaves the engine
    module unloaded."""
    code = use + (
        "\nimport sys\n"
        "assert 'dstack_tpu.workloads.serving' not in sys.modules\n"
    )
    subprocess.run(
        [sys.executable, "-c", code], check=True, timeout=120, cwd=REPO
    )


def test_temperature_zero_selects_the_argmax():
    logits = _logits()
    state = _state([0.0] * B, [1.0, 0.9, 0.3])  # top_p is ignored at temp 0
    tokens = _select_next_token(state, logits, jax.random.PRNGKey(0))
    assert tokens.tolist() == logits.argmax(-1).tolist()
    probs = _sampling_probs(logits[:, None], state.temperature, state.top_p)
    assert probs[:, 0].argmax(-1).tolist() == tokens.tolist()
    assert bool(jnp.all(probs[:, 0].max(-1) > 0.999))


@pytest.mark.parametrize("sampler", ["select_next_token", "sample_logits_row"])
def test_every_drawable_token_has_mass_under_sampling_probs(sampler):
    """Rejection sampling is exact only if what the decode step (and the
    prefill's first token) can draw is what `_sampling_probs` scores: at
    temperature > 0 with top_p < 1 no draw lands outside its support."""
    logits = _logits()
    temps, top_ps = [0.7, 1.0, 1.5], [0.5, 0.8, 0.95]
    state = _state(temps, top_ps)
    probs = _sampling_probs(logits[:, None], state.temperature, state.top_p)[:, 0]
    # The nucleus cuts something and keeps something in every row.
    support = probs > 0
    assert bool(jnp.all(support.any(-1))) and bool(jnp.all(~support.all(-1)))
    assert jnp.allclose(probs.sum(-1), 1.0, atol=1e-5)

    keys = jax.random.split(jax.random.PRNGKey(1), 256)
    if sampler == "select_next_token":
        draws = jax.vmap(lambda k: _select_next_token(state, logits, k))(keys)
    else:
        draws = jax.vmap(
            lambda k: jax.vmap(sample_logits_row, in_axes=(0, 0, 0, None))(
                logits, state.temperature, state.top_p, k
            )
        )(keys)
    mass = probs[jnp.arange(B)[None, :], draws]            # (256, B)
    assert bool(jnp.all(mass > 0)), "a draw fell outside the support"
    # Not a constant sampler: rows with more than one kept token vary.
    assert len(set(draws[:, 2].tolist())) > 1
