"""How a row of logits becomes a token: the one sampling law of the
workload library.

Every program that selects or scores a token reads it from here — the
chunked prefill's first token (`sample_logits_row`), the decode step's
per-slot selection (`_select_next_token`), and speculative decoding's
target and drafter distributions (`_sampling_probs`) — so the
temperature guard and the nucleus boundary rule cannot drift between
them. Imports jax only: the programs (kv_blocks.py) sit above this
module and the engine (serving.py) above them.
"""

import jax
import jax.numpy as jnp
from jax import lax


def _nucleus_filter(logits: jnp.ndarray, top_p) -> jnp.ndarray:
    """Nucleus (top-p) filter over one row of logits: strict `<` on the
    PRECEDING cumulative mass, so the top token always survives and
    top_p=1 keeps everything. The single source of truth — the jitted
    decode step vmaps this, prefill first-token sampling calls it
    directly, and speculative decoding's rejection sampling builds both
    its target (p) and drafter (q) distributions through it
    (`_sampling_probs`), so the boundary rule cannot drift between any
    of them: distribution-exact speculation requires p and q to share
    the exact filter semantics."""
    order = jnp.argsort(-logits)
    probs = jax.nn.softmax(logits[order])
    before = jnp.cumsum(probs) - probs
    keep = jnp.zeros(logits.shape[0], bool).at[order].set(before < top_p)
    return jnp.where(keep, logits, -jnp.inf)


def sample_logits_row(logits, temp, top_p, rng):
    """First-token sampling over one logits row (V,): greedy argmax when
    temp == 0, else temperature-scaled categorical behind the shared
    `_nucleus_filter`. `temp`/`top_p`/`rng` are traced, so callers pay no
    extra compile entries per sampling config. The chunked paged prefill
    (kv_blocks.make_chunk_prefill) samples a prompt's first token with
    it."""
    with jax.named_scope("sample"):
        def _sample(x):
            scaled = x / jnp.maximum(temp, 1e-6)
            filtered = lax.cond(
                top_p < 1.0,
                lambda s: _nucleus_filter(s, top_p),
                lambda s: s,
                scaled,
            )
            return jax.random.categorical(rng, filtered).astype(jnp.int32)

        return lax.cond(
            temp > 0.0,
            _sample,
            lambda x: jnp.argmax(x).astype(jnp.int32),
            logits,
        )


def _any_active_nucleus(state) -> jnp.ndarray:
    """True when any LIVE slot wants nucleus filtering.

    Gates the per-step sort/cumsum branch in the decode body. Must look
    only at active slots: retire keeps the old top_p in the freed row,
    and a stale < 1 value must not tax default traffic forever (pinned
    by tests/test_serving.py::test_nucleus_gate_ignores_retired_slots).
    Greedy slots (temperature 0) discard their sampled value entirely,
    so their top_p must not arm the branch either — the OpenAI-SDK
    combo {"temperature": 0, "top_p": 0.9} is routine. `state` is
    anything with `active`, `top_p` and `temperature` rows
    (kv_blocks.PagedDecodeState).
    """
    return jnp.any(
        state.active & (state.top_p < 1.0) & (state.temperature > 0.0)
    )


def _any_active_sampling(state) -> jnp.ndarray:
    """True when any LIVE slot samples (temperature > 0).

    Gates the categorical branch: an all-greedy batch (the default
    engine) compiles back to the argmax-only step instead of paying
    gumbel RNG + a second vocab-wide argmax per decode step whose
    result every slot discards."""
    return jnp.any(state.active & (state.temperature > 0.0))


def _select_next_token(state, logits, rng):
    """Per-slot next-token selection: scale by each slot's temperature
    (guarded so greedy slots don't divide by 0 — their sampled value is
    unused), nucleus-filter by each slot's top_p, then select greedy vs
    sampled per slot. top_p == 1 masks nothing (the strict `<` keeps
    every token whose PRECEDING cumulative mass is < p, so the top token
    always survives and p=1 keeps all).

    The traced sampling tail of the decode body
    (kv_blocks.make_paged_decode_step); `state` carries the per-slot
    `temperature`, `top_p` and `active` rows.

    Two nested runtime branches keep the DEFAULT paths free: an
    all-greedy batch (every live temp 0) never scales, filters, or
    draws gumbels — it compiles back to the argmax-only step; a
    sampling batch with every live top_p=1 skips the vocab-wide
    sort/cumsum. lax.cond executes one branch at runtime, so each
    skipped stage costs only its predicate."""
    with jax.named_scope("sample"):
        temps = state.temperature

        def _sample(x):
            scaled = x / jnp.maximum(temps, 1e-6)[:, None]
            filtered = lax.cond(
                _any_active_nucleus(state),
                lambda s: jax.vmap(_nucleus_filter)(s, state.top_p),
                lambda s: s,
                scaled,
            )
            return jax.random.categorical(rng, filtered, axis=-1).astype(jnp.int32)

        sampled = lax.cond(
            _any_active_sampling(state),
            _sample,
            lambda x: jnp.zeros((x.shape[0],), jnp.int32),  # value unused
            logits,
        )
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jnp.where(temps > 0, sampled, greedy)


def _sampling_probs(logits, temps, top_ps):
    """Per-slot sampling distributions under the ENGINE's semantics —
    temperature scale guarded like `_select_next_token`'s, nucleus
    filter via the shared `_nucleus_filter` (gated so all-top_p=1
    traffic never pays the vocab sort). logits (B, S, V), temps /
    top_ps (B,) -> probs (B, S, V). Rejection sampling is exact only
    if drafter q and target p both come from THIS function."""
    with jax.named_scope("sample"):
        scaled = logits / jnp.maximum(temps, 1e-6)[:, None, None]
        filtered = lax.cond(
            jnp.any((temps > 0.0) & (top_ps < 1.0)),
            lambda s: jax.vmap(
                lambda rows, tp: jax.vmap(
                    lambda r: _nucleus_filter(r, tp)
                )(rows)
            )(s, top_ps),
            lambda s: s,
            scaled,
        )
        return jax.nn.softmax(filtered, axis=-1)
