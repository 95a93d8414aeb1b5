"""The `jamba` block — state-space (Mamba-1) layers and attention layers in one
layer loop, a per-slot recurrent-state pool beside the KV block pool, four
query heads on ONE KV head, no rotary embedding, a tied head — at the
`tiny-mamba` preset (pattern mmam twice), on seeded weights, against the plain
reference (benchmarks/reference/jamba.py). LOGITS are compared, never sampled
tokens: the paged programs' are read where they hand them to the sampler
(`logit_tap`)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import jamba as ref
from dstack_tpu.workloads import kv_blocks
from dstack_tpu.workloads.config import FULL, MAMBA, PRESETS, ModelConfig
from dstack_tpu.workloads.generate import generate
from dstack_tpu.workloads.quant import quantize_params
from dstack_tpu.workloads import selective_scan as scans
from dstack_tpu.workloads.serving import ServingEngine
from dstack_tpu.workloads.train import loss_fn
from dstack_tpu.workloads.transformer import (
    forward,
    head_weights,
    init_params,
    mamba_mixer,
)

CFG = PRESETS["tiny-mamba"]
# The published sizes (AI21-Jamba2-3B config.json), never allocated here.
JAMBA = ModelConfig(
    vocab_size=65536, d_model=2560, n_layers=28, n_heads=20, n_kv_heads=1,
    d_ff=8192, norm_eps=1e-6, max_seq_len=262144, attn_layer_period=14,
    attn_layer_offset=7, mamba_d_state=16, mamba_d_conv=4, mamba_expand=2,
    mamba_dt_rank=160, use_rope=False, tie_embeddings=True,
)
CHUNK, BLOCK, MAX_LEN, SLOTS = 16, 8, 512, 3


@functools.lru_cache(maxsize=None)
def model(dtype="float32", seed=0):
    c = CFG.with_(dtype=dtype)
    return c, init_params(c, jax.random.PRNGKey(seed))


@pytest.fixture
def logit_tap(monkeypatch):
    """Every logits array a paged program hands its sampler, in order."""
    taps, plain = [], kv_blocks.logits_linear

    def tapped(x, w):
        y = plain(x, w)
        jax.debug.callback(lambda a: taps.append(np.asarray(a)), y, ordered=True)
        return y

    monkeypatch.setattr(kv_blocks, "logits_linear", tapped)
    return taps


# -- the model ---------------------------------------------------------------------


def test_config_derives_the_layer_order_and_the_state_geometry():
    assert CFG.layer_types == (MAMBA, MAMBA, FULL, MAMBA) * 2
    assert CFG.layer_period == (MAMBA, MAMBA, FULL, MAMBA)
    assert (CFG.n_state_layers, CFG.n_attn_layers, CFG.has_state_layers) == (6, 2, True)
    assert CFG.state_shapes() == ((16, 128), (3, 128))
    assert CFG.state_row_bytes() == 6 * (16 * 128 * 4 + 3 * 128 * 2)
    assert not PRESETS["tiny"].has_state_layers and PRESETS["tiny"].n_attn_layers == 2
    assert PRESETS["tiny"].state_row_bytes() == 0
    assert CFG.with_(n_layers=2).layer_types == (MAMBA, MAMBA)  # no attention layer
    with pytest.raises(ValueError, match="attn_layer_offset"):
        CFG.with_(attn_layer_offset=4, layer_types=())
    with pytest.raises(ValueError, match="give one of them"):
        CFG.with_(layer_types=(FULL,) * 8)
    with pytest.raises(ValueError, match="mamba_dt_rank"):
        CFG.with_(mamba_dt_rank=0)
    with pytest.raises(ValueError, match="expert bank"):
        CFG.with_(n_experts=4)


def test_counts_at_the_published_sizes():
    """ISSUE 33's table: 28 layers with attention at 7 and 21, 20 query heads
    on one KV head of 128, 3,029,337,472 parameters, 9,318,400 B of state a
    slot at any context and 1,024 B of rows a token."""
    kinds = JAMBA.layer_types
    assert [i for i, kind in enumerate(kinds) if kind == FULL] == [7, 21]
    assert kinds.count(MAMBA) == 26 and len(JAMBA.layer_period) == 14
    assert (JAMBA.n_heads, JAMBA.n_kv_heads, JAMBA.head_dim) == (20, 1, 128)
    assert JAMBA.mamba_params() == 41_241_792 and JAMBA.attn_params() == 13_762_560
    assert JAMBA.param_count() == 3_029_337_472
    assert JAMBA.state_row_bytes() == 9_318_400
    assert JAMBA.n_attn_layers * JAMBA.kv_row_bytes() == 1024
    # forward matrix products a token: 2 x the weights that multiply, the head once
    matmuls = (26 * JAMBA.mamba_matmul_params() + 2 * JAMBA.attn_params()
               + 28 * JAMBA.mlp_params() + JAMBA.d_model * JAMBA.vocab_size)
    assert abs(JAMBA.flops_per_token() / 3 / (2 * matmuls) - 1) < 0.01


def test_embedding_and_head_are_one_leaf_and_the_leaves_are_the_count():
    c, params = model()
    assert "lm_head" not in params
    assert head_weights(params).shape == (c.d_model, c.vocab_size)
    np.testing.assert_array_equal(head_weights(params), params["embed"].T)
    assert sum(a.size for a in jax.tree_util.tree_leaves(params)) == c.param_count()
    mixers = params["mixers"]
    assert mixers[MAMBA]["in_proj"].shape[0] == 6 and mixers[FULL]["wq"].shape[0] == 2
    assert params["layers"]["w_gate"].shape[0] == 8 and "wq" not in params["layers"]
    # the recurrence's regime: A_log = log(1..16) a channel, D = 1, and a step
    # softplus(dt_bias) in [1e-3, 1e-1]
    np.testing.assert_allclose(jnp.exp(mixers[MAMBA]["A_log"][0, :, 0]), np.arange(1, 17), rtol=1e-6)
    step = jax.nn.softplus(mixers[MAMBA]["dt_bias"])
    assert float(step.min()) >= 1e-3 * 0.999 and float(step.max()) <= 1e-1 * 1.001
    assert mixers[MAMBA]["A_log"].dtype == jnp.float32


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_forward_matches_the_reference(dtype):
    c, params = model(dtype)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 96), 0, c.vocab_size)
    got = forward(c, params, tokens)
    want = ref.logits(c, params, tokens)
    if dtype == "float32":
        assert float(jnp.max(jnp.abs(got - want))) < 1e-4
        rotated = forward(c.with_(use_rope=True), params, tokens)
        assert float(jnp.max(jnp.abs(rotated - want))) > 0.1
    result = ref.check_logits(got, want)
    assert result["ok"] and result["positions"] == 4 * 96, result


def test_generate_decodes_what_forward_computes_and_the_trainer_loss_runs():
    c, params = model()
    prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 13), 0, c.vocab_size)
    out = generate(c, params, prompt, max_new_tokens=6)
    logits = forward(c, params, jnp.concatenate([prompt, out], axis=1))
    np.testing.assert_array_equal(out, jnp.argmax(logits[:, 12:-1], axis=-1))
    # `forward` serves the trainer's loss on this model (the tied head through
    # `head_weights`); no cell trains it.
    batch = {"inputs": prompt[:, :-1], "targets": prompt[:, 1:]}
    loss, grads = jax.value_and_grad(lambda p: loss_fn(c, p, batch)[0])(params)
    assert np.isfinite(float(loss))
    assert float(jnp.abs(grads["mixers"][MAMBA]["A_log"]).max()) > 0


# -- the paged programs, by hand ------------------------------------------------------


class Programs:
    """One chunk bucket and a two-step decode program on a fresh state, with
    slot s's table the s-th run of blocks."""

    def __init__(self, c, params, state_dtype=jnp.float32, steps=2):
        self.c, self.params = c, params
        self.state = kv_blocks.init_paged_state(
            c, SLOTS, MAX_LEN, BLOCK, SLOTS * MAX_LEN // BLOCK, state_dtype=state_dtype)
        self.chunk = kv_blocks.make_chunk_prefill(c, CHUNK)
        self.step = kv_blocks.make_paged_decode_step(c, steps=steps)
        self.key = jax.random.PRNGKey(0)

    def prefill(self, slot, tokens, start, final, budget):
        per = MAX_LEN // BLOCK
        self.state, first = self.chunk(
            self.params, self.state, jnp.int32(slot),
            jnp.arange(slot * per, (slot + 1) * per, dtype=jnp.int32),
            jnp.asarray([tokens + [0] * (CHUNK - len(tokens))], jnp.int32),
            jnp.int32(len(tokens)), jnp.int32(start), jnp.int32(budget),
            jnp.float32(0.0), jnp.float32(1.0), self.key, jnp.asarray(final),
        )
        return int(first)

    def decode(self):
        self.state, tokens, _ = self.step(self.params, self.state, self.key)
        return np.asarray(tokens)


def interleaved(programs, taps, prompt, decode_launches):
    """Request A (slot 0) prefills `prompt` in chunks of 16, the last one
    padded, WHILE request B (slot 1) is admitted, decodes between A's chunks
    and retires (6 tokens), and request C takes slot 2 before A's last chunk
    -> (A's greedy tokens, A's logits at the positions that produced them)."""
    rng = np.random.default_rng(0)
    other = lambda n: rng.integers(0, programs.c.vocab_size, n).tolist()
    programs.prefill(1, other(11), 0, True, 6)
    programs.decode()
    chunks = [prompt[i:i + CHUNK] for i in range(0, len(prompt), CHUNK)]
    for i, chunk in enumerate(chunks[:-1]):
        programs.prefill(0, chunk, i * CHUNK, False, 99)
        programs.decode()                     # B decodes; A's row is not active
    programs.prefill(2, other(5), 0, True, 4)  # C: admitted between A's chunks
    first = programs.prefill(0, chunks[-1], len(prompt) - len(chunks[-1]), True,
                             2 * decode_launches + 1)
    jax.effects_barrier()
    tokens, logits = [first], [taps[-1][0]]
    for _ in range(decode_launches):
        tokens += [int(t) for t in programs.decode()[0]]
        jax.effects_barrier()
        logits += [tap[0] for tap in taps[-2:]]
    return tokens, np.stack(logits)


@pytest.mark.parametrize("dtype,tail", [("float32", 2), ("float32", 11), ("bfloat16", 2)])
def test_interleaved_chunks_then_decode_against_the_reference(dtype, tail, logit_tap):
    """A prompt of 32 + `tail` tokens: three chunks, the last padded (with
    `tail` 2 its n_valid is under the convolution's three taps, so the tail
    written back reaches into the previous chunk's), then 32 decode steps,
    other slots live, decoding and retiring meanwhile."""
    c, params = model(dtype)
    prompt = np.random.default_rng(1).integers(0, c.vocab_size, 32 + tail).tolist()
    tokens, got = interleaved(Programs(c, params), logit_tap, prompt, 16)
    full = jnp.asarray([prompt + tokens[:-1]], jnp.int32)
    want = np.asarray(ref.logits(c, params, full))[0, len(prompt) - 1:]
    assert got.shape == want.shape == (33, c.vocab_size)
    if dtype == "float32":
        assert float(np.max(np.abs(got - want))) < 2e-4
    assert ref.check_logits(got, want)["ok"]


@pytest.mark.parametrize("taken_out", ["n_valid_stop", "active_mask"])
def test_the_interleaved_test_fails_without_the_stop_or_the_mask(
        taken_out, logit_tap, monkeypatch):
    """What the test above is worth: with the recurrence running over a
    chunk's padded tail, or over rows that are not active in a decode step,
    the same comparison is off by orders of magnitude."""
    def every_token_moves_the_state(c, x, p, h0, tail0, n_valid, **kw):
        chunk = x.shape[1] > 1
        if chunk == (taken_out == "n_valid_stop"):
            n_valid = jnp.full_like(n_valid, x.shape[1])
        return mamba_mixer(c, x, p, h0, tail0, n_valid, **kw)

    monkeypatch.setattr(kv_blocks, "mamba_mixer", every_token_moves_the_state)
    c, params = model("float32")
    prompt = np.random.default_rng(1).integers(0, c.vocab_size, 34).tolist()
    tokens, got = interleaved(Programs(c, params), logit_tap, prompt, 4)
    full = jnp.asarray([prompt + tokens[:-1]], jnp.int32)
    want = np.asarray(ref.logits(c, params, full))[0, len(prompt) - 1:]
    assert float(np.max(np.abs(got - want))) > 1e-2
    assert not ref.check_logits(got, want)["ok"]


def test_decode_steps_leave_the_state_of_rows_that_are_not_live_bit_for_bit():
    c, params = model("bfloat16")
    programs = Programs(c, params, steps=3)
    programs.prefill(1, list(range(1, 12)), 0, True, 9)
    rng = jax.random.PRNGKey(3)
    st = programs.state
    noise = lambda a, k: jax.random.normal(jax.random.fold_in(rng, k), a.shape, a.dtype)
    keep = (jnp.arange(SLOTS) == 1)
    ssm = jnp.where(keep[None, :, None, None], st.ssm, noise(st.ssm, 0))
    conv = jnp.where(keep[None, :, None], st.conv, noise(st.conv, 1))
    programs.state = st._replace(ssm=ssm, conv=conv)
    before = jax.device_get((ssm, conv))
    programs.decode()
    after = jax.device_get((programs.state.ssm, programs.state.conv))
    for was, now in zip(before, after):
        np.testing.assert_array_equal(was[:, 0], now[:, 0])
        np.testing.assert_array_equal(was[:, 2], now[:, 2])
        assert not np.array_equal(was[:, 1], now[:, 1])           # the live row moved


def test_h_kept_in_bfloat16_is_another_model(logit_tap):
    """Why `h` is float32: the float32 programs follow the reference to 1e-4
    sd over 256 decode steps, and with nothing changed but the scan's state
    stored in bfloat16 between tokens they are a hundred times further off.
    (Under bfloat16 ACTIVATIONS the logit tolerances cannot see it at this
    size: 0.020-0.024 sd with float32 `h`, 0.021-0.027 with bfloat16 `h` over
    1,024 steps, reference/jamba.py. ISSUE 33 asked for that form; this is
    the form that tells the two apart.)"""
    c, params = model("float32")
    prompt = np.random.default_rng(7).integers(0, c.vocab_size, CHUNK).tolist()
    readings = {}
    for name, state_dtype in (("float32", jnp.float32), ("bfloat16", jnp.bfloat16)):
        programs = Programs(c, params, state_dtype=state_dtype, steps=8)
        del logit_tap[:]
        tokens = [programs.prefill(0, prompt, 0, True, 257)]
        for _ in range(32):
            tokens += [int(t) for t in programs.decode()[0]]
        jax.effects_barrier()
        got = np.stack([tap[0] for tap in logit_tap])
        full = jnp.asarray([prompt + tokens[:-1]], jnp.int32)
        want = np.asarray(ref.logits(c, params, full))[0, CHUNK - 1:]
        readings[name] = ref.check_logits(got[-64:], want[-64:])["rms_error_sd"]
    assert readings["float32"] < 1e-4, readings
    assert readings["bfloat16"] > 100 * readings["float32"], readings
    assert readings["bfloat16"] > 3e-3, readings


# -- the kernels, interpreted ---------------------------------------------------------


def scan_inputs(seed, *shapes):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal(shape), jnp.float32) for shape in shapes]


@pytest.mark.parametrize("live", [(1, 0, 1, 1, 0, 0), (0, 0, 1, 0, 1, 0), (0,) * 6, (1,) * 6])
def test_decode_kernel_updates_live_rows_in_place_and_no_other(live):
    """Against the plain recurrence: the live rows of ONE layer of the pool
    move, every other row and layer is bit for bit what it was (leading dead
    rows, dead rows between live ones, no live row at all)."""
    n, di, rows, layers = 16, 256, 6, 3
    pool, a, delta, u, b_in, c_out = scan_inputs(
        0, (layers, rows, n, di), (n, di), (rows, di), (rows, di), (rows, n), (rows, n))
    a, live = -jnp.exp(0.3 * a), jnp.asarray(live, bool)
    delta = jnp.where(live[:, None], jax.nn.softplus(delta), 0.0)
    y, new = scans.selective_scan_decode(pool, 1, live, delta, u, b_in, c_out, a, interpret=True)
    want_y, want_h = scans.selective_scan(
        delta[:, None], u[:, None], b_in[:, None], c_out[:, None], a, pool[1])
    keep = np.asarray(live)
    np.testing.assert_allclose(new[1][keep], want_h[keep], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(y[keep], want_y[keep, 0], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(new[1][~keep], pool[1][~keep])
    np.testing.assert_array_equal(y[~keep], 0)
    np.testing.assert_array_equal(new[0], pool[0])
    np.testing.assert_array_equal(new[2], pool[2])


@pytest.mark.parametrize("tokens", [8, 16, 64])
def test_chunk_kernel_is_the_plain_recurrence(tokens):
    """Groups of 16 tokens (8 in the shortest bucket), two blocks of
    channels, a padded tail (delta 0) that moves nothing."""
    n, di = 16, 1024
    a, h0, delta, u, b_in, c_out = scan_inputs(
        tokens, (n, di), (n, di), (tokens, di), (tokens, di), (tokens, n), (tokens, n))
    a = -jnp.exp(0.3 * a)
    delta = jax.nn.softplus(delta).at[tokens - 3:].set(0.0)
    y, h = scans.selective_scan_chunk(delta, u, b_in, c_out, a, h0, interpret=True)
    want_y, want_h = scans.selective_scan(
        delta[None], u[None], b_in[None], c_out[None], a, h0[None])
    np.testing.assert_allclose(y, want_y[0], rtol=1e-5, atol=2e-5)
    np.testing.assert_allclose(h, want_h[0], rtol=1e-5, atol=2e-5)
    _, before_the_tail = scans.selective_scan(
        delta[None, :-3], u[None, :-3], b_in[None, :-3], c_out[None, :-3], a, h0[None])
    np.testing.assert_allclose(h, before_the_tail[0], rtol=1e-5, atol=2e-5)


def test_the_paged_programs_on_the_kernels_follow_the_reference(logit_tap, monkeypatch):
    """The interleaved schedule again with both kernels in the programs
    (interpreted; a model wide enough for them to tile)."""
    monkeypatch.setattr(kv_blocks, "scan_impl", lambda n, di: "pallas")
    interpreted = lambda fn: functools.partial(fn, interpret=True)
    monkeypatch.setattr(kv_blocks, "selective_scan_decode",
                        interpreted(scans.selective_scan_decode))
    from dstack_tpu.workloads import transformer

    monkeypatch.setattr(transformer, "selective_scan_chunk",
                        interpreted(scans.selective_scan_chunk))
    c = CFG.with_(dtype="float32")
    assert scans.scan_impl(c.mamba_d_state, c.d_inner, interpret=True) == "pallas"
    assert scans.scan_impl(c.mamba_d_state, c.d_inner) == "lax"        # no TPU here
    params = init_params(c, jax.random.PRNGKey(3))
    prompt = np.random.default_rng(1).integers(0, c.vocab_size, 34).tolist()
    tokens, got = interleaved(Programs(c, params), logit_tap, prompt, 4)
    full = jnp.asarray([prompt + tokens[:-1]], jnp.int32)
    want = np.asarray(ref.logits(c, params, full))[0, len(prompt) - 1:]
    assert float(np.max(np.abs(got - want))) < 2e-4


# -- the engine ----------------------------------------------------------------------


def serve(engine, prompt, max_new_tokens):
    out, tokens = engine.submit(prompt, max_new_tokens=max_new_tokens, temperature=0.0), []
    while (tok := out.get(timeout=300)) is not None:
        assert not isinstance(tok, BaseException), tok
        tokens.append(int(tok))
    return tokens


def make_engine(c, params, slots):
    return ServingEngine(c, params, slots=slots, max_len=128, kv_block_size=BLOCK,
                         prefill_chunk_tokens=CHUNK)


def test_engine_holds_the_references_path_and_counts_state_rows():
    """Six requests on three slots (slots are taken over as they retire),
    prompts of two full chunks and a padded one, against the reference's
    greedy path; the counters of the state pool."""
    c, params = model("bfloat16")
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, c.vocab_size, 40).tolist() for _ in range(6)]
    engine = make_engine(c, params, 3)
    try:
        outs = [engine.submit(p, max_new_tokens=12, temperature=0.0) for p in prompts]
        got = []
        for out in outs:
            got.append([])
            while (tok := out.get(timeout=300)) is not None:
                assert not isinstance(tok, BaseException), tok
                got[-1].append(int(tok))
        stats = engine.stats()
    finally:
        engine.close()
    ref_out = jax.device_get(ref.greedy_path(c, params, jnp.asarray(prompts, jnp.int32), 12))
    result = ref.check_tokens(got, *ref_out)
    assert result["checked"] >= 40 and result["outside_at_sd"]["0.15"] == 0, result
    assert stats["layer_pattern"] == "mmam" and stats["kv_pool_layers"] == 2
    assert stats["state_row_bytes"] == c.state_row_bytes() == 53760
    assert stats["state_pool_bytes"] == 3 * 53760
    assert stats["decode_state_rows_total"] == 6 * stats["decode_slot_steps_total"] > 0
    assert stats["decode_state_rows_computed_total"] == 6 * 3 * stats["decode_steps_total"]
    assert stats["kv_layer_blocks"] == 0                         # idle


def test_a_slot_taken_over_gives_a_fresh_engines_logits(logit_tap):
    """One slot: Q is submitted while P decodes, so Q takes the slot P
    leaves (in the shadow of P's last chunk, or after it) and must start from
    zero state whoever held it: its logits are those of an engine that only
    ever saw Q, bit for bit."""
    c, params = model("float32")
    rng = np.random.default_rng(9)
    p, q = (rng.integers(0, c.vocab_size, n).tolist() for n in (21, 37))
    engine = make_engine(c, params, 1)
    try:
        first = engine.submit(p, max_new_tokens=9, temperature=0.0)
        q_tokens = serve(engine, q, 7)
        while first.get(timeout=300) is not None:
            pass
    finally:
        engine.close()
    jax.effects_barrier()
    after_p = [np.array(t) for t in logit_tap]
    del logit_tap[:]
    fresh = make_engine(c, params, 1)
    try:
        assert serve(fresh, q, 7) == q_tokens
    finally:
        fresh.close()
    jax.effects_barrier()
    alone = [np.array(t) for t in logit_tap]
    assert len(after_p) > len(alone) >= 3 + 2
    for got, want in zip(after_p[-len(alone):], alone):
        np.testing.assert_array_equal(got, want)


def test_prefix_reuse_is_off_and_says_why(logit_tap):
    """The same prompt twice: nothing is matched (a cached chain would lack
    the state at its end), the second run's logits are the first's, and
    `stats()` names the reason."""
    c, params = model("float32")
    prompt = np.random.default_rng(11).integers(0, c.vocab_size, 41).tolist()
    engine = make_engine(c, params, 2)
    try:
        assert engine._alloc.cache_enabled is False
        first = serve(engine, prompt, 5)
        jax.effects_barrier()
        n = len(logit_tap)
        assert serve(engine, prompt, 5) == first
        jax.effects_barrier()
        stats = engine.stats()
    finally:
        engine.close()
    assert len(logit_tap) == 2 * n
    for got, want in zip(logit_tap[n:], logit_tap[:n]):
        np.testing.assert_array_equal(got, want)
    assert stats["prefix_cache"].startswith("off: state-space layers")
    assert stats["prefix_tokens_reused_total"] == stats["prefix_cache_hits_total"] == 0
    assert stats["kv_blocks_cached"] == 0
    assert stats["prefill_tokens_computed_total"] == 2 * 41
    plain = ServingEngine(PRESETS["tiny"], init_params(PRESETS["tiny"], jax.random.PRNGKey(0)),
                          slots=1, max_len=64)
    try:
        assert plain.stats()["prefix_cache"] == "on"
        assert plain.stats()["state_pool_bytes"] == 0 and plain.state.ssm is None
    finally:
        plain.close()


REFUSED = {
    "lora": (dict(lora_max_adapters=2), "LoRA"),
    "int8": (dict(), "int8"),
    "mesh": (dict(), "mesh"),
    "spec": (dict(spec_enable=True), "speculative"),
    "int8_drafter": (dict(spec_enable=True, spec_draft_config=CFG), "speculative"),
    "prefill_role": (dict(role="prefill"), "prefill/decode split"),
    "decode_role": (dict(role="decode"), "prefill/decode split"),
    "host_tier": (dict(kv_host_budget_bytes=1 << 20), "host KV tier"),
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_engine_features_that_assume_rows_refuse_state_layers(feature):
    """Each raises at construction naming the feature and the state; none
    runs and gives other numbers."""
    c, params = model("bfloat16")
    kwargs, named = REFUSED[feature]
    if feature == "int8":
        params = quantize_params(params)
    if feature == "mesh":
        from dstack_tpu.workloads.sharding import make_mesh

        kwargs = dict(mesh=make_mesh(jax.devices()[:2], model=2))
    with pytest.raises(ValueError, match=named) as err:
        ServingEngine(c, params, slots=2, max_len=64, kv_block_size=BLOCK, **kwargs)
    if feature == "mesh":       # one KV head divides no model axis: refused sooner
        assert "1 kv" in str(err.value)
    else:
        assert "state-space" in str(err.value) and "recurrent state" in str(err.value)


def test_what_moves_a_block_chain_without_the_state_cannot_be_reached():
    """Slot preemption and `submit_prefilled` move a block chain and would
    drop the state: both need what construction refused (the host tier, the
    decode role). Pipeline stages cut one stack of one kind."""
    from dstack_tpu.workloads.pipeline import stage_params

    c, params = model("bfloat16")
    with pytest.raises(ValueError, match="host tier"):
        ServingEngine(c, params, slots=2, max_len=64, kv_block_size=BLOCK,
                      max_resident_slots=1)
    engine = make_engine(c, params, 1)
    try:
        with pytest.raises(RuntimeError, match="role='decode'"):
            engine.submit_prefilled(None)
        assert engine._host_tier is None and not engine._preempt_slot(0)
    finally:
        engine.close()
    with pytest.raises(ValueError, match="ONE kind"):
        stage_params(c, params, 2)
