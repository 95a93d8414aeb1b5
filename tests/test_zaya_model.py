"""The `zaya` block — compressed convolutional attention (two causal
convolutions over queries and keys, half of each KV head's value from the
token before, queries and keys on the sphere with a temperature a head, half
a head rotated) carrying a tail a slot and layer, then top-1 of the experts
or a skip, chosen by a router MLP over a projection carried from layer to
layer, each sublayer merged into the stream by learned scales — at the
`tiny-zaya` preset on seeded weights, against the plain reference
(benchmarks/reference/zaya.py): `forward`, the router's carry, chunked
prefill then decode through `ServingEngine` with its convolutions' tail (and
without it), the pair and skip counters, the counts at the published sizes,
the configuration's rules and the engine features that refuse the model."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import zaya as ref
from dstack_tpu.workloads import kv_blocks, moe, transformer
from dstack_tpu.workloads.config import HYBRID, PRESETS, ModelConfig
from dstack_tpu.workloads.quant import quantize_params
from dstack_tpu.workloads.serving import ServingEngine
from dstack_tpu.workloads.transformer import forward, init_params

CFG = PRESETS["tiny-zaya"]
# The published sizes (Zyphra/ZAYA1-8B config.json), never allocated.
ZAYA = ModelConfig(
    vocab_size=262272, d_model=2048, n_layers=40, n_heads=8, n_kv_heads=2,
    head_size=128, d_ff=2048, n_experts=16, experts_per_token=1,
    capacity_factor=16.0, norm_eps=1e-5, max_seq_len=131072,
    tie_embeddings=True, layer_types=(HYBRID,) * 40, cca_time0=2, cca_time1=2,
    router_hidden_size=256,
    rope_parameters={
        HYBRID: {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                 "rope_type": "default"},
        "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000,
                           "rope_type": "default"},
        "rope_type": "default"},
)
# One chip's cut of it (benchmarks/configs/zaya1-8b): 20 of 40 layers.
CUT = ZAYA.with_(n_layers=20, max_seq_len=6144)


def model(dtype="float32", seed=0):
    """The preset's weights with the residual scales moved off their start
    of 1, so that a scale read in the wrong place shows."""
    c = CFG.with_(dtype=dtype)
    params = init_params(c, jax.random.PRNGKey(seed))
    layers = dict(params["layers"])
    for i, name in enumerate(("res_attn", "res_mlp")):
        layers[name] = layers[name] * jax.random.uniform(
            jax.random.PRNGKey(100 + i), layers[name].shape, minval=0.5, maxval=1.5)
    return c, {**params, "layers": layers}


def test_forward_matches_the_reference():
    c, params = model()
    tokens = jax.random.randint(jax.random.PRNGKey(2), (4, 40), 0, c.vocab_size)
    want = ref.logits(c, params, tokens)
    with jax.default_matmul_precision("highest"):
        got = forward(c, params, tokens)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4
    # each mechanism is a term of the result: the reference without it is
    # another model
    for fault in ref.WITHOUT:
        assert float(jnp.max(jnp.abs(ref.logits(c, params, tokens, (fault,)) - want))) > 0.1


def test_the_router_carries_its_projection_from_layer_to_layer():
    """r^_l = r_l + gamma_l r^_(l-1), zero before the first layer: the first
    layer's depth weight multiplies nothing, a later layer's moves its
    routing and so the logits."""
    c, params = model()
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 24), 0, c.vocab_size)
    base = forward(c, params, tokens)

    def gamma_zero_at(layer):
        layers = dict(params["layers"])
        layers["router_gamma"] = layers["router_gamma"].at[layer].set(0.0)
        return {**params, "layers": layers}

    assert float(jnp.max(jnp.abs(forward(c, gamma_zero_at(0), tokens) - base))) == 0.0
    moved = gamma_zero_at(1)
    assert float(jnp.max(jnp.abs(forward(c, moved, tokens) - base))) > 0.1
    _, before = ref.hidden(c, params, tokens)
    _, after = ref.hidden(c, moved, tokens)
    choices, changed = np.asarray(before["choices"]), np.asarray(after["choices"])
    assert (choices[..., 0] == changed[..., 0]).all()       # layer 0 as it was
    assert (choices[..., 1] != changed[..., 1]).any()       # layer 1's routing moved


PROMPT_LEN, NEW = 13, 7


def serve_in_chunks(monkeypatch, zero_tail_at_chunks=False):
    """One request through a float32 `ServingEngine`: a 13-token prompt in
    chunks of 3 (boundaries at 3, 6, 9, 12: every phase mod 2, the last
    chunk one token), then decode. -> (prompt, tokens, the logits of every
    program the engine ran, in order, its stats)."""
    c, params = model()
    taps, plain = [], kv_blocks.logits_linear

    def tapped(x, w):
        y = plain(x, w)
        jax.debug.callback(lambda a: taps.append(np.asarray(a)), y, ordered=True)
        return y

    monkeypatch.setattr(kv_blocks, "logits_linear", tapped)
    if zero_tail_at_chunks:
        project = kv_blocks.cca_project

        def no_tail(c, x, p, positions, tail, n_valid):
            if x.shape[1] > 1:       # a chunk: start it from a zero tail
                tail = jnp.zeros_like(tail)
            return project(c, x, p, positions, tail, n_valid)

        monkeypatch.setattr(kv_blocks, "cca_project", no_tail)
    engine = ServingEngine(c, params, slots=1, max_len=64, kv_block_size=4,
                           prefill_chunk_tokens=3, steps_per_sync=4)
    try:
        prompt = np.random.default_rng(5).integers(0, c.vocab_size, PROMPT_LEN).tolist()
        out, tokens = engine.submit(prompt, max_new_tokens=NEW, temperature=0.0), []
        while (tok := out.get(timeout=300)) is not None:
            assert not isinstance(tok, BaseException), tok
            tokens.append(int(tok))
        jax.effects_barrier()
        stats = engine.stats()
    finally:
        engine.close()
    return c, params, prompt, tokens, taps, stats


def served_against_the_reference(monkeypatch, **fault):
    """-> (largest logit difference over the chunks' last positions and the
    decode steps that emitted, against the reference's full pass over the
    tokens the engine fed; what the engine counted; the reference's routing
    at those tokens)."""
    c, params, prompt, tokens, taps, stats = serve_in_chunks(monkeypatch, **fault)
    assert len(tokens) == NEW
    chunks = 5                                        # 3 + 3 + 3 + 3 + 1
    assert len(taps) == chunks + stats["decode_steps_total"], len(taps)
    fed = jnp.asarray([prompt + tokens[:-1]], jnp.int32)
    want = np.asarray(ref.logits(c, params, fed))[0]
    positions = [2, 5, 8, 11, 12] + list(range(PROMPT_LEN, PROMPT_LEN + NEW - 1))
    got = np.stack([t.reshape(-1, c.vocab_size)[0] for t in taps[:chunks + NEW - 1]])
    _, routing = ref.hidden(c, params, fed)
    return float(np.max(np.abs(got - want[positions]))), stats, routing


def test_chunked_prefill_then_decode_carries_the_tail(monkeypatch):
    worst, stats, routing = served_against_the_reference(monkeypatch)
    assert worst < 2e-4, worst
    assert stats["prefix_cache"].startswith("off: CCA layers")
    # Pairs: the 13 + 6 fed tokens through 3 expert layers, one choice each;
    # those that took the skip, from the reference's own routing.
    choices = np.asarray(routing["choices"])
    skipped = int(np.sum(choices == CFG.n_experts))
    assert skipped > 0
    assert stats["moe_pairs_total"] == (PROMPT_LEN + NEW - 1) * CFG.n_layers
    assert stats["moe_skip_pairs_total"] == skipped
    assert stats["moe_local_pairs_total"] == stats["moe_pairs_total"] - skipped
    # Launches: five chunks, then the decode launches the engine counted;
    # a 4-token chunk is on the capacity path, one row of one token routed
    # (it touches 1 of 9 choices).
    assert moe.plan(CFG, 1, 4)[0] is False and moe.plan(CFG, 1, 1)[0] is True
    launches = stats["decode_steps_total"] // 4
    assert stats["moe_expert_launches_total"] == 5 + launches
    assert stats["moe_routed_launches_total"] == launches


def test_a_tail_zeroed_at_each_chunk_fails_the_comparison(monkeypatch):
    worst, _, _ = served_against_the_reference(monkeypatch, zero_tail_at_chunks=True)
    assert worst > 100 * 2e-4, worst


def test_the_configuration_says_what_it_cannot_run():
    c = CFG
    assert c.cca and c.expert_skip and c.n_choices == 9 and c.layer_period == (HYBRID,)
    assert c.rope(HYBRID).rotary_dim(16) == 8 and c.window(HYBRID) == 0
    assert ZAYA.rope(HYBRID).theta == 5000000                 # the top-level key names no kind
    for bad in (dict(router_hidden_size=0), dict(experts_per_token=2),
                dict(attn_gate="sigmoid"), dict(experts_held=4),
                dict(n_dense_layers=1, dense_d_ff=64), dict(router_score="sigmoid")):
        with pytest.raises(ValueError, match="CCA"):
            c.with_(**bad)
    with pytest.raises(ValueError, match="a 'hybrid' layer is a CCA layer"):
        c.with_(cca_time0=0, cca_time1=0, router_hidden_size=0)
    with pytest.raises(ValueError, match="CCA"):
        PRESETS["tiny-moe"].with_(router_hidden_size=16)


def test_counts_at_the_published_sizes():
    """The weights counted leaf by leaf (norms, temperatures, depth weights
    and residual scales too), and the geometry of a slot."""
    shapes = jax.eval_shape(lambda: init_params(CFG, jax.random.PRNGKey(0)))
    assert CFG.param_count() == sum(a.size for a in jax.tree_util.tree_leaves(shapes))
    assert ZAYA.attn_params(HYBRID) == 5_242_880
    assert ZAYA.cca_params() == 2 * 1280 + 2 * 10 * 128 * 128 + 8 == 330_248
    assert ZAYA.router_params() == 2048 * 256 + 1 + 256 + 2 * 256 * 256 + 256 * 17
    assert ZAYA.mlp_params() == 16 * 3 * 2048 * 2048 + ZAYA.router_params()
    # the router's products alone are 659,712; its norm (256) and its depth
    # weight (1) beside them
    assert ZAYA.router_params() == 659_712 + 256 + 1
    per_layer = 5_242_880 + 330_248 + 201_326_592 + 659_969 + 6 * 2048
    assert ZAYA.param_count() == 40 * per_layer + 262272 * 2048 + 2048
    assert CUT.param_count() == 20 * per_layer + 262272 * 2048 + 2048
    assert round(CUT.param_count() * 2 / 1e9, 2) == 9.38
    # a position's rows: 2 KV heads x 128 x (K + V) x 2 B = 1 KB a layer
    assert CUT.kv_row_bytes() == 1024
    # the tail: u_(t-1) and c1_(t-1) (8 + 2 heads of 128) and the shifted
    # half of each KV head's value at t-1
    assert CUT.cca_tail_width() == 1280 + 1280 + 128 == 2688
    # 40 rows of one token over 17 choices touch 91% of the experts: the
    # capacity path; 8 rows touch 38%: routed
    assert moe.touched_share(40, 1, 17) == pytest.approx(0.911, abs=1e-3)
    assert moe.plan(CUT, 40, 1)[0] is False and moe.plan(CUT, 8, 1)[0] is True
    assert moe.plan(CUT, 1, 512)[0] is True                   # a 512-token chunk


REFUSED = {
    "lora": (dict(lora_max_adapters=2), "LoRA"),
    "int8": (dict(), "int8"),
    "mesh": (dict(), "mesh"),
    "spec": (dict(spec_enable=True), "speculative"),
    "prefill_role": (dict(role="prefill"), "prefill/decode split"),
    "decode_role": (dict(role="decode"), "prefill/decode split"),
    "host_tier": (dict(kv_host_budget_bytes=1 << 20), "host KV tier"),
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_engine_features_that_would_drop_the_tail_refuse_the_model(feature):
    """Each raises at construction naming the feature: speculation would
    have to roll the tail back, the split and the host tier (so preemption,
    which parks a slot there) hand over a block chain without it."""
    c, params = model("bfloat16")
    kwargs, named = REFUSED[feature]
    if feature == "int8":
        params = quantize_params(params)
    if feature == "mesh":
        from dstack_tpu.workloads.sharding import make_mesh

        kwargs = dict(mesh=make_mesh(jax.devices()[:2], model=2))
    with pytest.raises(ValueError, match=named):
        ServingEngine(c, params, slots=2, max_len=64, kv_block_size=4, **kwargs)


def test_the_one_request_cache_and_pipeline_stages_refuse_the_model():
    from dstack_tpu.workloads.generate import init_cache
    from dstack_tpu.workloads.pipeline import stage_params

    c, params = model()
    with pytest.raises(ValueError, match="convolutions' tail"):
        init_cache(c, 1, 16)
    with pytest.raises(ValueError, match="ONE kind"):
        stage_params(c, params, 3)


def test_the_tail_is_the_last_valid_tokens_and_a_dead_row_keeps_its_own():
    """cca_project's tail after a row of n valid tokens is what a row of
    those n alone hands on; a row with none hands back the tail it came
    with."""
    c, params = model()
    p = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(7), (3, 5, c.d_model))
    tail0 = jax.random.normal(jax.random.PRNGKey(8), (3, c.cca_tail_width()))
    positions = jnp.arange(5, dtype=jnp.int32)
    *_, tail = transformer.cca_project(
        c, x, p, positions, tail0, jnp.asarray([5, 2, 0], jnp.int32))
    *_, alone = transformer.cca_project(c, x[1:2, :2], p, positions[:2], tail0[1:2])
    np.testing.assert_allclose(tail[1], alone[0], rtol=1e-6)
    np.testing.assert_array_equal(tail[2], tail0[2])
