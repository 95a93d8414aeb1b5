"""The glm4_moe_lite block — latent attention over one latent KV pool, a
leading dense layer, sigmoid-routed experts beside a shared one — at the
`tiny-latent` preset, on seeded weights, against the plain reference
(benchmarks/reference/glm4_moe_lite.py): `forward`, the absorbed form against
the expanded one, chunked prefill then decode through the paged latent pool
(logits, with a shared prefix and a copy-on-write tail), the router, the
parameter and FLOP counts, and the engine features that refuse the model."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import glm4_moe_lite as ref
from dstack_tpu.workloads import kv_blocks, moe
from dstack_tpu.workloads.config import PRESETS, ModelConfig
from dstack_tpu.workloads.generate import _forward_cached, generate, init_cache
from dstack_tpu.workloads.paged_attention import ragged_attention
from dstack_tpu.workloads.quant import quantize_params
from dstack_tpu.workloads.serving import ServingEngine
from dstack_tpu.workloads.transformer import (
    absorb_query,
    expand_latent,
    forward,
    init_params,
    latent_output,
    logits_linear,
    project_latent,
    rms_norm,
)

CFG = PRESETS["tiny-latent"]
# The published sizes (GLM-4.7-Flash config.json), never allocated here.
GLM = ModelConfig(
    vocab_size=154880, d_model=2048, n_layers=47, n_heads=20, n_kv_heads=20,
    d_ff=1536, n_experts=64, experts_per_token=4, capacity_factor=16.0,
    q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=192,
    qk_rope_head_dim=64, v_head_dim=256, n_dense_layers=1, dense_d_ff=10240,
    n_shared_experts=1, router_score="sigmoid", routed_scaling=1.8,
    max_seq_len=202752, rope_theta=1e6,
)


def model(dtype="float32", seed=0, bias_sd=0.1):
    """(config, params) with a selection bias that matters: `init_params`
    starts it at zero, as the published model does."""
    c = CFG.with_(dtype=dtype)
    params = init_params(c, jax.random.PRNGKey(seed))
    bias = bias_sd * jax.random.normal(
        jax.random.PRNGKey(seed + 100), params["layers"]["router_bias"].shape
    )
    return c, {**params, "layers": {**params["layers"], "router_bias": bias}}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_forward_matches_the_reference(dtype):
    c, params = model(dtype)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0, c.vocab_size)
    got = forward(c, params, tokens)
    _, stats = ref.hidden(c, params, tokens)
    want = ref.logits(c, params, tokens)
    if dtype == "float32":
        assert float(jnp.max(jnp.abs(got - want))) < 1e-4
    result = ref.check_logits(got, want, stats["margin"])
    assert result["ok"] and result["positions"] > 200, result


def test_params_have_two_stacks_and_one_latent_row_a_token():
    c, params = model()
    assert params["dense_layers"]["w_gate"].shape == (1, c.d_model, c.dense_d_ff)
    assert params["layers"]["we_gate"].shape == (2, c.n_experts, c.d_model, c.d_ff)
    assert "router" not in params["dense_layers"]
    assert "wq" not in params["layers"] and "wk" not in params["layers"]
    assert not np.asarray(init_params(c, jax.random.PRNGKey(0))["layers"]["router_bias"]).any()
    # The cache row: 32 + 16 values kept, padded to one 128-value lane, and
    # no value pool. GQA configurations keep the shapes they had.
    assert c.latent_row == 48 and c.kv_row_shapes() == ((1, 128), (1, 0))
    assert c.kv_row_bytes() == 128 * 4
    assert GLM.latent_row == 576 and GLM.kv_row_shapes() == ((1, 640), (1, 0))
    tiny = PRESETS["tiny"]
    assert tiny.kv_row_shapes() == ((2, 32), (2, 32))
    assert tiny.kv_row_bytes() == 2 * 2 * 32 * 4 // 2
    state = kv_blocks.init_paged_state(c, 2, 64, 16, 8)
    assert state.k.shape == (3, 8, 16, 1, 128) and state.v.shape == (3, 8, 16, 1, 0)
    state = kv_blocks.init_paged_state(tiny, 2, 64, 16, 8)
    assert state.k.shape == state.v.shape == (2, 8, 16, 2, 32)


def test_absorbed_attention_is_the_expanded_attention():
    """q.k over the up-projected keys == (q W_uk^T).c_kv + q_rope.k_rope, and
    sum p v == (sum p c_kv) W_uv: the same mathematics, to float32 rounding."""
    c, params = model()
    p = jax.tree_util.tree_map(lambda w: w[1], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, c.d_model), jnp.float32)
    positions = jnp.arange(24, dtype=jnp.int32)
    q, row = project_latent(c, x, p, positions)
    causal = jnp.tril(jnp.ones((24, 24), bool))

    k, v = expand_latent(c, row, p)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * c.head_dim ** -0.5
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    expanded = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(2, 24, -1) @ p["wo"]

    q_abs = absorb_query(c, q, p, 128)
    assert q_abs.shape == (2, 24, c.n_heads, 128)
    assert not np.asarray(q_abs[..., c.latent_row:]).any()
    scores = jnp.einsum("bqhw,bkw->bhqk", q_abs[..., :c.latent_row], row)
    probs = jax.nn.softmax(
        jnp.where(causal, scores * c.head_dim ** -0.5, -jnp.inf), axis=-1
    )
    o_lat = jnp.einsum("bhqk,bkc->bqhc", probs, row[..., :c.kv_lora_rank])
    absorbed = latent_output(c, o_lat.reshape(2, 24, -1), p)
    assert float(jnp.max(jnp.abs(absorbed - expanded))) < 1e-5


def test_generate_decodes_what_forward_computes():
    c, params = model()
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 24), 0, c.vocab_size)
    want = forward(c, params, tokens)
    cache = init_cache(c, 2, 32)
    assert cache.k.shape == (3, 2, 32, 1, 128) and cache.v.shape[-1] == 0
    got, cache = _forward_cached(c, params, tokens[:, :16], cache)
    assert float(jnp.max(jnp.abs(got - want[:, 15]))) < 1e-4
    for i in range(16, 24):
        got, cache = _forward_cached(c, params, tokens[:, i:i + 1], cache)
        assert float(jnp.max(jnp.abs(got - want[:, i]))) < 1e-4
    out = generate(c, params, tokens[:, :16], max_new_tokens=4)
    assert out.shape == (2, 4)
    assert np.array_equal(np.asarray(out[:, 0]), np.asarray(jnp.argmax(want[:, 15], -1)))


# -- the paged latent pool, logits ---------------------------------------------

BLOCK, MAX_LEN, POOL = 16, 128, 24


def run_rows(c, params, state, table, tokens, start):
    """One request's `tokens` at cache positions start.. through the paged
    layer loop (what every paged program runs) -> (logits (n, V), state)."""
    n = len(tokens)
    positions = start + jnp.arange(n, dtype=jnp.int32)
    row = jnp.asarray(table + [POOL] * (MAX_LEN // BLOCK - len(table)), jnp.int32)
    x = jnp.take(params["embed"], jnp.asarray([tokens], jnp.int32), axis=0)
    x, k, v = kv_blocks._layer_loop(
        c, params, x, positions, state.k, state.v,
        jnp.take(row, positions // BLOCK)[None], (positions % BLOCK)[None],
        row[None], (positions + 1)[None], attn_impl="lax_ragged",
    )
    logits = logits_linear(rms_norm(x, params["final_norm"], c.norm_eps), params["lm_head"])
    return logits[0], state._replace(k=k, v=v)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_chunked_prefill_then_decode_through_the_latent_pool(dtype):
    """Request A prefills in chunks and decodes; request B shares A's first
    two blocks from the cache, copies A's partial third block on write, and
    computes only what is its own. Every logit row of both is held to the
    reference's full forward over the whole sequence."""
    c, params = model(dtype)
    rng = np.random.default_rng(4)
    a_tokens = rng.integers(0, c.vocab_size, 60).tolist()
    b_tokens = a_tokens[:40] + rng.integers(0, c.vocab_size, 20).tolist()
    state = kv_blocks.init_paged_state(c, 2, MAX_LEN, BLOCK, POOL)
    copy_block = kv_blocks.make_copy_block()

    got_a, a_table = [], [5, 9, 2, 11]
    for start, stop in ((0, 24), (24, 48), (48, 52)):          # chunks of a prompt
        lg, state = run_rows(c, params, state, a_table, a_tokens[start:stop], start)
        got_a.append(lg)
    for i in range(52, 60):                                      # decode
        lg, state = run_rows(c, params, state, a_table, a_tokens[i:i + 1], i)
        got_a.append(lg)
    got_a = jnp.concatenate(got_a)

    # B: blocks 5 and 9 are A's (32 positions, read in place); A's third
    # block holds positions 32..47, of which B shares 32..39: copy, then write.
    state = copy_block(state, jnp.int32(2), jnp.int32(7))
    b_table = [5, 9, 7, 3]
    got_b = []
    lg, state = run_rows(c, params, state, b_table, b_tokens[40:56], 40)
    got_b.append(lg)
    for i in range(56, 60):
        lg, state = run_rows(c, params, state, b_table, b_tokens[i:i + 1], i)
        got_b.append(lg)
    got_b = jnp.concatenate(got_b)
    assert np.asarray(state.v).size == 0

    both = jnp.asarray([a_tokens, b_tokens], jnp.int32)
    _, stats = ref.hidden(c, params, both)
    want = ref.logits(c, params, both)
    for got, want_rows, margin in (
        (got_a, want[0], stats["margin"][0]),
        (got_b, want[1, 40:], stats["margin"][1, 40:]),
    ):
        if dtype == "float32":
            assert float(jnp.max(jnp.abs(got - want_rows))) < 2e-4
        result = ref.check_logits(got, want_rows, margin)
        assert result["ok"], result
    # A's rows behind B's copy are untouched: A decodes on as before.
    lg, state = run_rows(c, params, state, a_table, a_tokens[59:60], 59)
    assert float(jnp.max(jnp.abs(lg[0] - got_a[-1]))) < (1e-4 if dtype == "float32" else 0.1)


def test_engine_serves_a_second_request_from_the_firsts_cached_blocks():
    c, params = model("bfloat16")
    engine = ServingEngine(
        c, params, slots=4, max_len=256, kv_block_size=16, prefill_chunk_tokens=32
    )
    try:
        rng = np.random.default_rng(5)
        head = rng.integers(0, c.vocab_size, 100).tolist()
        prompts = [head + rng.integers(0, c.vocab_size, 20).tolist() for _ in range(3)]
        got = []
        for prompt in prompts:                        # one after the other
            out, tokens = engine.submit(prompt, max_new_tokens=6, temperature=0.0), []
            while (tok := out.get(timeout=120)) is not None:
                assert not isinstance(tok, BaseException), tok
                tokens.append(int(tok))
            got.append(tokens)
        stats = engine.stats()
    finally:
        engine.close()
    ref_out = jax.device_get(ref.greedy_path(c, params, jnp.asarray(prompts, jnp.int32), 6))
    result = ref.check_tokens(got, *ref_out)
    assert result["ok"] and result["pass_share"] >= 0.9, result
    assert stats["prefix_tokens_reused_total"] == 2 * 96    # six whole blocks, twice
    assert stats["attn_path"] == "lax_ragged_latent"
    assert stats["kv_row_bytes"] == 128 * 2
    # 2 expert layers x 2 experts a token; a decode launch computes a slot for
    # each of 8 experts x 4 slots, a chunk for 8 experts x its padded length.
    assert stats["moe_routed_slots_total"] == 2 * 2 * (
        stats["prefill_tokens_computed_total"] + stats["decode_slot_steps_total"]
    )
    assert 0 < stats["moe_routed_slots_total"] < stats["moe_computed_slots_total"]


def test_latent_kernel_is_the_lax_path():
    """The Pallas variant (interpreted here; compiled for the v5e in
    test_tpu_lowering.py and run on it by chip_smoke.py) against the lax path:
    sentinel table entries, ragged valid lengths, a layer inside the stack."""
    keys = jax.random.split(jax.random.PRNGKey(6), 2)
    q = jax.random.normal(keys[0], (3, 2, 4, 128), jnp.float32)
    pool = jax.random.normal(keys[1], (2, 12, 16, 1, 128), jnp.float32)
    no_v = jnp.zeros((2, 12, 16, 1, 0), jnp.float32)
    tables = jnp.asarray([[3, 5, 12, 12], [0, 1, 2, 7], [9, 12, 12, 12]], jnp.int32)
    valid = jnp.asarray([[19, 20], [63, 64], [4, 5]], jnp.int32)
    kw = {"latent_values": 32, "scale": 40 ** -0.5}
    lax_out = ragged_attention(q, pool, no_v, jnp.int32(1), tables, valid,
                               impl="lax_ragged", **kw)
    pallas_out = ragged_attention(q, pool, no_v, jnp.int32(1), tables, valid,
                                  impl="pallas", interpret=True, **kw)
    assert lax_out.shape == (3, 2, 4 * 32)
    assert float(jnp.max(jnp.abs(lax_out - pallas_out))) < 1e-5
    # by hand, row 2: five positions of block 9 of layer 1
    rows = pool[1, 9, :5, 0]
    p = jax.nn.softmax(jnp.einsum("hw,tw->ht", q[2, 1], rows) * kw["scale"], axis=-1)
    assert float(jnp.max(jnp.abs(
        lax_out[2, 1].reshape(4, 32) - p @ rows[:, :32]))) < 1e-5


# -- the router ----------------------------------------------------------------


def routed(c, h, router, bias):
    vals, idx, slot, _, aux = moe.route_assignments(c, h, router, bias)
    return np.asarray(vals), np.asarray(idx), np.asarray(slot), float(aux)


@pytest.mark.parametrize("case", ["bias", "scaling", "renormalisation", "no_drop",
                                  "shared_expert"])
def test_router(case):
    c, params = model()
    p = jax.tree_util.tree_map(lambda w: w[0], params["layers"])
    h = jax.random.normal(jax.random.PRNGKey(7), (1, 128, c.d_model), jnp.float32)
    scores = np.asarray(jax.nn.sigmoid(h @ p["router"]))
    zero = jnp.zeros((c.n_experts,), jnp.float32)
    vals, idx, slot, aux = routed(c, h, p["router"], zero)
    assert aux == 0.0
    if case == "bias":
        # A bias on expert 3 changes who is chosen and not what a chosen
        # expert weighs: weights are the renormalised scores of the chosen.
        bias = zero.at[3].set(10.0)
        b_vals, b_idx, _, _ = routed(c, h, p["router"], bias)
        assert (b_idx == 3).any(axis=-1).all() and not (idx == 3).any(axis=-1).all()
        chosen = np.take_along_axis(scores, b_idx, axis=-1)
        want = c.routed_scaling * chosen / chosen.sum(-1, keepdims=True)
        assert np.allclose(b_vals, want, atol=1e-6)
        assert np.array_equal(routed(c, h, p["router"], None)[1], idx)
    elif case == "scaling":
        assert np.allclose(vals.sum(-1), c.routed_scaling, atol=1e-5)
        plain = routed(c.with_(routed_scaling=1.0), h, p["router"], zero)[0]
        assert np.allclose(vals, c.routed_scaling * plain, atol=1e-6)
    elif case == "renormalisation":
        # The chosen sigmoid scores do not sum to 1 by themselves; the
        # weights are those scores over their sum.
        raw = np.take_along_axis(scores, idx, axis=-1)
        assert not np.allclose(raw.sum(-1), 1.0, atol=1e-2)
        plain = routed(c.with_(routed_scaling=1.0), h, p["router"], zero)[0]
        assert np.allclose(plain, raw / raw.sum(-1, keepdims=True), atol=1e-6)
        assert np.allclose(plain.sum(-1), 1.0, atol=1e-5)
    elif case == "no_drop":
        # capacity factor = experts / experts per token: a slot for every
        # routed token of a 128-token chunk, and of a one-token decode row.
        assert moe.expert_capacity(c, 128) == 128 and slot.max() < 128
        assert moe.expert_capacity(c, 1) == 1
        # The worst case: every token of the chunk wants the same experts.
        same = jnp.broadcast_to(h[:, :1], h.shape)
        assert routed(c, same, p["router"], zero)[2].max() == 127
        out, _ = moe.moe_mlp(c, same, p)
        assert float(jnp.max(jnp.abs(out - out[:, :1]))) < 1e-5      # none dropped
        tight, _ = moe.moe_mlp(c.with_(capacity_factor=1.25), same, p)
        assert float(jnp.max(jnp.abs(tight[:, -1]))) == 0.0          # dropped
    else:
        x = jax.random.normal(jax.random.PRNGKey(8), (1, 16, c.d_model), jnp.float32)
        with_shared, _ = moe.moe_block(c, x, p)
        p_without = {k: v for k, v in p.items() if not k.startswith("ws_")}
        without, _ = moe.moe_block(c, x, p_without)
        hn = rms_norm(x, p["mlp_norm"], c.norm_eps)
        shared = (jax.nn.silu(hn @ p["ws_gate"]) * (hn @ p["ws_up"])) @ p["ws_down"]
        assert float(jnp.max(jnp.abs(with_shared - without - shared))) < 1e-5
        assert float(jnp.max(jnp.abs(shared))) > 0.1


# -- counts ----------------------------------------------------------------------


def matrices(tree):
    """Weights the count covers: no norms, no selection bias."""
    return sum(
        leaf.size for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
        if not any(k in jax.tree_util.keystr(path) for k in ("norm", "router_bias"))
    )


@pytest.mark.parametrize("name", ["tiny", "tiny-moe", "tiny-latent"])
def test_param_count_is_the_counted_leaves(name):
    c = PRESETS[name]
    shapes = jax.eval_shape(lambda: init_params(c, jax.random.PRNGKey(0)))
    assert c.param_count() == matrices(shapes)
    if name == "tiny-latent":
        dense = matrices(shapes["dense_layers"])
        expert = matrices(shapes["layers"]) // 2
        assert dense == c.attn_params() + c.mlp_params(dense=True)
        assert expert == c.attn_params() + c.mlp_params()


def test_counts_at_the_published_sizes():
    assert GLM.attn_params() == (2048 * 768 + 768 * 5120 + 2048 * 576
                                 + 512 * 8960 + 5120 * 2048) == 21_757_952
    assert round((GLM.attn_params() + GLM.mlp_params()) / 1e6, 1) == 635.3
    assert round((GLM.attn_params() + GLM.mlp_params(dense=True)) / 1e6, 1) == 84.7
    assert round(2 * GLM.d_model * GLM.vocab_size / 1e6, 1) == 634.4
    assert GLM.param_count() == 47 * 21_757_952 + 3 * 2048 * 10240 + 46 * (
        3 * 2048 * 1536 * 65 + 2048 * 64) + 2 * 2048 * 154880
    # resolve_remat reads the same count (12 B a parameter of train state).
    assert GLM.with_(remat="auto").resolve_remat(4096, {"fsdp": 64}) in ("none", "dots")
    # Active FLOPs a token: projections, 4 routed + 1 shared expert, router;
    # the dense layer at its own width; causal scores over 256 + 256 a head.
    attn = 2 * 21_757_952 + 4096 * 20 * (256 + 256)
    expert = 3 * 2 * 2048 * 1536 * 5 + 2 * 2048 * 64
    dense = 3 * 2 * 2048 * 10240
    assert GLM.flops_per_token(4096) == 3.0 * (
        47 * attn + 46 * expert + dense + 2 * 2048 * 154880)
    with pytest.raises(ValueError, match="q_lora_rank"):
        ModelConfig(kv_lora_rank=64)
    with pytest.raises(ValueError, match="n_dense_layers"):
        ModelConfig(n_dense_layers=1)


def test_the_trainers_specs_place_the_latent_tree_on_a_mesh():
    """`forward` is the trainer's path: sharding.PARAM_SPECS names every
    leaf of both stacks, and the sharded forward is the unsharded one."""
    from dstack_tpu.workloads.sharding import make_mesh, param_shardings

    c, params = model()
    mesh = make_mesh(jax.devices()[:4], fsdp=2, model=2)
    placed = jax.device_put(params, param_shardings(mesh, params))
    assert placed["dense_layers"]["wq_b"].sharding.spec == placed["layers"]["wq_b"].sharding.spec
    assert "model" in placed["layers"]["wkv_b"].sharding.spec
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, c.vocab_size)
    with mesh:
        got = jax.jit(lambda p, t: forward(c, p, t))(placed, tokens)
    assert float(jnp.max(jnp.abs(got - forward(c, params, tokens)))) < 1e-4


# -- what may not be silently wrong ----------------------------------------------


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
def test_engine_features_that_assume_gqa_rows_refuse_the_latent_model(feature):
    """Each either works for the latent model and has its own test, or raises
    at construction naming the feature; none runs and gives other numbers."""
    c, params = model("bfloat16")
    kwargs, named = REFUSED[feature]
    if feature == "int8":
        params = quantize_params(params)
    if feature == "mesh":
        from dstack_tpu.workloads.sharding import make_mesh

        kwargs = dict(mesh=make_mesh(jax.devices()[:2], model=2))
    with pytest.raises(ValueError, match=named):
        ServingEngine(c, params, slots=2, max_len=64, kv_block_size=16, **kwargs)
