"""The `laguna` block — query heads and a per-head output gate that go by
layer kind, half of a head rotated (YaRN) on the full kind, a leading dense
layer BESIDE a layer pattern, sigmoid-routed experts with scaling beside a
shared one, and an expert bank that may be a device's SHARE of the experts
routed over — at the `tiny-laguna` preset (pattern f|wwwf, window 8, 4 / 6
query heads on 2 KV heads, 8 experts top-3), on seeded weights, against the
plain reference (benchmarks/reference/laguna.py): `forward`, `generate`,
chunked prefill then decode through the paged pool and through
`ServingEngine`, the share against the uncut layer, the counts, the
published 48-layer pattern, the configuration's rules and the engine
features that refuse the model."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import laguna as ref
from dstack_tpu.workloads import kv_blocks, moe, paged_attention
from dstack_tpu.workloads.config import (
    FULL,
    PRESETS,
    SLIDING,
    ModelConfig,
    RopeParams,
    period_of,
)
from dstack_tpu.workloads.generate import _forward_cached, generate, init_cache
from dstack_tpu.workloads.quant import quantize_params
from dstack_tpu.workloads.serving import ServingEngine
from dstack_tpu.workloads.transformer import (
    _rope,
    forward,
    init_params,
    logits_linear,
    rms_norm,
)

CFG = PRESETS["tiny-laguna"]
PERIOD = (FULL, SLIDING, SLIDING, SLIDING)
YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 128,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.4852030263919618,
        "partial_rotary_factor": 0.5}
# The published sizes (poolside/Laguna-S-2.1 config.json), never allocated.
LAGUNA = ModelConfig(
    vocab_size=100352, d_model=3072, n_layers=48, n_heads=48, n_kv_heads=8,
    head_size=128, d_ff=1024, n_experts=256, experts_per_token=10,
    capacity_factor=25.6, norm_eps=1e-6, max_seq_len=1048576,
    layer_types=PERIOD * 12, heads_per_layer=(48, 72, 72, 72) * 12,
    sliding_window=512, attn_gate="softplus", n_dense_layers=1,
    dense_d_ff=12288, n_shared_experts=1, router_score="sigmoid",
    routed_scaling=2.5,
    rope_parameters={FULL: YARN, SLIDING: {"rope_type": "default", "rope_theta": 10000,
                                           "partial_rotary_factor": 1}},
)
# One chip's cut of it (benchmarks/configs/laguna-s-2.1): 5 layers, experts
# 0..127 of 256, half the vocabulary.
CUT = LAGUNA.with_(n_layers=5, vocab_size=50176, experts_held=128)
BANK = ("we_gate", "we_up", "we_down")


def model(dtype="float32", seed=0, c=CFG):
    c = c.with_(dtype=dtype)
    return c, init_params(c, jax.random.PRNGKey(seed))


def share_of(c, params, first, held):
    """The configuration and weights of the device that holds experts
    `first .. first + held - 1` of the model `c`, `params`."""
    layers = {**params["layers"],
              **{w: params["layers"][w][:, first:first + held] for w in BANK}}
    return (c.with_(experts_held=held, experts_first=first),
            {**params, "layers": layers})


@pytest.fixture
def interpreted(monkeypatch):
    """The paged programs' attention on the Pallas kernel, interpreted."""
    monkeypatch.setattr(
        kv_blocks, "ragged_attention",
        functools.partial(paged_attention.ragged_attention, interpret=True),
    )
    monkeypatch.setattr(ServingEngine, "_resolve_attn_path", lambda self, c: "pallas")


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """This file's programs are compiled and kept in its process only: they
    are the suite's largest (a dense block and four expert blocks in one
    body), and on this jaxlib a worker died at one of them in five whole
    runs of five, in XLA:CPU's compile or in the persistent cache's
    serialization of what it made (`tests/conftest.py` has the account and
    the flag that ended it). The whole run that passed had both."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


# -- forward -------------------------------------------------------------------


def fewer_heads_on_the_window_layers(c, params):
    """The model with a full layer's 4 query heads on its window layers too
    (their first 4 of 6): another model."""
    cut = {"wq": lambda w: w[:, :, :4 * 32], "wo": lambda w: w[:, :4 * 32],
           "wg": lambda w: w[:, :, :4]}
    mixers = {**params["mixers"], SLIDING: {
        w: cut[w](a) for w, a in params["mixers"][SLIDING].items()}}
    return c.with_(heads_per_layer=(4,) * 5), {**params, "mixers": mixers}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_forward_matches_the_reference(dtype):
    """Contexts eight windows long and twice YaRN's original length. A copy
    without the gate, with the gate's other activation, with the whole head
    rotated on the full kind, or with a full layer's head count on the window
    layers is another model, by the rule's own limits."""
    c, params = model(dtype)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 64), 0, c.vocab_size)
    got = forward(c, params, tokens)
    _, stats = ref.hidden(c, params, tokens)
    want = ref.logits(c, params, tokens)
    result = ref.check_logits(got, want, stats["margin"])
    assert result["ok"] and result["positions"] > 100, result
    if dtype == "bfloat16":
        return
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4
    whole_head = dict(c.rope_parameters)
    whole_head[FULL] = RopeParams(**{**vars(whole_head[FULL]), "partial_rotary_factor": 1.0})
    others = {
        "no gate": (c.with_(attn_gate=""), params),
        "sigmoid gate": (c.with_(attn_gate="sigmoid"), params),
        "whole head rotated": (c.with_(rope_parameters=tuple(whole_head.items())), params),
        "4 heads on window layers": fewer_heads_on_the_window_layers(c, params),
    }
    for name, (other, weights) in others.items():
        wrong = ref.check_logits(forward(other, weights, tokens), want, stats["margin"])
        assert not wrong["ok"], (name, wrong)


def test_params_are_stacks_by_kind_and_the_pool_has_one_layer_axis():
    c, params = model()
    assert c.stack_kinds == ((FULL,), (SLIDING, SLIDING, SLIDING, FULL))
    assert (c.heads(FULL), c.heads(SLIDING), c.heads_by_kind) == (4, 6, True)
    dense, layers = params["dense_layers"], params["layers"]
    assert "wq" not in layers and "wq" not in dense
    assert layers["wk"].shape == layers["wv"].shape == (4, 96, 2 * 32)
    assert dense["w_gate"].shape == (1, 96, 192) and "router" not in dense
    full, window = params["mixers"][FULL], params["mixers"][SLIDING]
    assert full["wq"].shape == (1, 96, 4 * 32) and window["wq"].shape == (3, 96, 6 * 32)
    assert full["wo"].shape == (1, 4 * 32, 96) and window["wo"].shape == (3, 6 * 32, 96)
    assert full["wg"].shape == (1, 96, 4) and window["wg"].shape == (3, 96, 6)
    assert set(params["dense_mixers"]) == {FULL}
    assert params["dense_mixers"][FULL]["wq"].shape == (1, 96, 4 * 32)
    assert layers["router"].shape == (4, 96, 8) and layers["we_gate"].shape == (4, 8, 96, 48)
    # KV heads do not go by kind: one row shape, one pool, one layer axis.
    assert c.kv_row_shapes() == ((2, 32), (2, 32)) and c.n_attn_layers == 5
    state = kv_blocks.init_paged_state(c, 2, 64, 4, 32)
    assert state.k.shape == state.v.shape == (5, 32, 4, 2, 32)
    assert state.moe_pairs is None                      # the whole bank: no leaf
    assert CUT.kv_row_bytes() == 2 * 8 * 128 * 2 == 4096
    # the bank of a share holds the experts held; its router scores them all
    held, shapes = share_of(c, params, 4, 4)
    assert shapes["layers"]["we_gate"].shape == (4, 4, 96, 48)
    made = jax.eval_shape(lambda: init_params(held, jax.random.PRNGKey(0)))
    assert made["layers"]["we_up"].shape == (4, 4, 96, 48)
    assert made["layers"]["router"].shape == (4, 96, 8)
    assert kv_blocks.init_paged_state(held, 2, 64, 4, 32).moe_pairs.shape == (2,)
    # top-3 of 8 at capacity factor 8 / 3, top-10 of 256 at 25.6: a slot for
    # every routed token, on a share as on the whole bank.
    assert moe.expert_capacity(held, 16) == 16 and moe.expert_capacity(CUT, 512) == 512
    assert moe.expert_capacity(CUT, 1) == 1


def test_generate_decodes_what_forward_computes():
    c, params = model()
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 40), 0, c.vocab_size)
    want = forward(c, params, tokens)
    cache = init_cache(c, 2, 48)
    got, cache = _forward_cached(c, params, tokens[:, :30], cache)
    assert float(jnp.max(jnp.abs(got - want[:, 29]))) < 1e-4
    for i in range(30, 40):
        got, cache = _forward_cached(c, params, tokens[:, i:i + 1], cache)
        assert float(jnp.max(jnp.abs(got - want[:, i]))) < 1e-4
    out = generate(c, params, tokens[:, :30], max_new_tokens=4)
    assert np.array_equal(np.asarray(out[:, 0]), np.asarray(jnp.argmax(want[:, 29], -1)))


def test_the_published_depth_ends_inside_a_period_and_runs():
    """48 layers: a dense layer, then 47 expert layers = 11 periods of
    (w, w, w, f) and three window layers more, at tiny widths."""
    c = CFG.with_(n_layers=48, layer_types=PERIOD * 12, heads_per_layer=(4, 6, 6, 6) * 12)
    assert LAGUNA.stack_kinds[0] == (FULL,) and len(LAGUNA.stack_kinds[1]) == 47
    assert period_of(c.stack_kinds[1]) == (SLIDING, SLIDING, SLIDING, FULL)
    assert c.stack_kinds[1][-3:] == (SLIDING,) * 3
    params = init_params(c.with_(dtype="float32"), jax.random.PRNGKey(0))
    assert params["mixers"][SLIDING]["wq"].shape[0] == 36
    assert params["mixers"][FULL]["wq"].shape[0] == 11
    c = c.with_(dtype="float32")
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 24), 0, c.vocab_size)
    want = ref.logits(c, params, tokens)
    assert float(jnp.max(jnp.abs(forward(c, params, tokens) - want))) < 2e-4
    out = generate(c, params, tokens[:, :20], max_new_tokens=2)
    assert int(out[0, 0]) == int(jnp.argmax(want[0, 19]))
    got, _, _ = run_rows(c, params, kv_blocks.init_paged_state(c, 1, MAX_LEN, BLOCK, POOL),
                         list(range(6)), np.asarray(tokens[0]).tolist(), 0, "lax_ragged")
    assert float(jnp.max(jnp.abs(got - want[0]))) < 2e-4


# -- the paged pool, logits ------------------------------------------------------

BLOCK, MAX_LEN, POOL = 4, 96, 40


def run_rows(c, params, state, table, tokens, start, impl):
    """One request's `tokens` at cache positions start.. through the paged
    layer loop (what every paged program runs) -> (logits (n, V), state, the
    loop's pair counts or None)."""
    n = len(tokens)
    positions = start + jnp.arange(n, dtype=jnp.int32)
    row = jnp.asarray(table + [POOL] * (MAX_LEN // BLOCK - len(table)), jnp.int32)
    x = jnp.take(params["embed"], jnp.asarray([tokens], jnp.int32), axis=0)
    x, k, v, *pairs = kv_blocks._layer_loop(
        c, params, x, positions, state.k, state.v,
        jnp.take(row, positions // BLOCK)[None], (positions % BLOCK)[None],
        row[None], (positions + 1)[None], attn_impl=impl,
        tally=(jnp.ones((1, n), bool), jnp.zeros((2,), jnp.int32))
        if c.expert_share else None,
    )
    logits = logits_linear(rms_norm(x, params["final_norm"], c.norm_eps), params["lm_head"])
    return logits[0], state._replace(k=k, v=v), pairs[0] if pairs else None


def chunks_then_decode(c, params, impl):
    """Request A prefills in chunks (the second straddles the window's edge
    of the first, the third starts mid-block) and decodes; request B shares
    A's first ten blocks from the cache and computes only what is its own,
    so its window layers read A's rows. -> both requests' logit rows, and the
    pair counts of every call summed."""
    rng = np.random.default_rng(4)
    a_tokens = rng.integers(0, c.vocab_size, 60).tolist()
    b_tokens = a_tokens[:40] + rng.integers(0, c.vocab_size, 20).tolist()
    state = kv_blocks.init_paged_state(c, 2, MAX_LEN, BLOCK, POOL)
    a_table = [5, 9, 2, 11, 30, 31, 7, 8, 21, 22, 23, 24, 25, 26, 27]
    b_table = a_table[:10] + [0, 1, 3, 4, 6]
    calls = [(a_table, a_tokens, start, stop) for start, stop in ((0, 6), (6, 30), (30, 52))]
    calls += [(a_table, a_tokens, i, i + 1) for i in range(52, 60)]
    calls += [(b_table, b_tokens, 40, 56)]
    calls += [(b_table, b_tokens, i, i + 1) for i in range(56, 60)]
    rows, pairs = {id(a_tokens): [], id(b_tokens): []}, np.zeros(2, np.int64)
    for table, tokens, start, stop in calls:
        lg, state, counted = run_rows(c, params, state, table, tokens[start:stop], start, impl)
        rows[id(tokens)].append(lg)
        if counted is not None:
            pairs += np.asarray(counted)
    return ((a_tokens, b_tokens), jnp.concatenate(rows[id(a_tokens)]),
            jnp.concatenate(rows[id(b_tokens)]), pairs)


@pytest.mark.parametrize("impl,dtype,held", [
    ("lax_ragged", "float32", 8), ("lax_ragged", "bfloat16", 8),
    ("pallas", "float32", 8), ("lax_ragged", "float32", 4),
    ("lax_ragged", "bfloat16", 4)])
def test_chunked_prefill_then_decode_through_the_paged_pool(impl, dtype, held, request):
    """Every logit row of both requests against the reference's full
    forward over the whole sequence: contexts seven windows long, query
    tiles of 2 and of 3 rows a KV head in one program, the whole bank and a
    device's half of it (experts 4..7), whose pairs are counted by hand."""
    if impl == "pallas":
        request.getfixturevalue("interpreted")
    c, params = model(dtype)
    if held < c.n_experts:
        c, params = share_of(c, params, c.n_experts - held, held)
    both, got_a, got_b, pairs = chunks_then_decode(c, params, impl)
    both = jnp.asarray(both, jnp.int32)
    _, stats = ref.hidden(c, params, both)
    want = ref.logits(c, params, both)
    for got, want_rows, margin in (
        (got_a, want[0], stats["margin"][0]),
        (got_b, want[1, 40:], stats["margin"][1, 40:]),
    ):
        if dtype == "float32":
            assert float(jnp.max(jnp.abs(got - want_rows))) < 2e-4
        else:
            # bf16 against float32, by the rule as it stands (at this
            # preset's 8 experts top-3 the median reads 0.03 sd and the
            # RMS, which one routing flip in fifty positions makes, 0.15).
            result = ref.check_logits(got, want_rows, margin)
            assert result["ok"], result
    if c.expert_share and dtype == "float32":
        # 60 + 20 tokens through 4 expert layers, 3 experts each; those on
        # experts 4..7 counted from the reference's own routing.
        assert pairs[0] == (60 + 20) * 4 * 3
        assert pairs[1] == routed_here(c, params, both[0:1]) \
            + routed_here(c, params, both[1:2], since=40)


def routed_here(c, params, tokens, since=0):
    """By hand: the pairs of `tokens`' positions `since`.. that fall on the
    experts held, over the expert layers, from the router's scores in the
    plain reference's float32 activations (its layers, traced once with a
    tap on each router)."""
    first, held = c.held
    sizes = ref._sizes(c)
    layers = ref._layers(sizes, params)
    items = tuple(sorted((k, v) for k, v in sizes.items()
                         if k not in ("layer_types", "heads")))
    real = ref._mlp

    @jax.jit
    def chosen(tokens):
        taps = []

        def tapped(cc, x, p):
            if "router" in p:
                xn = ref._rms_norm(x, p["mlp_norm"], cc["norm_eps"])
                scores = jax.nn.sigmoid(xn @ p["router"]) + p["router_bias"]
                taps.append(jax.lax.top_k(scores[0, since:], c.experts_per_token)[1])
            return real(cc, x, p)

        ref._mlp = tapped
        try:
            x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
            for kind, h, stack, index, own, rank in layers:
                x, _ = ref._layer(items, kind, h, x, stack, index, own, rank)
        finally:
            ref._mlp = real
        return jnp.stack(taps)

    with jax.default_matmul_precision("highest"):
        top = chosen(tokens)
    return int(jnp.sum((top >= first) & (top < first + held)))


def test_engine_serves_the_share_and_counts_its_pairs_on_the_device():
    """Through `ServingEngine`, float32 so that the count by hand is exact:
    a prompt in chunks, then decode; the counters the device keeps are read
    with the tokens and agree with the reference's routing; the slot
    counters are `moe.plan`'s of the bank held."""
    c, params = share_of(*model("float32"), 0, 4)
    engine = ServingEngine(c, params, slots=2, max_len=128, kv_block_size=4,
                           prefill_chunk_tokens=16)
    try:
        prompt = np.random.default_rng(8).integers(0, c.vocab_size, 41).tolist()
        out, tokens = engine.submit(prompt, max_new_tokens=9, temperature=0.0), []
        while (tok := out.get(timeout=300)) is not None:
            assert not isinstance(tok, BaseException), tok
            tokens.append(int(tok))
        stats = engine.stats()
    finally:
        engine.close()
    want = ref.greedy_path(c, params, jnp.asarray([prompt], jnp.int32), 9)
    assert tokens == np.asarray(want[0][0]).tolist()
    # Routed: the prompt's 41 tokens and the 8 generated ones that were fed.
    fed = jnp.asarray([prompt + tokens[:-1]], jnp.int32)
    assert stats["moe_experts_held"] == 4 and stats["moe_experts_published"] == 8
    assert stats["moe_pairs_total"] == 49 * 4 * 3
    assert stats["moe_local_pairs_total"] == routed_here(c, params, fed)
    assert stats["moe_routed_slots_total"] == stats["moe_local_pairs_total"]
    # Computed: the capacity path's held x rows x capacity, a launch: chunks
    # of 16, 16, 9 (padded to 16) tokens, then 2 launches of 4 steps over
    # the 2 slots, 4 expert layers each.
    assert moe.plan(c, 1, 16) == (False, 4 * 16, 0) and moe.plan(c, 2, 1)[:2] == (False, 8)
    assert stats["moe_computed_slots_total"] == 4 * (3 * 4 * 16 + 2 * 4 * 4 * 2)
    assert stats["layer_pattern"] == "fwwwf" and stats["kv_pool_layers"] == 5


@pytest.mark.parametrize("path", ["lax_ragged", "pallas"])
def test_engine_serves_the_model_and_reuses_cached_head_blocks(path, request):
    if path == "pallas":
        request.getfixturevalue("interpreted")
    c, params = model("bfloat16")
    engine = ServingEngine(
        c, params, slots=4, max_len=128, kv_block_size=4, prefill_chunk_tokens=16
    )
    try:
        # At 8 experts top-3 with scaling 2.5 one expert flipped on a router
        # near-tie is most of a layer, and bf16 rounds such a tie one way on
        # one attention path and the other way on the other: seeds 6 and 7
        # read 2.2 and 0.44 sd at one token; 11 reads 0.0 on the lax path
        # and one token out on the kernel's.
        rng = np.random.default_rng(11)
        head = rng.integers(0, c.vocab_size, 40).tolist()
        prompts = [head + rng.integers(0, c.vocab_size, 12).tolist() for _ in range(3)]
        got = []
        for prompt in prompts:                        # one after the other
            out, tokens = engine.submit(prompt, max_new_tokens=6, temperature=0.0), []
            while (tok := out.get(timeout=300)) is not None:
                assert not isinstance(tok, BaseException), tok
                tokens.append(int(tok))
            got.append(tokens)
        stats = engine.stats()
    finally:
        engine.close()
    ref_out = jax.device_get(ref.greedy_path(c, params, jnp.asarray(prompts, jnp.int32), 6))
    # The rule's own numbers are the cell's (at Laguna's widths); here the
    # bf16 engine's tokens are held to 0.15 sd under the reference's best,
    # all but at most one (a flipped tie ends its row: a wrong model leaves
    # the path in every row at once and checks three tokens, not twelve).
    result = ref.check_tokens(got, *ref_out)
    assert result["checked"] >= 12 and result["outside_at_sd"]["0.15"] <= 1, result
    assert stats["prefix_tokens_reused_total"] == 2 * 40        # ten whole blocks, twice
    assert stats["attn_path"] == path and stats["sliding_window"] == 8
    assert stats["moe_experts_held"] == stats["moe_experts_published"] == 8
    # the whole bank: every pair is local, counted from shapes at the launch
    assert stats["moe_pairs_total"] == stats["moe_local_pairs_total"] == 4 * 3 * (
        stats["prefill_tokens_computed_total"] + stats["decode_slot_steps_total"])


# -- the share ties to the model ------------------------------------------------------


def expert_layer(params, index):
    return jax.tree_util.tree_map(lambda a: a[index], params["layers"])


@pytest.mark.parametrize("formulation", ["capacity", "routed"])
def test_the_shares_add_up_to_the_uncut_layer(formulation):
    """Experts 0..3 and 4..7 of 8, each on a device of its own: the parts
    the two give, the shared expert (which both compute) counted once, are
    the uncut reference's whole layer."""
    c, params = model("float32")
    p = expert_layer(params, 1)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 128, c.d_model), jnp.float32)
    sizes = ref._sizes(c)
    with jax.default_matmul_precision("highest"):
        whole, _ = ref._mlp(sizes, x, p)
        xn = ref._rms_norm(x, p["mlp_norm"], c.norm_eps)
        shared = ref._swiglu(xn, p["ws_gate"], p["ws_up"], p["ws_down"])
        parts = []
        for first in (0, 4):
            cs = c.with_(experts_held=4, experts_first=first)
            ps = {**p, **{w: p[w][first:first + 4] for w in BANK}}
            if formulation == "routed":
                routed, _ = moe._moe_mlp_routed(cs, xn, ps)
                also, _ = moe._moe_mlp_capacity(cs, xn, ps)
                assert float(jnp.max(jnp.abs(routed - also))) < 1e-5
            else:
                routed, _, pairs = moe.moe_mlp(cs, xn, ps, counted=jnp.ones((1, 128), bool))
                assert pairs[0] == 128 * 3 and 0 < pairs[1] < 128 * 3
            mine, _ = ref._mlp({**sizes, "experts_first": first}, x, ps)
            assert float(jnp.max(jnp.abs(x + routed + shared - mine))) < 1e-5
            parts.append(routed)
    assert float(jnp.max(jnp.abs(x + parts[0] + parts[1] + shared - whole))) < 1e-5
    assert float(jnp.max(jnp.abs(parts[0]))) > 0.1 < float(jnp.max(jnp.abs(parts[1])))


def test_a_share_of_the_routed_bank_has_the_capacity_paths_gradient():
    c, params = model("float32")
    p = expert_layer(params, 0)
    cs = c.with_(experts_held=4, experts_first=4)
    ps = {**p, **{w: p[w][4:] for w in BANK}}
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 128, c.d_model), jnp.float32)

    def loss(path, x, bank):
        out, _ = path(cs, x, {**ps, **bank})
        return jnp.sum(out * out)

    bank = {w: ps[w] for w in BANK}
    with jax.default_matmul_precision("highest"):
        want = jax.grad(functools.partial(loss, moe._moe_mlp_capacity), (0, 1))(x, bank)
        got = jax.grad(functools.partial(loss, moe._moe_mlp_routed), (0, 1))(x, bank)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert bool(jnp.all(jnp.isfinite(a)))
        assert float(jnp.max(jnp.abs(a - b))) < 1e-3 * max(1.0, float(jnp.max(jnp.abs(b))))


def test_the_whole_bank_held_is_the_layer_it_was():
    """`experts_held` = every expert is no share: the same operations, the
    same bits, the same programs (tests/test_tpu_lowering.py holds the
    hashes of the parent's programs for the other presets)."""
    c, params = model("bfloat16")
    named = c.with_(experts_held=c.n_experts)
    assert not named.expert_share and named.held == c.held == (0, 8)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 32), 0, c.vocab_size)
    assert bool(jnp.all(forward(named, params, tokens) == forward(c, params, tokens)))
    lowered = [
        jax.jit(functools.partial(forward, cfg)).lower(params, tokens).as_text()
        for cfg in (c, named)
    ]
    assert lowered[0] == lowered[1]
    assert moe.plan(named, 1, 512) == moe.plan(c, 1, 512)


def test_plan_and_the_slots_of_a_share():
    """What the cell's launches multiply, by hand: a decode launch of 16
    rows takes the capacity path over the 128 experts held (one slot a row
    and expert); a 512-token chunk the routed path, 5,120 pairs of which
    half fall here in the mean, and a 128-row tile an expert held."""
    assert moe.plan(CUT, 16, 1) == (False, 128 * 16 * 1, 0)
    assert moe.row_tile(512 * 10, 256) == 128
    assert moe.plan(CUT, 1, 512) == (True, 5120 * 128 // 256 + 128 * 128, 128)
    assert moe.bank_slots(1, 512, 10, 256, 512, 128, 128) == (128 * 512, 2560 + 16384)
    whole = CUT.with_(experts_held=0)
    assert moe.plan(whole, 1, 512) == (True, 5120 + 256 * 128, 128)
    assert moe.bank_slots(1, 512, 10, 256, 512, 128) == (256 * 512, 5120 + 32768)


# -- the rotary embedding of a part of a head -----------------------------------------


def test_partial_rotary_rotates_a_slice_with_yarn_on_its_width():
    rope = RopeParams.of(YARN, 1e4)
    assert rope.partial_rotary_factor == 0.5 and rope.rotary_dim(128) == 64
    inv_freq, factor = rope.inv_freq(64)
    width, want, scale = ref.rope_frequencies(YARN, 128)
    assert width == 64 and np.allclose(inv_freq, want, rtol=1e-12)
    assert factor == scale == pytest.approx(0.1 * np.log(128) + 1)
    # the correction range is of the ROTATED width: 64, not 128
    assert not np.allclose(inv_freq, RopeParams.of(
        {**YARN, "partial_rotary_factor": 1}, 1e4).inv_freq(128)[0][:32])
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 2, 128), jnp.float32)
    got = _rope(x, jnp.arange(6), rope)
    assert bool(jnp.all(got[..., 64:] == x[..., 64:]))          # unrotated, unscaled
    want = ref._rope(x, width, want, scale)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    assert float(jnp.max(jnp.abs(got[:, 1:, :, :64] - x[:, 1:, :, :64]))) > 0.1
    assert RopeParams.of({"rope_type": "default", "rope_theta": 1e4,
                          "partial_rotary_factor": 1}, 1e4).rotary_dim(128) == 128
    with pytest.raises(ValueError, match="not understood"):
        RopeParams.of({"rope_type": "default", "rope_theta": 1e4, "mscale": 1.0}, 1e4)
    with pytest.raises(ValueError, match="partial_rotary_factor"):
        RopeParams.of({"rope_theta": 1e4, "partial_rotary_factor": 1.5}, 1e4)


# -- the configuration's rules and counts ---------------------------------------------


def test_the_configuration_says_what_it_cannot_run():
    c = CFG
    assert c.heads_per_layer == (4, 6, 6, 6, 4) and c.layer_types[0] == FULL
    assert hash(c) == hash(c.with_()) and c.with_(n_layers=3).heads_per_layer == (4, 6, 6)
    assert PRESETS["tiny"].heads(FULL) == 4 and not PRESETS["tiny"].heads_by_kind
    assert PRESETS["tiny-window"].stack_kinds == (PRESETS["tiny-window"].layer_types,)
    assert PRESETS["tiny-latent"].stack_kinds == ((FULL,), (FULL, FULL))
    with pytest.raises(ValueError, match="layer_types beside latent attention"):
        PRESETS["tiny-latent"].with_(layer_types=[FULL] * 3)
    with pytest.raises(ValueError, match="heads_per_layer names 5 layers"):
        c.with_(n_layers=6, layer_types=PERIOD * 2)
    with pytest.raises(ValueError, match="every layer of one kind the same count"):
        c.with_(heads_per_layer=(4, 6, 6, 4, 4))
    with pytest.raises(ValueError, match="counts that n_kv_heads divides"):
        c.with_(heads_per_layer=(4, 5, 5, 5, 4))
    with pytest.raises(ValueError, match="heads_per_layer needs attention layers"):
        PRESETS["tiny-latent"].with_(heads_per_layer=(4, 4, 4))
    with pytest.raises(ValueError, match="attn_gate='tanh'"):
        c.with_(attn_gate="tanh")
    with pytest.raises(ValueError, match="attn_gate beside latent attention"):
        PRESETS["tiny-latent"].with_(attn_gate="sigmoid")
    for first, held in ((0, 9), (6, 4), (-1, 4), (2, 0)):
        with pytest.raises(ValueError, match="not a share of the bank"):
            c.with_(experts_first=first, experts_held=held)


def test_counts_at_the_published_sizes():
    """The weights reckoned in ISSUE 37, norms included. The router's
    selection bias (256 floats an expert layer, `assumed` zero) is a buffer
    the gradient does not move and is counted apart: `init_params` makes it,
    `param_count` leaves it out."""
    shapes = jax.eval_shape(lambda: init_params(CFG, jax.random.PRNGKey(0)))
    leaves = jax.tree_util.tree_leaves_with_path(shapes)
    bias = sum(a.size for path, a in leaves if "router_bias" in jax.tree_util.keystr(path))
    assert bias == 4 * 8
    assert CFG.param_count() == sum(a.size for _, a in leaves) - bias
    assert LAGUNA.attn_params(FULL) == 2 * 3072 * 6144 + 2 * 3072 * 1024 + 3072 * 48 \
        == 44_187_648
    assert LAGUNA.attn_params(SLIDING) == 2 * 3072 * 9216 + 2 * 3072 * 1024 + 3072 * 72 \
        == 63_135_744
    assert LAGUNA.mlp_params(dense=True) == 113_246_208
    assert LAGUNA.mlp_params() == 257 * 9_437_184 + 786_432
    assert CUT.mlp_params() == 129 * 9_437_184 + 786_432
    assert LAGUNA.param_count() == 117_561_953_280                 # the card's "118B"
    assert CUT.param_count() == 5_572_076_544                      # 11.14 GB in bf16
    assert 4 * 256 == 1024 and 47 * 256 == 12_032                  # the bias, apart
    # FLOPs a token at 4,096 positions, the published model: a full layer's
    # queries see 2,048 keys in the mean, a window layer's 512 - 512^2 / 8,192
    # = 480; 10 of 256 experts and the shared one; the dense layer.
    attn = lambda kind, keys: 2 * LAGUNA.attn_params(kind) \
        + 2 * keys * LAGUNA.heads(kind) * (128 + 128)
    expert = 3 * 2 * 3072 * 1024 * 11 + 2 * 3072 * 256
    assert LAGUNA.flops_per_token(4096) == pytest.approx(3.0 * (
        12 * attn(FULL, 2048) + 36 * attn(SLIDING, 480) + 47 * expert
        + 3 * 2 * 3072 * 12288 + 2 * 3072 * 100352))
    # a chip's share multiplies the half of the 10 that falls on it
    assert CUT.flops_per_token() - CUT.with_(experts_held=0).flops_per_token() \
        == pytest.approx(-3.0 * 4 * 3 * 2 * 3072 * 1024 * 5)


# -- what may not be silently wrong ----------------------------------------------------------


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
def test_engine_features_that_assume_one_stack_of_one_kind_refuse_the_model(feature):
    """Each either works for this model and has its own test, or raises at
    construction naming the feature; none runs and gives other numbers."""
    c, params = model("bfloat16")
    kwargs, named = REFUSED[feature]
    if feature == "int8":
        params = quantize_params(params)
    if feature == "mesh":
        from dstack_tpu.workloads.sharding import make_mesh

        kwargs = dict(mesh=make_mesh(jax.devices()[:2], model=2))
    with pytest.raises(ValueError, match=named) as err:
        ServingEngine(c, params, slots=2, max_len=64, kv_block_size=4, **kwargs)
    assert "layer_types" in str(err.value)


def test_pipeline_stages_refuse_the_model():
    from dstack_tpu.workloads.pipeline import stage_params

    c, params = model()
    with pytest.raises(ValueError, match="ONE kind"):
        stage_params(c, params, 2)
