"""Mixture-of-experts workload: routing math, expert parallelism, training.

Runs on the 8-device virtual CPU mesh from tests/conftest.py.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dstack_tpu.workloads.config import PRESETS
from dstack_tpu.workloads import moe
from dstack_tpu.workloads.moe import expert_capacity, moe_mlp, route
from dstack_tpu.workloads.sharding import make_mesh
from dstack_tpu.workloads.train import (
    init_train_state,
    make_train_step,
    synthetic_batch,
)
from dstack_tpu.workloads.transformer import forward, init_params

CFG = PRESETS["tiny-moe"]


def _rand_params(key, c):
    p = init_params(c, key)["layers"]
    # Strip the leading layer-stack dim for direct moe_mlp calls.
    return {k: v[0] for k, v in p.items() if k.startswith(("router", "we_"))}


class TestRouting:
    def test_dispatch_combine_shapes_and_capacity(self):
        c = CFG
        h = jax.random.normal(jax.random.PRNGKey(0), (2, 16, c.d_model),
                              dtype=jnp.bfloat16)
        router = jax.random.normal(jax.random.PRNGKey(1), (c.d_model, c.n_experts))
        dispatch, combine, aux = route(c, h, router)
        C = expert_capacity(c, 16)
        assert dispatch.shape == (2, 16, c.n_experts, C)
        assert combine.shape == dispatch.shape
        # Each slot of each expert holds at most one token.
        assert float(jnp.max(jnp.sum(dispatch, axis=1))) <= 1.0 + 1e-6
        # A token occupies at most k slots and combine weights sum to <= 1.
        per_token = jnp.sum(combine, axis=(2, 3))
        assert float(jnp.max(per_token)) <= 1.0 + 1e-5
        assert float(aux) > 0.0

    def test_moe_matches_dense_reference(self):
        """With capacity high enough that nothing drops, the einsum-dispatch
        layer must equal the straightforward per-token top-k computation."""
        c = CFG.with_(capacity_factor=8.0)  # no drops
        key = jax.random.PRNGKey(2)
        p = _rand_params(key, c)
        h = jax.random.normal(
            jax.random.fold_in(key, 1), (2, 8, c.d_model), dtype=jnp.float32
        ).astype(jnp.bfloat16)

        out, _ = moe_mlp(c, h, p)

        # Reference: loop over tokens in numpy-esque jax.
        probs = jax.nn.softmax(
            jnp.einsum("bsd,de->bse", h, p["router"],
                       preferred_element_type=jnp.float32), axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, c.experts_per_token)
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

        def expert_ffn(e, x):
            g = jax.nn.silu(
                (x @ p["we_gate"][e]).astype(jnp.float32)
            ).astype(x.dtype)
            u = x @ p["we_up"][e]
            return (g * u) @ p["we_down"][e]

        ref = jnp.zeros_like(h)
        for b in range(h.shape[0]):
            for s in range(h.shape[1]):
                acc = jnp.zeros((c.d_model,), dtype=jnp.float32)
                for j in range(c.experts_per_token):
                    e = int(gate_idx[b, s, j])
                    y = expert_ffn(e, h[b, s][None, None, :])[0, 0]
                    acc = acc + float(gate_vals[b, s, j]) * y.astype(jnp.float32)
                ref = ref.at[b, s].set(acc.astype(ref.dtype))

        np.testing.assert_allclose(
            np.asarray(out, dtype=np.float32),
            np.asarray(ref, dtype=np.float32),
            rtol=0.1, atol=0.05,
        )

    def test_capacity_overflow_drops_not_crashes(self):
        c = CFG.with_(capacity_factor=0.25)
        p = _rand_params(jax.random.PRNGKey(3), c)
        h = jax.random.normal(jax.random.PRNGKey(4), (1, 32, c.d_model),
                              dtype=jnp.bfloat16)
        out, aux = moe_mlp(c, h, p)
        assert bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))
        # Some tokens must have been dropped at this capacity.
        dispatch, _, _ = route(c, h, p["router"])
        placed = float(jnp.sum(dispatch))
        wanted = h.shape[0] * h.shape[1] * c.experts_per_token
        assert placed < wanted


class TestMoETraining:
    def test_forward_returns_aux(self):
        params = init_params(CFG, jax.random.PRNGKey(0))
        tokens = jnp.zeros((2, 16), dtype=jnp.int32)
        logits, aux = forward(CFG, params, tokens, return_aux=True)
        assert logits.shape == (2, 16, CFG.vocab_size)
        assert float(aux) > 0.0

    def test_train_step_single_device(self):
        state = init_train_state(CFG, jax.random.PRNGKey(0))
        step = make_train_step(CFG)
        batch = synthetic_batch(CFG, batch_size=2, seq_len=32)
        state, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))
        assert float(metrics["router_aux"]) > 0.0
        assert int(state.step) == 1

    def test_train_step_expert_parallel_mesh(self):
        """ep x tp x fsdp: expert axis 2, model 2, fsdp absorbs 2."""
        mesh = make_mesh(jax.devices()[:8], expert=2, model=2)
        assert dict(mesh.shape)["expert"] == 2
        state = init_train_state(CFG, jax.random.PRNGKey(0), mesh=mesh)
        step = make_train_step(CFG, mesh)
        batch = synthetic_batch(CFG, batch_size=4, seq_len=32, mesh=mesh)
        state, metrics = step(state, batch)
        loss_ep = float(metrics["loss"])
        assert np.isfinite(loss_ep)

        # Same math without the mesh: losses must agree (routing + experts
        # are deterministic; only the layout differs).
        state1 = init_train_state(CFG, jax.random.PRNGKey(0))
        step1 = make_train_step(CFG)
        batch1 = synthetic_batch(CFG, batch_size=4, seq_len=32)
        _, metrics1 = step1(state1, batch1)
        assert abs(loss_ep - float(metrics1["loss"])) < 0.05

    def test_expert_weights_sharded_over_expert_axis(self):
        mesh = make_mesh(jax.devices()[:8], expert=2, model=2)
        state = init_train_state(CFG, jax.random.PRNGKey(0), mesh=mesh)
        sh = state.params["layers"]["we_gate"].sharding
        assert "expert" in sh.spec


class TestMoEGenerate:
    def test_decode_matches_forward(self):
        from dstack_tpu.workloads.generate import generate

        c = CFG.with_(capacity_factor=8.0)
        params = init_params(c, jax.random.PRNGKey(0))
        prompt = jnp.array([[5, 7, 11, 13]], dtype=jnp.int32)
        new = generate(c, params, prompt, max_new_tokens=4, temperature=0.0)
        assert new.shape == (1, 4)

        # Greedy decode must agree with argmax over the plain forward at
        # every step (KV-cache path == training forward, MoE included).
        seq = prompt
        for t in range(4):
            logits = forward(c, params, seq)
            greedy = int(jnp.argmax(logits[0, -1]))
            assert int(new[0, t]) == greedy, f"step {t}"
            seq = jnp.concatenate([seq, new[:, t : t + 1]], axis=1)


def _rel_l2(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(a), 1e-9))


class TestRoutedPath:
    """The routed path (moe._moe_mlp_routed: expert-sorted rows through
    grouped matmuls) must equal the capacity path's einsums — same slot
    permutation, same drops, same gate weighting (tests pin both clean
    and overflow regimes). The grouped matmul runs interpreted here."""

    def _pair(self, c, key, shape):
        p = _rand_params(key, c)
        h = jax.random.normal(
            jax.random.fold_in(key, 7), shape, dtype=jnp.float32
        ).astype(jnp.bfloat16)
        out_e, aux_e = moe._moe_mlp_capacity(c, h, p)
        out_g, aux_g = moe._moe_mlp_routed(c, h, p)
        return out_e, aux_e, out_g, aux_g

    def test_matches_einsum_no_drops(self):
        c = CFG.with_(capacity_factor=8.0)
        out_e, aux_e, out_g, aux_g = self._pair(
            c, jax.random.PRNGKey(11), (2, 32, c.d_model))
        np.testing.assert_allclose(
            np.asarray(out_e, np.float32), np.asarray(out_g, np.float32),
            # Each path rounds to bf16 at its own places (the einsum path
            # the gate, the routed path the expert's gate product): a bf16
            # ulp of the k terms a token sums (|term| < 4), both as near
            # an f32 evaluation (0.0099 and 0.0092 from it).
            rtol=2e-2, atol=2e-2,
        )
        assert float(aux_e) == float(aux_g)

    def test_matches_einsum_with_overflow_drops(self):
        c = CFG.with_(capacity_factor=0.25)
        out_e, _, out_g, _ = self._pair(
            c, jax.random.PRNGKey(12), (1, 64, c.d_model))
        np.testing.assert_allclose(
            np.asarray(out_e, np.float32), np.asarray(out_g, np.float32),
            rtol=2e-2, atol=2e-2,
        )

    def test_gradients_match_einsum(self):
        c = CFG.with_(capacity_factor=1.0)
        p = _rand_params(jax.random.PRNGKey(13), c)
        h = jax.random.normal(
            jax.random.PRNGKey(14), (2, 32, c.d_model), jnp.float32
        ).astype(jnp.bfloat16)

        def loss(params, mlp):
            out, aux = mlp(c, h, params)
            return jnp.sum(out.astype(jnp.float32) ** 2) + aux

        g_e = jax.grad(loss)(p, moe._moe_mlp_capacity)
        g_g = jax.grad(loss)(p, moe._moe_mlp_routed)
        # The einsum path rounds the gate to bf16 inside combine (the
        # routed path keeps it f32), so the two formulations are slightly
        # different FUNCTIONS at bf16 — gradients agree to bf16 rounding
        # accumulated over the token sum, tightest for the expert banks
        # and loosest for the router (whose grad flows entirely through
        # the gate). Elementwise for the banks; relative L2 for router.
        for k in ("we_gate", "we_up", "we_down"):
            np.testing.assert_allclose(
                np.asarray(g_e[k], np.float32), np.asarray(g_g[k], np.float32),
                rtol=1e-1, atol=1e-1,
            )
        assert _rel_l2(g_e["router"], g_g["router"]) < 0.05

    @pytest.mark.parametrize("axes,routed", [
        ({"data": 2, "fsdp": 2}, True),
        # An expert axis makes the dispatch einsum the token all-to-all:
        # the rule keeps the capacity path there.
        ({"data": 2, "fsdp": 1, "model": 2, "expert": 2}, False),
    ], ids=["fsdp", "expert"])
    def test_trains_on_mesh_with_expert_parallelism(self, axes, routed, monkeypatch):
        # 8 experts at the no-drop factor: a 512-token row is the least
        # the rule hands to the routed path.
        c = PRESETS["tiny-moe"].with_(
            n_experts=8, capacity_factor=4.0, max_seq_len=512
        )
        taken = []
        monkeypatch.setattr(
            moe, "_moe_mlp_routed",
            lambda *a, _f=moe._moe_mlp_routed: taken.append(1) or _f(*a),
        )
        mesh = make_mesh(jax.devices()[:math.prod(axes.values())], **axes)
        state = init_train_state(c, jax.random.PRNGKey(0), mesh=mesh,
                                 learning_rate=1e-2)
        step = make_train_step(c, mesh, learning_rate=1e-2)
        batch = synthetic_batch(c, batch_size=4, seq_len=512, mesh=mesh)
        losses = []
        for _ in range(3):
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
        assert all(np.isfinite(losses)) and losses[-1] < losses[0]
        assert bool(taken) == routed


# The three routers the benchmark's cells use, at test widths.
_ROUTERS = {
    "softmax-top2-of-8": dict(n_experts=8, experts_per_token=2),
    "sigmoid-top4-bias-scaling-shared": dict(
        n_experts=16, experts_per_token=4, router_score="sigmoid",
        routed_scaling=1.8, n_shared_experts=1,
    ),
    "softmax-top8-of-64": dict(n_experts=64, experts_per_token=8),
}


def _block_params(c, key):
    """One expert layer's MLP weights (router, bank, shared expert, norm),
    the router's selection bias drawn at random where there is one."""
    layer = {
        k: v[0] for k, v in init_params(c, key)["layers"].items()
        if k.startswith(("router", "we_", "ws_", "mlp_norm"))
    }
    if "router_bias" in layer:
        layer["router_bias"] = 0.1 * jax.random.normal(
            jax.random.fold_in(key, 5), layer["router_bias"].shape)
    return layer


@pytest.mark.parametrize("drops", [False, True], ids=["no-drops", "drops"])
@pytest.mark.parametrize("router", sorted(_ROUTERS))
class TestRoutedAgainstCapacity:
    """moe_block through both formulations, over the cells' routers: the
    forward within bf16 rounding, jax.grad of a loss through both."""

    def _case(self, router, drops):
        spec = _ROUTERS[router]
        c = CFG.with_(
            capacity_factor=(0.5 if drops else
                             spec["n_experts"] / spec["experts_per_token"]),
            **spec,
        )
        key = jax.random.PRNGKey(len(router) + drops)
        x = jax.random.normal(
            jax.random.fold_in(key, 1), (2, 64, c.d_model), jnp.float32
        ).astype(jnp.bfloat16)
        if drops:  # some routed row must lie beyond the capacity
            _, _, slot, _, _ = moe.route_assignments(
                c, x, _block_params(c, key)["router"])
            assert int(slot.max()) >= expert_capacity(c, 64)
        return c, _block_params(c, key), x

    @staticmethod
    def _block(c, x, p, mlp, monkeypatch):
        monkeypatch.setattr(moe, "moe_mlp", lambda c, h, p, *a: mlp(c, h, p))
        return moe.moe_block(c, x, p)

    def test_forward(self, router, drops, monkeypatch):
        c, p, x = self._case(router, drops)
        out_c, aux_c = self._block(c, x, p, moe._moe_mlp_capacity, monkeypatch)
        out_r, aux_r = self._block(c, x, p, moe._moe_mlp_routed, monkeypatch)
        np.testing.assert_allclose(
            np.asarray(out_c, np.float32), np.asarray(out_r, np.float32),
            rtol=2e-2, atol=2e-2,
        )
        assert _rel_l2(out_c - x, out_r - x) < 0.01
        assert float(aux_c) == float(aux_r)

    def test_gradients(self, router, drops, monkeypatch):
        c, p, x = self._case(router, drops)

        def loss(params, x, mlp):
            out, aux = self._block(c, x, params, mlp, monkeypatch)
            return jnp.sum((out - x).astype(jnp.float32) ** 2) + aux

        g_c = jax.grad(loss, argnums=(0, 1))(p, x, moe._moe_mlp_capacity)
        g_r = jax.grad(loss, argnums=(0, 1))(p, x, moe._moe_mlp_routed)
        for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(g_c),
            jax.tree_util.tree_leaves(g_r),
        ):
            name = jax.tree_util.keystr(path)
            if "router_bias" in name:  # chooses, does not weigh: no gradient
                assert not np.any(np.asarray(a)) and not np.any(np.asarray(b))
                continue
            assert _rel_l2(a, b) < (0.05 if "router" in name else 0.02), name


def test_routed_path_with_an_expert_that_receives_no_row():
    """A group of size 0 in the grouped matmuls: its weights get a zero
    gradient and the other experts' rows keep their places."""
    c = CFG.with_(n_experts=8, capacity_factor=4.0)
    p = _rand_params(jax.random.PRNGKey(21), c)
    p["router"] = p["router"].at[:, 3].set(0.0)
    h = jnp.abs(jax.random.normal(
        jax.random.PRNGKey(22), (2, 32, c.d_model), jnp.float32
    )).astype(jnp.bfloat16)
    # Expert 3 scores 0 on every token and some expert scores above it.
    p["router"] = jnp.abs(p["router"]).at[:, 3].set(-1.0)
    _, gate_idx, _, _, _ = moe.route_assignments(c, h, p["router"])
    assert not bool(jnp.any(gate_idx == 3))

    def loss(params, mlp):
        out, aux = mlp(c, h, params)
        return jnp.sum(out.astype(jnp.float32) ** 2) + aux

    out_c, _ = moe._moe_mlp_capacity(c, h, p)
    out_r, _ = moe._moe_mlp_routed(c, h, p)
    assert _rel_l2(out_c, out_r) < 0.01
    g_c = jax.grad(loss)(p, moe._moe_mlp_capacity)
    g_r = jax.grad(loss)(p, moe._moe_mlp_routed)
    for k in ("we_gate", "we_up", "we_down"):
        assert not np.any(np.asarray(g_r[k][3], np.float32))
        assert _rel_l2(g_c[k], g_r[k]) < 0.02


_BANK = ("we_gate", "we_up", "we_down")


@pytest.mark.parametrize("rows", [16, 5], ids=["16-rows", "5-rows-padded"])
@pytest.mark.parametrize("held", [0, 32], ids=["whole", "share"])
class TestFewRowsOfOneToken:
    """A decode launch's expert layer on the routed path: rows of one token,
    top-2 of 64 experts (all held, or experts 32..63), the routed rows padded
    to a whole row tile of 32 where they are fewer (5 x 2 = 10)."""

    def _case(self, rows, held):
        c = CFG.with_(n_experts=64, experts_per_token=2, capacity_factor=32.0,
                      experts_held=held, experts_first=64 - held if held else 0)
        p = _block_params(c, jax.random.PRNGKey(31 + rows))
        p = {w: v for w, v in p.items() if w == "router" or w in _BANK}
        if held:
            assert c.expert_share and p["we_gate"].shape[0] == held
        h = jax.random.normal(
            jax.random.PRNGKey(32), (rows, 1, c.d_model), jnp.float32
        ).astype(jnp.bfloat16)
        return c, p, h

    def test_equals_the_capacity_path(self, rows, held):
        c, p, h = self._case(rows, held)
        assert moe.plan(c, rows, 1)[0] and moe.plan(c, rows, 1)[2] == 32
        out_c, aux_c = moe._moe_mlp_capacity(c, h, p)
        out_r, aux_r = moe._moe_mlp_routed(c, h, p)
        np.testing.assert_allclose(
            np.asarray(out_c, np.float32), np.asarray(out_r, np.float32),
            rtol=2e-2, atol=2e-2,
        )
        assert _rel_l2(out_c, out_r) < 0.01 and float(aux_c) == float(aux_r)

    def test_an_expert_no_row_chose_is_not_read(self, rows, held):
        """Its weights made NaN: the routed path gives the same bits (the
        grouped matmul visits no tile of an empty group), the capacity path
        NaN (it multiplies every expert's slots, empty or not)."""
        c, p, h = self._case(rows, held)
        _, gate_idx, _, _, _ = moe.route_assignments(c, h, p["router"])
        first, n = c.held
        chosen = set(np.asarray(gate_idx).ravel().tolist())
        idle = jnp.asarray([e for e in range(n) if first + e not in chosen])
        assert 0 < len(idle) < n
        poisoned = {**p, **{w: p[w].at[idle].set(jnp.nan) for w in _BANK}}
        clean, _ = moe._moe_mlp_routed(c, h, p)
        out, _ = moe._moe_mlp_routed(c, h, poisoned)
        assert bool(jnp.all(out == clean)) and bool(jnp.all(jnp.isfinite(clean)))
        assert not bool(jnp.all(jnp.isfinite(moe._moe_mlp_capacity(c, h, poisoned)[0])))


# (rows on a device, tokens a row, experts, top-k, capacity factor, experts
# held: 0 = all) of the launches the benchmark's cells make -> the
# formulation moe.plan answers and the slots it multiplies (PERF.md section
# 3). A decode launch's 16 rows touch 1 - (1 - k / E) ** 16 of the experts:
# 99.0% (rollout), 88.2% (mellum), 64.4% (the latent cell), 47.1% (laguna);
# routed, it multiplies its rows and a 32-row tile for each expert touched.
_LAUNCHES = {
    "train row, 4,096 tokens, top-2 of 8": ((1, 4096, 8, 2, 4.0, 0), True, 8192 + 8 * 512),
    "latent cell, 512-token chunk, top-4 of 64": ((1, 512, 64, 4, 16.0, 0), True, 2048 + 64 * 128),
    "mellum, 512-token chunk, top-8 of 64": ((1, 512, 64, 8, 8.0, 0), True, 4096 + 64 * 128),
    "rollout, 128-token chunk, top-2 of 8": ((1, 128, 8, 2, 4.0, 0), False, 8 * 128),
    "rollout, decode launch, 16 rows": ((16, 1, 8, 2, 4.0, 0), False, 8 * 16),
    "latent cell, decode launch, 16 rows": ((16, 1, 64, 4, 16.0, 0), True, 64 + 41 * 32),
    "mellum, decode launch, 16 rows": ((16, 1, 64, 8, 8.0, 0), False, 64 * 16),
    "laguna, decode launch, 16 rows, 128 of 256 held":
        ((16, 1, 256, 10, 25.6, 128), True, 80 + 60 * 32),
    "top-10 of 256, decode launch, 16 rows, whole bank":
        ((16, 1, 256, 10, 25.6, 0), True, 160 + 121 * 32),
    "laguna, 512-token chunk, 128 of 256 held":
        ((1, 512, 256, 10, 25.6, 128), True, 2560 + 128 * 128),
}


def _config(E, k, cf, held):
    return CFG.with_(n_experts=E, experts_per_token=k, capacity_factor=cf,
                     experts_held=held)


@pytest.mark.parametrize("launch", sorted(_LAUNCHES))
def test_the_rule_at_the_cells_launch_shapes(launch):
    (rows, row_len, E, k, cf, held), routed, slots = _LAUNCHES[launch]
    c = _config(E, k, cf, held)
    assert moe.plan(c, rows, row_len)[:2] == (routed, slots)
    # A bank that is not whole on the device keeps the capacity path.
    capacity = c.held[1] * rows * expert_capacity(c, row_len)
    assert moe.plan(c, rows, row_len, whole_bank=False)[:2] == (False, capacity)


def _rule_before_few_rows(c, rows, row_len, whole_bank):
    """`moe.plan` as it was before it routed a launch of few rows of one
    token: routed only where it multiplies at most two thirds of the slots."""
    k, E = c.experts_per_token, c.n_experts
    tile = moe.row_tile(rows * row_len * k, E)
    at_capacity, routed = moe.bank_slots(
        rows, row_len, k, E, expert_capacity(c, row_len), tile, c.held[1])
    if whole_bank and tile and 3 * routed <= 2 * at_capacity:
        return True, routed, tile
    return False, at_capacity, tile


@pytest.mark.parametrize("bank", sorted({
    (E, k, cf, held) for (_, _, E, k, cf, held), _, _ in _LAUNCHES.values()}))
def test_calls_of_more_than_one_token_a_row_keep_the_rule_they_had(bank):
    """Only a launch of one token a row on a whole bank can change answer:
    train rows, prefill chunks and speculation's verify rows keep it, and a
    bank that is not whole keeps the capacity path at any shape."""
    c = _config(*bank)
    for rows in (1, 2, 4, 16, 128):
        for row_len in (2, 5, 16, 128, 512, 4096):
            for whole in (True, False):
                assert moe.plan(c, rows, row_len, whole) == _rule_before_few_rows(
                    c, rows, row_len, whole), (rows, row_len, whole)
        assert moe.plan(c, rows, 1, False)[:2] == _rule_before_few_rows(c, rows, 1, False)[:2]
        assert not moe.plan(c, rows, 1, False)[0]


def test_engine_counts_the_slots_its_programs_multiply(monkeypatch):
    """A 512-token prefill chunk of an 8-expert model takes the routed path,
    the short last chunk the capacity path, and every decode launch the
    routed path again (its 2 rows of top-2 of 8 touch 44% of the experts):
    the engine's counters read `moe.plan`, the function `moe_mlp` dispatched
    on, and the tokens are those of an engine held to the capacity path."""
    from dstack_tpu.workloads.serving import ServingEngine

    c = CFG.with_(
        n_experts=8, capacity_factor=4.0, max_seq_len=1024, dtype="float32"
    )
    params = init_params(c, jax.random.PRNGKey(0))
    prompt = np.random.default_rng(3).integers(0, c.vocab_size, 600).tolist()

    def serve():
        traced = []
        monkeypatch.setattr(
            moe, "_routed_bank",
            lambda *a, _f=moe._routed_bank: traced.append(a[1].shape) or _f(*a),
        )
        engine = ServingEngine(
            c, params, slots=2, max_len=1024, kv_block_size=16,
            prefill_chunk_tokens=512,
        )
        try:
            out = engine.submit(prompt, max_new_tokens=5, temperature=0.0)
            tokens = []
            while (tok := out.get(timeout=300)) is not None:
                assert not isinstance(tok, BaseException), tok
                tokens.append(int(tok))
            return tokens, engine.stats(), traced
        finally:
            engine.close()
            monkeypatch.undo()

    tokens, stats, traced = serve()
    # the long chunk and the decode launches; not the short chunk
    assert set(traced) == {(1, 512, c.d_model), (2, 1, c.d_model)}
    layers, k, E = c.n_layers, c.experts_per_token, c.n_experts
    decode = stats["decode_steps_total"] // 4  # launches of 4 steps
    assert stats["moe_expert_launches_total"] == stats["prefill_chunks_total"] + decode == 2 + decode
    assert stats["moe_routed_launches_total"] == 1 + decode
    assert moe.plan(c, 2, 1) == (True, 2 * k + 4 * 32, 32)  # 3.5 experts of 8 touched
    assert stats["moe_computed_slots_total"] == layers * (
        (512 * k + E * 128)  # the routed chunk: its rows and a tile an expert
        + E * 128            # 88 tokens padded to 128, at capacity 128
        + stats["decode_steps_total"] * (2 * k + 4 * 32)  # rows, a tile an expert touched
    )
    assert stats["moe_routed_slots_total"] == layers * k * (
        600 + stats["decode_slot_steps_total"]
    )

    monkeypatch.setattr(moe, "plan", lambda c, rows, row_len, whole=True: (
        False, c.n_experts * rows * expert_capacity(c, row_len), 0))
    at_capacity, stats, traced = serve()
    assert not traced and stats["moe_routed_launches_total"] == 0
    assert stats["moe_expert_launches_total"] == 2 + stats["decode_steps_total"] // 4
    assert tokens == at_capacity
