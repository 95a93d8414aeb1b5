"""Podracer RL workload: actor/learner gangs on the serving engine.

Fast tier: the pure pieces — advantage math, the teacher-forced scorer,
the PPO step's direction, epoch-fenced weight refresh over all three
channels, trajectory framing, named-params validation, stats/metrics
rendering, gang-resize invariance, and the engine's idle-only
refresh_params contract.

Slow tier: the seeded Anakin learning smoke (exact determinism + a
smoothed-window improvement gate), the headless preemption drill as a
real subprocess, and a 2-device mesh learner step via
run_in_device_subprocess.
"""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_in_device_subprocess
from dstack_tpu.workloads.rl import (
    Actor,
    CheckpointWeightRefresh,
    InProcessWeightRefresh,
    Learner,
    RLStats,
    TargetTokenEnv,
    TrajectoryBatch,
    TrajectoryClient,
    TrajectorySink,
    WeightRefreshClient,
    WeightRefreshServer,
    compute_advantages,
    init_rl_state,
    make_rl_train_step,
    make_sequence_scorer,
    named_params,
    pack_trajectories,
    params_from_named,
    rl_prometheus_metrics,
    run_anakin,
    tiny_rl_config,
    unpack_trajectories,
)
from dstack_tpu.workloads.train import init_params
from dstack_tpu.workloads.transformer import forward

CFG = tiny_rl_config()


def _params(seed=0):
    return init_params(CFG, jax.random.PRNGKey(seed))


# ------------------------------------------------------------- environment


def test_env_prompts_deterministic_per_round():
    env = TargetTokenEnv(CFG.vocab_size, seed=3)
    a = env.prompts(4, round_ix=7)
    b = env.prompts(4, round_ix=7)
    c = env.prompts(4, round_ix=8)
    assert a == b
    assert a != c
    for row in a:
        assert all(1 <= t < CFG.vocab_size for t in row)


def test_env_rewards_target_token_only():
    env = TargetTokenEnv(64, target=7)
    acts = np.array([[7, 3, 7], [1, 1, 1]], np.int32)
    np.testing.assert_array_equal(
        env.token_rewards(acts), [[1.0, 0.0, 1.0], [0.0, 0.0, 0.0]]
    )


# --------------------------------------------------------------- advantages


def test_compute_advantages_discounted_return_to_go():
    rewards = np.array([[1.0, 0.0, 2.0]], np.float32)
    mask = np.ones_like(rewards)
    adv = compute_advantages(rewards, mask, gamma=0.5, normalize=False)
    # returns-to-go: [1 + 0.5*(0 + 0.5*2), 0.5*2, 2]
    np.testing.assert_allclose(adv, [[1.5, 1.0, 2.0]], rtol=1e-6)


def test_compute_advantages_normalized_masked():
    rng = np.random.default_rng(0)
    rewards = rng.random((4, 6)).astype(np.float32)
    mask = np.ones((4, 6), np.float32)
    mask[:, 4:] = 0.0  # padded tail must not contribute to the moments
    adv = compute_advantages(rewards, mask, gamma=0.9)
    live = adv[mask > 0]
    assert abs(live.mean()) < 1e-5
    assert abs(live.std() - 1.0) < 1e-4
    np.testing.assert_array_equal(adv[mask == 0], 0.0)


# ------------------------------------------------------------------- scorer


def test_sequence_scorer_matches_manual_log_softmax():
    params = _params()
    score = make_sequence_scorer(CFG)
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(1, CFG.vocab_size, (2, 9), np.int32))
    got = np.asarray(score(params, tokens, jnp.float32(0.7)))
    logits = forward(CFG, params, tokens[:, :-1]) / 0.7
    want = jax.nn.log_softmax(logits, axis=-1)
    want = jnp.take_along_axis(
        want, tokens[:, 1:][..., None], axis=-1
    )[..., 0]
    assert got.shape == (2, 8)
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-5, atol=1e-5)
    assert np.all(got <= 0.0)  # log-probabilities


# ---------------------------------------------------------------- PPO step


def _step_batch(params, tokens, h, advantage):
    score = make_sequence_scorer(CFG)
    p = tokens.shape[1] - h
    behavior = np.asarray(
        score(params, jnp.asarray(tokens), jnp.float32(1.0))
    )[:, p - 1:]
    return {
        "tokens": jnp.asarray(tokens),
        "behavior_logprob": jnp.asarray(behavior.astype(np.float32)),
        "advantage": jnp.asarray(advantage),
        "mask": jnp.ones((tokens.shape[0], h), jnp.float32),
        "temperature": jnp.float32(1.0),
    }


def test_rl_step_raises_logprob_of_advantaged_actions():
    """One PPO step with uniformly positive advantage must make the
    sampled actions more likely; negative advantage the reverse."""
    state = init_rl_state(CFG, jax.random.PRNGKey(0), learning_rate=5e-2)
    step = make_rl_train_step(CFG, learning_rate=5e-2)
    score = make_sequence_scorer(CFG)
    rng = np.random.default_rng(2)
    h = 6
    tokens = rng.integers(1, CFG.vocab_size, (4, 4 + h), np.int32)

    for sign in (+1.0, -1.0):
        batch = _step_batch(
            state.params, tokens, h,
            np.full((4, h), sign, np.float32),
        )
        new_state, metrics = step(
            jax.tree_util.tree_map(jnp.copy, state), batch
        )
        before = np.asarray(
            score(state.params, jnp.asarray(tokens), jnp.float32(1.0))
        )[:, 3:].sum()
        after = np.asarray(
            score(new_state.params, jnp.asarray(tokens), jnp.float32(1.0))
        )[:, 3:].sum()
        if sign > 0:
            assert after > before
        else:
            assert after < before
        for key in ("loss", "pg_loss", "entropy", "clip_fraction",
                    "grad_norm"):
            assert np.isfinite(float(metrics[key])), key


def test_rl_step_metrics_clip_fraction_zero_on_policy():
    """Behavior == current policy -> every ratio is exactly 1, nothing
    clips on the first step."""
    state = init_rl_state(CFG, jax.random.PRNGKey(1))
    step = make_rl_train_step(CFG)
    rng = np.random.default_rng(3)
    tokens = rng.integers(1, CFG.vocab_size, (2, 10), np.int32)
    batch = _step_batch(
        state.params, tokens, 6,
        rng.standard_normal((2, 6)).astype(np.float32),
    )
    _, metrics = step(state, batch)
    assert float(metrics["clip_fraction"]) == 0.0


# -------------------------------------------------------- named params


def test_named_params_roundtrip_and_validation():
    params = _params()
    named = named_params(params)
    assert len(named) > 4
    assert all(isinstance(n, str) and n for n, _ in named)
    by_name = dict(named)
    rebuilt = params_from_named(params, by_name)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(rebuilt)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    missing = dict(named)
    gone = next(iter(missing))
    del missing[gone]
    with pytest.raises(ValueError, match="missing"):
        params_from_named(params, missing)

    extra = dict(named)
    extra["bogus_leaf"] = np.zeros(3, np.float32)
    with pytest.raises(ValueError, match="unknown"):
        params_from_named(params, extra)

    bad_shape = dict(named)
    first = next(iter(bad_shape))
    bad_shape[first] = np.zeros((1, 1), np.float32)
    with pytest.raises(ValueError, match="shape"):
        params_from_named(params, bad_shape)


# ------------------------------------------------------- weight refresh


def _epoch_params(value: float):
    """A params tree whose every leaf is filled with `value` — makes a
    torn mix (leaves from different epochs) detectable by inspection."""
    return jax.tree_util.tree_map(
        lambda a: jnp.full(a.shape, value, a.dtype), _params()
    )


def _assert_epoch(by_name, value):
    for name, arr in by_name.items():
        np.testing.assert_array_equal(
            np.asarray(arr), np.full(arr.shape, value, arr.dtype),
            err_msg=f"leaf {name} not uniformly epoch {value} — torn mix",
        )


def test_socket_refresh_roundtrip_and_epoch_fencing():
    server = WeightRefreshServer()
    client = WeightRefreshClient("127.0.0.1", server.port)
    try:
        assert client.poll(0) is None  # nothing published yet
        e1 = server.publish(_epoch_params(1.0))
        assert e1 == 1
        epoch, by_name = client.poll(0)
        assert epoch == 1
        _assert_epoch(by_name, 1.0)
        assert client.poll(1) is None       # fenced: nothing newer
        assert client.poll(5) is None       # future stamp: still fenced
        e2 = server.publish(_epoch_params(2.0))
        epoch, by_name = client.poll(1)
        assert epoch == e2 == 2
        _assert_epoch(by_name, 2.0)         # never a mix of 1.0 and 2.0
        # The server counts a pull after it has sent it: the client can be
        # here first.
        deadline = time.monotonic() + 10
        while server.pulls_served < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert server.pulls_served >= 2
    finally:
        client.close()
        server.close()


def test_socket_refresh_client_reconnects_after_drop():
    server = WeightRefreshServer()
    client = WeightRefreshClient("127.0.0.1", server.port)
    try:
        server.publish(_epoch_params(1.0))
        assert client.poll(0)[0] == 1
        client._sock.close()  # sever under the client
        time.sleep(0.05)
        server.publish(_epoch_params(2.0))
        assert client.poll(1)[0] == 2  # redialed transparently
    finally:
        client.close()
        server.close()


def test_checkpoint_refresh_roundtrip(tmp_path):
    refr = CheckpointWeightRefresh(str(tmp_path))
    assert refr.poll(0) is None  # empty dir
    assert refr.publish(_epoch_params(1.0)) == 1
    epoch, by_name = refr.poll(0)
    assert epoch == 1
    _assert_epoch(by_name, 1.0)
    assert refr.poll(1) is None
    assert refr.publish(_epoch_params(2.0)) == 2
    epoch, by_name = refr.poll(1)
    assert epoch == 2
    _assert_epoch(by_name, 2.0)
    # No stray tmp files left behind by the atomic replace.
    assert not [p for p in os.listdir(tmp_path) if "tmp" in p]


def test_inprocess_refresh_fences_like_the_others():
    refr = InProcessWeightRefresh()
    assert refr.poll(0) is None
    refr.publish(_epoch_params(1.0))
    epoch, by_name = refr.poll(0)
    assert epoch == 1
    _assert_epoch(by_name, 1.0)
    assert refr.poll(1) is None


# -------------------------------------------------- trajectory transport


def _traj(actor_id=0, epoch=3, b=2, p=4, h=5, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(1, 64, (b, p + h)).astype(np.int32)
    return TrajectoryBatch(
        tokens=tokens,
        actions=tokens[:, p:].copy(),
        behavior_logprob=rng.standard_normal((b, h)).astype(np.float32),
        rewards=rng.random((b, h)).astype(np.float32),
        mask=np.ones((b, h), np.float32),
        prompt_len=p, actor_id=actor_id, weight_epoch=epoch,
    )


def test_trajectory_pack_unpack_roundtrip():
    t = _traj()
    header, payloads = pack_trajectories(t)
    by_name = dict(zip([s["name"] for s in header["arrays"]], payloads))
    header["_arrays"] = [by_name[s["name"]] for s in header["arrays"]]
    got = unpack_trajectories(header)
    assert got.actor_id == t.actor_id
    assert got.weight_epoch == t.weight_epoch
    assert got.prompt_len == t.prompt_len
    for field in ("tokens", "actions", "behavior_logprob", "rewards",
                  "mask"):
        np.testing.assert_array_equal(getattr(got, field),
                                      getattr(t, field))
    assert got.env_steps == t.env_steps


def test_trajectory_sink_delivery_over_loopback():
    received = []
    sink = TrajectorySink(on_batch=received.append)
    client = TrajectoryClient("127.0.0.1", sink.port)
    try:
        client.send(_traj(actor_id=1, epoch=2, seed=1))
        client.send(_traj(actor_id=1, epoch=3, seed=2))
        assert [t.weight_epoch for t in received] == [2, 3]
        np.testing.assert_array_equal(
            received[0].tokens, _traj(actor_id=1, epoch=2, seed=1).tokens
        )
    finally:
        client.close()
        sink.close()


# --------------------------------------------------------- stats/metrics


def test_rl_stats_actor_epoch_monotone_and_staleness():
    stats = RLStats()
    stats.note_actor_epoch(0, 3)
    stats.note_actor_epoch(0, 2)  # out-of-order stamp must not regress
    stats.note_actor_epoch(1, 5)
    stats.observe_staleness(0, 2)
    snap = stats.snapshot()
    assert snap["actor_epochs"] == {0: 3, 1: 5}
    assert snap["staleness_epochs"] == {0: 2}


def test_rl_prometheus_rendering():
    stats = RLStats()
    stats.count_rollout(env_steps=32, episodes=4, seconds=0.5,
                        reward_mean=0.25)
    stats.count_learn_step(0.1)
    stats.count_publish(1)
    stats.count_adoption(0, 1, 0.01)
    stats.count_adoption(7, 1, 0.02)
    stats.note_actor_epoch(7, 1)
    stats.observe_staleness(7, 3)
    stats.count_gang_resize()
    text = rl_prometheus_metrics(stats.snapshot())
    assert "dstack_tpu_rl_env_steps_total 32" in text
    assert "dstack_tpu_rl_episodes_total 4" in text
    assert "dstack_tpu_rl_learn_steps_total 1" in text
    assert "dstack_tpu_rl_gang_resizes_total 1" in text
    assert 'dstack_tpu_rl_weight_refreshes_total{role="learner"} 1' in text
    assert 'dstack_tpu_rl_weight_refreshes_total{role="actor"} 2' in text
    assert 'dstack_tpu_rl_weight_epoch{role="learner"} 1' in text
    # Actor-side epoch is the MINIMUM across actors (the laggard).
    assert 'dstack_tpu_rl_weight_epoch{role="actor"} 1' in text
    assert 'dstack_tpu_rl_refresh_staleness_epochs{actor="7"} 3' in text
    assert 'dstack_tpu_rl_learn_step_seconds_count 1' in text
    assert 'dstack_tpu_rl_refresh_seconds_count 2' in text
    assert 'dstack_tpu_rl_rollout_seconds_sum 0.5' in text


def test_rl_metric_series_all_registered():
    """Every series the renderer emits must be declared in the registry
    (MET01 enforces the reverse direction statically)."""
    from dstack_tpu.server.metrics_registry import METRICS

    stats = RLStats()
    stats.count_adoption(0, 1, 0.01)
    stats.observe_staleness(0, 1)
    text = rl_prometheus_metrics(stats.snapshot())
    declared = set(METRICS)
    for line in text.splitlines():
        if line.startswith("#") or not line:
            continue
        name = line.split("{")[0].split(" ")[0]
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and name[: -len(suffix)] in declared:
                name = name[: -len(suffix)]
                break
        assert name in declared, f"unregistered series {name}"


# ------------------------------------------------------------ gang resize


def test_learner_rescale_gang_preserves_batches_per_update():
    learner = Learner(CFG, accum_per_actor=1, gang_width=2)
    assert learner.batches_per_update == 2
    learner.rescale_gang(1)  # preemption: 2 actors -> 1
    assert learner.accum_per_actor == 2
    assert learner.batches_per_update == 2  # invariant
    learner.rescale_gang(2)  # re-admit
    assert learner.accum_per_actor == 1
    assert learner.batches_per_update == 2
    assert learner.stats.gang_resizes_total == 2


def test_learner_rescale_gang_rejects_indivisible_width():
    learner = Learner(CFG, accum_per_actor=1, gang_width=2)
    with pytest.raises(ValueError, match="divide"):
        learner.rescale_gang(4)  # 2 batches over 4 actors: 0.5 each
    assert learner.gang_width == 2  # unchanged on failure


def test_learner_gather_timeout_is_loud():
    learner = Learner(CFG, accum_per_actor=1, gang_width=2)
    learner.ingest(_traj())
    with pytest.raises(TimeoutError, match="1/2"):
        learner.gather(timeout=0.3)


# ------------------------------------------- engine refresh_params seam


def test_engine_refresh_params_swaps_idle_engine():
    from dstack_tpu.workloads.serving import ServingEngine

    engine = ServingEngine(CFG, _epoch_params(1.0), slots=2, max_len=32)
    try:
        engine.refresh_params(_epoch_params(2.0))
        leaf = jax.tree_util.tree_leaves(engine.params)[0]
        np.testing.assert_array_equal(
            np.asarray(leaf), np.full(leaf.shape, 2.0, leaf.dtype)
        )
    finally:
        engine.close()


def test_engine_refresh_params_rejects_mismatched_tree():
    from dstack_tpu.workloads.serving import ServingEngine

    engine = ServingEngine(CFG, _params(), slots=2, max_len=32)
    try:
        wrong = init_params(
            tiny_rl_config(d_model=32, n_heads=2), jax.random.PRNGKey(0)
        )
        with pytest.raises(ValueError, match="match"):
            engine.refresh_params(wrong)
    finally:
        engine.close()


def test_engine_refresh_params_refuses_while_busy():
    from dstack_tpu.workloads.serving import ServingEngine

    engine = ServingEngine(CFG, _params(), slots=2, max_len=32)
    try:
        engine._next_req = object()  # simulate an in-flight admission
        with pytest.raises(RuntimeError, match="idle"):
            engine.refresh_params(_params())
    finally:
        engine._next_req = None
        engine.close()


# ------------------------------------------------------ slow integration


@pytest.mark.slow
def test_anakin_seeded_learning_smoke():
    """Fixed seed: the reward/loss trajectory is exactly reproducible,
    and the smoothed reward improves over the run."""
    kwargs = dict(updates=8, batch_size=8, horizon=8, seed=0,
                  learning_rate=2e-2, refresh="direct")
    a = run_anakin(tiny_rl_config(), **kwargs)
    b = run_anakin(tiny_rl_config(), **kwargs)
    assert a["rewards"] == b["rewards"], "trajectory not deterministic"
    assert a["losses"] == b["losses"]
    head = sum(a["rewards"][:3]) / 3
    tail = sum(a["rewards"][-3:]) / 3
    assert tail > head, (a["rewards"], "no smoothed-window improvement")
    assert tail > 0.3, a["rewards"]
    assert a["env_steps_total"] == 8 * 8 * 8
    # The actor adopts at the TOP of each round, so it finishes exactly
    # one epoch behind the learner's final publish — deterministically.
    assert a["learner_epoch"] == 8
    assert a["final_weight_epoch"] == 7


_ANAKIN = dict(updates=3, batch_size=8, horizon=8, seed=0,
               learning_rate=2e-2, publish_every=1)


@pytest.fixture(scope="module")
def anakin_direct():
    return run_anakin(tiny_rl_config(), refresh="direct", **_ANAKIN)


@pytest.mark.parametrize("channel", ["socket", "checkpoint"])
def test_anakin_trajectory_is_the_same_over_every_refresh_channel(
    channel, anakin_direct, tmp_path
):
    """The refresh channel must be invisible to the math: same seed,
    same rewards and losses as the in-process swap, update for update
    (a divergence means torn weights or a stale adoption)."""
    extra = {"checkpoint_dir": str(tmp_path)} if channel == "checkpoint" else {}
    out = run_anakin(tiny_rl_config(), refresh=channel, **_ANAKIN, **extra)
    assert any(out["rewards"]), out["rewards"]  # something was learned from
    assert out["rewards"] == anakin_direct["rewards"]
    assert out["losses"] == anakin_direct["losses"]
    assert out["final_weight_epoch"] == anakin_direct["final_weight_epoch"]


@pytest.mark.slow
def test_rl_drill_subprocess_smoke():
    """The full preemption drill as shipped (`make drill-rl`), one
    update per phase to keep it inside the slow-tier budget."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "dstack_tpu.workloads.rl_drill",
         "--updates-per-phase", "1", "--timeout", "300"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=360,
    )
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    summary = json.loads(out.stdout[out.stdout.index("{"):])
    assert summary["ok"] is True
    assert summary["learner_restarts"] == 0
    assert summary["gang_resizes"] == 2
    assert summary["preemptions"] == 1
    survivors = {
        k: v for k, v in summary["actor_final_epochs"].items()
        if v == summary["final_weight_epoch"]
    }
    assert len(survivors) >= 2


@pytest.mark.slow
def test_mesh_learner_two_devices():
    """The learner's jitted PPO step under a 2-way data mesh: shapes
    shard over `data`, loss finite, params update."""
    src = """
import json
import jax, jax.numpy as jnp, numpy as np
from dstack_tpu.workloads.rl import (
    init_rl_state, make_rl_train_step, make_sequence_scorer,
    tiny_rl_config,
)
from dstack_tpu.workloads.sharding import make_mesh

config = tiny_rl_config()
devices = jax.devices()
mesh = make_mesh(devices, data=len(devices))
state = init_rl_state(config, jax.random.PRNGKey(0), mesh=mesh)
step = make_rl_train_step(config, mesh=mesh)
score = make_sequence_scorer(config)
rng = np.random.default_rng(0)
h = 6
tokens = rng.integers(1, config.vocab_size, (4, 4 + h)).astype(np.int32)
behavior = np.asarray(score(state.params, jnp.asarray(tokens),
                            jnp.float32(1.0)))[:, 3:]
batch = {
    "tokens": jnp.asarray(tokens),
    "behavior_logprob": jnp.asarray(behavior.astype(np.float32)),
    "advantage": jnp.asarray(rng.standard_normal((4, h)).astype(np.float32)),
    "mask": jnp.ones((4, h), jnp.float32),
    "temperature": jnp.float32(1.0),
}
before = np.asarray(jax.tree_util.tree_leaves(state.params)[0]).copy()
state2, metrics = step(state, batch)
after = np.asarray(jax.tree_util.tree_leaves(state2.params)[0])
print(json.dumps({
    "devices": len(devices),
    "loss": float(metrics["loss"]),
    "finite": bool(np.isfinite(float(metrics["loss"]))),
    "changed": bool((before != after).any()),
    "step": int(state2.step),
}))
"""
    out = run_in_device_subprocess(src, device_count=2)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["devices"] == 2
    assert got["finite"] and got["changed"]
    assert got["step"] == 1
