"""gridmaze84 against its plain reference (``bench/reference/env_gridmaze84``),
on the CPU: the host env (vmapped), its device port and the reference
give the same observation, reward and done streams bit for bit through
auto-resets, on gridmaze's rules; the reference rebuilds its state from
an observation."""
import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import envs as ref_envs
from repro.envs import get_env
from repro.envs.device import batched_env
from repro.envs.interfaces import vectorize

N_ENVS, STEPS = 6, 90
# env 0 walks to the goal and back again: down the open first column,
# then along the bottom row (16 moves), so its stream holds +1 rewards
TO_GOAL = [1] * 8 + [3] * 8


def _actions(seed):
    rng = np.random.default_rng(seed)
    acts = rng.integers(0, 4, (STEPS, N_ENVS)).astype(np.int32)
    acts[:, 0] = [TO_GOAL[t % len(TO_GOAL)] for t in range(STEPS)]
    return acts


def _streams(env, actions):
    """obs, reward, done and state per step from the reset, under the
    keys the executors would draw."""
    keys0 = jax.random.split(jax.random.key(5), N_ENVS)
    state, obs = jax.jit(env.reset)(keys0)
    step = jax.jit(env.step)
    out = {"obs": [obs], "reward": [], "done": [], "state": [state]}
    for t, a in enumerate(actions):
        keys = jax.random.split(jax.random.key(t), N_ENVS)
        state, obs, r, d = step(state, jnp.asarray(a), keys)
        for k, v in (("obs", obs), ("reward", r), ("done", d),
                     ("state", state)):
            out[k].append(v)
    return out


def test_host_device_and_reference_agree_bit_for_bit():
    actions = _actions(0)
    host84 = get_env("gridmaze84")
    runs = {"host": _streams(vectorize(host84, N_ENVS), actions),
            "device": _streams(batched_env(host84, N_ENVS, "device"),
                               actions),
            "reference": _streams(ref_envs.make("gridmaze84"), actions)}
    grid = _streams(vectorize(get_env("gridmaze"), N_ENVS), actions)
    want = runs["reference"]
    assert want["obs"][0].dtype == jnp.uint8
    assert want["obs"][0].shape == (N_ENVS, 84, 84, 4)
    rewards = np.stack(want["reward"])
    assert (rewards == 1.0).sum() >= 2 and np.stack(want["done"]).sum() >= 8
    for name, got in runs.items():
        for k in ("obs", "reward", "done"):
            for t, (g, w) in enumerate(zip(got[k], want[k])):
                np.testing.assert_array_equal(
                    np.asarray(g), np.asarray(w), err_msg=f"{name} {k} {t}")
        # the rules are gridmaze's: the same (r, c, t) stream
        for t, (g, w) in enumerate(zip(got["state"], grid["state"])):
            for k in ("r", "c", "t"):
                np.testing.assert_array_equal(
                    np.asarray(g[k]), np.asarray(w[k]),
                    err_msg=f"{name} {k} {t}")
    np.testing.assert_array_equal(np.stack(grid["reward"]), rewards)


def test_state_from_obs_round_trips():
    env = ref_envs.make("gridmaze84")
    run = _streams(env, _actions(1))
    for obs, state in zip(run["obs"], run["state"]):
        back = jax.jit(env.state_from_obs)(obs, state["t"])
        assert sorted(back) == sorted(state)
        for k in state:
            np.testing.assert_array_equal(np.asarray(back[k]),
                                          np.asarray(state[k]), err_msg=k)
        nxt = env.step(back, jnp.zeros((N_ENVS,), jnp.int32),
                       jax.random.split(jax.random.key(0), N_ENVS))
        ref = env.step(state, jnp.zeros((N_ENVS,), jnp.int32),
                       jax.random.split(jax.random.key(0), N_ENVS))
        np.testing.assert_array_equal(np.asarray(nxt[1]), np.asarray(ref[1]))


def test_the_reader_knows_the_render_and_tree_scopes():
    """The frames' ``render`` scope and the tree sum's paths are among the
    program's ``SCOPES``, so the reader reads each as its own chain, inside
    the chain that its ``interval.*`` metric reads."""
    from bench import spans
    from repro.core import spans as program
    for inner in (program.TREE_HALVES, program.TREE_STRIDED):
        assert spans.scope_chain(
            f"jit(body)/while/body/hts.learner/grad_reduce/{inner}/add:"
        ) == f"hts.learner/grad_reduce/{inner}"
    assert spans.scope_chain(
        "jit(body)/while/body/closed_call/hts.rollout/env_step/render/"
        "select_n:") == "hts.rollout/env_step/render"
