"""A configuration is added as new files: its env's and its policy's
references are found by file name under the bench dir, the env gets the
configuration's kwargs, and a cell whose env (the repo's ``catch``) and
policy (``mlp``) have references only in a temporary bench dir is built
and checked through the harness, at a small size on the CPU."""
import hashlib
import json
import shutil
import time

import jax
import pytest

from bench import flops, harness, run as bench_run
from bench.reference import envs as ref_envs
from bench.reference import nets

ROOT = harness.ROOT

TOY_ENV = '''
import jax.numpy as jnp
from bench.reference.envs import RefEnv, autoreset


def make(size=3, reward=1.0):
    def reset(keys):
        n = keys.shape[0]
        return {"t": jnp.zeros((n,), jnp.int32)}, jnp.zeros((n, size, size, 1))

    def inner(state, actions, keys):
        n = actions.shape[0]
        return (state, jnp.zeros((n, size, size, 1)),
                jnp.full((n,), reward), jnp.zeros((n,)))

    return RefEnv(reset, autoreset(reset, inner),
                  lambda obs, t: {"t": t}, (size, size, 1), 2)
'''

# catch (repro.envs.catch) written plainly: a ball falls from a random
# column of a 10 x 5 board, the paddle on the bottom row moves
# left/stay/right; +1 on a catch, -1 on a miss, 9 steps an episode
CATCH_ENV = '''
import jax
import jax.numpy as jnp
from bench.reference.envs import RefEnv, autoreset

ROWS, COLS = 10, 5


def make():
    def obs(state):
        rows = jnp.arange(ROWS)[None, :, None]
        cols = jnp.arange(COLS)[None, None, :]
        ball = ((rows == state["ball_r"][:, None, None])
                & (cols == state["ball_c"][:, None, None]))
        paddle = (rows == ROWS - 1) & (cols == state["paddle"][:, None, None])
        return (ball | paddle).astype(jnp.float32)[..., None]

    def reset(keys):
        n = keys.shape[0]
        col = jax.vmap(lambda k: jax.random.randint(k, (), 0, COLS))(keys)
        state = {"ball_r": jnp.zeros((n,), jnp.int32), "ball_c": col,
                 "paddle": jnp.full((n,), COLS // 2, jnp.int32)}
        return state, obs(state)

    def inner(state, actions, keys):
        paddle = jnp.clip(state["paddle"] + actions - 1, 0, COLS - 1)
        ball_r = state["ball_r"] + 1
        done = ball_r >= ROWS - 1
        reward = jnp.where(done, jnp.where(paddle == state["ball_c"],
                                           1.0, -1.0), 0.0)
        ns = {"ball_r": ball_r, "ball_c": state["ball_c"], "paddle": paddle}
        return ns, obs(ns), reward, done.astype(jnp.float32)

    def state_from_obs(o, t):
        n = o.shape[0]
        ball = jnp.argmax(o[:, :ROWS - 1, :, 0].reshape(n, -1), axis=-1)
        return {"ball_r": (ball // COLS).astype(jnp.int32),
                "ball_c": (ball % COLS).astype(jnp.int32),
                "paddle": jnp.argmax(o[:, ROWS - 1, :, 0], axis=-1
                                     ).astype(jnp.int32)}

    return RefEnv(reset, autoreset(reset, inner), state_from_obs,
                  (ROWS, COLS, 1), 3)
'''

# the two-layer tanh MLP policy (repro.models.cnn_policy) written plainly
MLP_NET = '''
import math
import jax
import jax.numpy as jnp
from bench.reference import nets


def make(policy, obs_shape, n_actions):
    hidden = policy["arch"]["hidden"]
    d = math.prod(obs_shape)

    def init(key):
        ks = jax.random.split(key, 4)
        return {"w1": jax.random.normal(ks[0], (d, hidden)) * math.sqrt(2 / d),
                "b1": jnp.zeros((hidden,)),
                "w2": (jax.random.normal(ks[1], (hidden, hidden))
                       * math.sqrt(2 / hidden)),
                "b2": jnp.zeros((hidden,)),
                "pi_w": jax.random.normal(ks[2], (hidden, n_actions)) * 0.01,
                "pi_b": jnp.zeros((n_actions,)),
                "v_w": jax.random.normal(ks[3], (hidden, 1)),
                "v_b": jnp.zeros((1,))}

    def apply(params, obs, mode="highest"):
        dtype = nets.arith(mode)
        p = jax.tree.map(lambda a: a.astype(dtype), params)
        x = obs.astype(dtype).reshape(obs.shape[0], -1)
        x = jnp.tanh(nets.dot(x, p["w1"], mode) + p["b1"])
        x = jnp.tanh(nets.dot(x, p["w2"], mode) + p["b2"])
        logits = nets.dot(x, p["pi_w"], mode) + p["pi_b"]
        return logits, (nets.dot(x, p["v_w"], mode) + p["v_b"])[:, 0]

    return init, apply


def layers(obs_shape, arch, n_actions):
    d, h = math.prod(obs_shape), arch["hidden"]
    return [("fc1", 2.0 * d * h), ("fc2", 2.0 * h * h),
            ("heads", 2.0 * h * (n_actions + 1))]
'''


def _write(bench_dir, files):
    for rel, text in files.items():
        path = bench_dir / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(text)


def test_reference_env_and_net_are_found_by_file(tmp_path):
    _write(tmp_path, {"reference/env_toy.py": TOY_ENV,
                      "reference/net_toymlp.py": MLP_NET})
    env = ref_envs.make("toy", {"size": 5, "reward": 2.0}, tmp_path)
    assert env.obs_shape == (5, 5, 1) and env.n_actions == 2
    keys = jax.random.split(jax.random.key(0), 3)
    state, obs = env.reset(keys)
    _, _, reward, _ = env.step(state, jax.numpy.zeros((3,), int), keys)
    assert obs.shape == (3, 5, 5, 1) and reward.tolist() == [2.0] * 3
    policy = {"name": "toymlp", "arch": {"hidden": 8}}
    init, apply = nets.make(policy, env.obs_shape, env.n_actions, tmp_path)
    logits, value = apply(init(jax.random.key(1)), obs)
    assert logits.shape == (3, 2) and value.shape == (3,)
    fwd = 2.0 * (25 * 8 + 8 * 8 + 8 * 3)
    assert flops.forward(policy, (5, 5, 1), 2, tmp_path) == fwd
    assert flops.train_step(policy, (5, 5, 1), 2, 5, tmp_path) == (
        fwd + fwd + 2 * fwd - 2.0 * 25 * 8 + fwd / 5)


@pytest.mark.parametrize("find,known", [
    (lambda name: ref_envs.make(name), "gridmaze"),
    (lambda name: nets.make({"name": name, "arch": {}}, (9, 9, 3), 4),
     "cnn"),
    (lambda name: flops.forward({"name": name, "arch": {}}, (9, 9, 3), 4),
     "cnn"),
])
def test_an_unknown_name_lists_the_names_found(find, known):
    with pytest.raises(LookupError) as err:
        find("no-such-name")
    assert "no-such-name" in str(err.value)
    assert repr(known) in str(err.value)


def _digest(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).digest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_a_configuration_is_added_as_new_files_only(tmp_path):
    """Config, traffic, limits and the env's and policy's references are
    new files in a copy of the benchmark, and the cell's name is added
    to the rate's ``workloads`` in ``BENCHMARK.json``; the copy's files
    stay as they were, and the cell they make runs and checks correct.
    The check compares the env that was added: with its reference's
    reward flipped, the same cell reads transitions that break the
    env's rules, and is not correct."""
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digest(bench)
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in doc["end_to_end"]:
        if m["name"] == "env_steps_per_s":
            m["workloads"].append("mlp-catch.fit-n8")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    base = json.loads((ROOT / "bench" / "configs"
                       / "paper-cnn-gridmaze.json").read_text())
    spec = dict(base["spec"], env={"name": "catch", "kwargs": {}},
                policy={"name": "mlp", "kwargs": {"hidden": 16}})
    limits = json.loads((ROOT / "bench" / "limits"
                         / "cnn-gridmaze.host-n64.json").read_text())
    _write(bench, {
        "configs/mlp-catch.json": json.dumps(
            {"spec": spec, "matmul_precision": "highest",
             "control": "one_pass"}),
        "traffic/fit-sharded-n8.json": json.dumps(
            {"kind": "fit", "ckpt_every": 2, "intervals_per_s": 2,
             "spec": {"runtime": {"name": "sharded", "kwargs": {}},
                      "hts": {"n_envs": 8, "env_backend": "device"},
                      "batch": {"n_replicas": 1}}}),
        "limits/mlp-catch.fit-n8.json": json.dumps(
            {"limits": limits["limits"]}),
        "reference/env_catch.py": CATCH_ENV,
        "reference/net_mlp.py": MLP_NET,
    })
    after = _digest(bench)
    assert len(set(after) - set(before)) == 5
    assert {k: v for k, v in after.items() if k in before} == before
    cell = harness.Benchmark(tmp_path).make_cell(
        "mlp-catch.fit-n8", "mlp-catch", "fit-sharded-n8", 1)
    assert cell.bench_dir == bench
    out = bench_run.run_cell(cell, 2**31 + 23, 1.0, False,
                             jax.devices()[:1], time.time(), root=tmp_path)
    assert out["correct"], out["checks"]
    assert set(out["checks"]) == set(limits["limits"])
    assert set(out["metrics"]) == {"env_steps_per_s", "setup_s"}
    assert out["metrics"]["env_steps_per_s"]["value"] > 0
    flipped = CATCH_ENV.replace("1.0, -1.0", "-1.0, 1.0")
    assert flipped != CATCH_ENV
    (bench / "reference" / "env_catch.py").write_text(flipped)
    out = bench_run.run_cell(cell, 2**31 + 23, 1.0, False,
                             jax.devices()[:1], time.time(), root=tmp_path)
    assert not out["correct"]
    assert out["checks"]["env_mismatch"]["value"] > 0
