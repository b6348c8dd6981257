"""Device-resident env ports (repro.envs.device): registry wiring, the
host-oracle bit-exactness contract, and the env_backend selection axis.

The contract (DESIGN.md §2.2): for every env with a device port, the
natively-batched ``reset``/``step`` produce bit-identical obs, rewards,
dones, AND state pytrees to ``vectorize(host_env, n)`` under the same
PRNG keys — the host path stays the oracle, the device path is pure
speed. Training on either backend is therefore the same trajectory,
which the runtime-level cells below pin for (a2c|ppo) x K in {1,2} on
both ported envs (the acceptance matrix), plus a cross-backend
checkpoint resume.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import api, models
from repro.core import engine
from repro.core.engine import HTSConfig
from repro.envs import get_env
from repro.envs import device as device_envs
from repro.envs.device import DeviceEnv, batched_env
from repro.envs.interfaces import vectorize
from repro.optim import rmsprop

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:        # container skips; CI installs hypothesis
    HAVE_HYPOTHESIS = False

PORTED = ["catch", "gridmaze", "gridmaze84"]


# ------------------------------------------------------------- registry
def test_ported_envs_are_registered():
    assert sorted(device_envs.device_port_names()) == PORTED
    for name in PORTED:
        assert device_envs.has_device_port(name)
    assert not device_envs.has_device_port("football")
    assert not device_envs.has_device_port("token_stream")


def test_get_device_env_unported_raises():
    with pytest.raises(ValueError, match="no device-resident port"):
        device_envs.get_device_env("football")


def test_get_env_exposes_device_ports():
    for name in PORTED:
        port = get_env(f"{name}_device")
        assert isinstance(port, DeviceEnv)
        assert port.host_name == name
        host = get_env(name)
        assert port.obs_shape == host.obs_shape
        assert port.n_actions == host.n_actions


def test_batched_env_backend_selection():
    env = get_env("catch")
    host = batched_env(env, 4, "host")
    dev = batched_env(env, 4, "device")
    assert isinstance(dev, DeviceEnv)
    assert not isinstance(host, DeviceEnv)
    with pytest.raises(ValueError, match="unknown env_backend"):
        batched_env(env, 4, "tpu")


def test_device_reset_leaves_are_distinct_buffers():
    """The engine donates carries; XLA refuses one buffer donated under
    two leaves, so constant-valued state fields (gridmaze's r/c/t zeros)
    must not share the eager constant cache."""
    for name in PORTED:
        venv = batched_env(get_env(name), 6, "device")
        keys = jax.random.split(jax.random.key(3), 6)
        state, obs = venv.reset(keys)
        ptrs = [leaf.unsafe_buffer_pointer()
                for leaf in jax.tree.leaves((state, obs))]
        assert len(ptrs) == len(set(ptrs)), name


# ------------------------------------------------- env-level bit-exactness
def _compare_rollout(name, n_envs, seed, steps=40):
    """Step the vmapped host env and the device port in lockstep under
    identical keys; everything must agree bit-exactly, crossing
    autoreset boundaries."""
    env = get_env(name)
    hv = vectorize(env, n_envs)
    dv = batched_env(env, n_envs, "device")
    master = jax.random.key(seed)
    keys0 = jax.random.split(jax.random.fold_in(master, 0), n_envs)
    hs, ho = hv.reset(keys0)
    ds, do = dv.reset(keys0)
    np.testing.assert_array_equal(np.asarray(ho), np.asarray(do))
    for t in range(steps):
        k = jax.random.fold_in(master, t + 1)
        actions = jax.random.randint(k, (n_envs,), 0, env.n_actions)
        keys = jax.random.split(k, n_envs)
        hs, ho, hr, hd = hv.step(hs, actions, keys)
        ds, do, dr, dd = dv.step(ds, actions, keys)
        np.testing.assert_array_equal(np.asarray(ho), np.asarray(do))
        np.testing.assert_array_equal(np.asarray(hr), np.asarray(dr))
        np.testing.assert_array_equal(np.asarray(hd), np.asarray(dd))
        for hx, dx in zip(jax.tree.leaves(hs), jax.tree.leaves(ds)):
            np.testing.assert_array_equal(np.asarray(hx), np.asarray(dx))


@pytest.mark.parametrize("name", PORTED)
def test_device_port_matches_host_oracle(name):
    _compare_rollout(name, n_envs=5, seed=0)


if HAVE_HYPOTHESIS:
    @settings(deadline=None, max_examples=8)
    @given(name=st.sampled_from(PORTED),
           n_envs=st.integers(min_value=1, max_value=9),
           seed=st.integers(min_value=0, max_value=2**31 - 1))
    def test_fuzz_device_port_matches_host_oracle(name, n_envs, seed):
        """Property form of the oracle contract: any seed, any batch
        width — the device port never drifts from the host env."""
        _compare_rollout(name, n_envs=n_envs, seed=seed, steps=25)
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_fuzz_device_port_matches_host_oracle():
        pass


# --------------------------------------------- runtime-level bit-exactness
def _run(env_name, backend, algorithm="a2c", staleness=1, runtime="mesh",
         alpha=4, n_envs=4, intervals=4):
    env = get_env(env_name)
    cfg = HTSConfig(alpha=alpha, n_envs=n_envs, seed=3,
                    algorithm=algorithm, staleness=staleness,
                    env_backend=backend)
    policy = models.get_policy("mlp", env)
    params = policy.init(jax.random.key(0))
    opt = rmsprop(7e-4, eps=1e-5)
    rt = engine.make_runtime(runtime, env, policy.apply, params, opt, cfg)
    return rt.run(intervals)


def _assert_same(a, b):
    md = max(float(jnp.max(jnp.abs(x - y)))
             for x, y in zip(jax.tree.leaves(a.params),
                             jax.tree.leaves(b.params)))
    assert md == 0.0
    np.testing.assert_array_equal(np.asarray(a.rewards),
                                  np.asarray(b.rewards))
    np.testing.assert_array_equal(np.asarray(a.dones),
                                  np.asarray(b.dones))


@pytest.mark.parametrize("staleness", [1, 2], ids=lambda k: f"K{k}")
@pytest.mark.parametrize("algorithm", ["a2c", "ppo"])
@pytest.mark.parametrize("env_name", PORTED)
def test_mesh_backends_bit_exact(env_name, algorithm, staleness):
    """The acceptance matrix: host and device trajectories identical for
    (a2c|ppo) x K in {1,2} on both ported envs under the fused runtime."""
    _assert_same(_run(env_name, "host", algorithm, staleness),
                 _run(env_name, "device", algorithm, staleness))


def test_host_runtime_backends_bit_exact():
    """The threaded host runtime accepts the device port as a drop-in
    for its batched reset/step programs — same dispatch cadence, same
    trajectory."""
    _assert_same(_run("catch", "host", runtime="host"),
                 _run("catch", "device", runtime="host"))


def test_capsule_resumes_across_backends(tmp_path):
    """TrainState is backend-agnostic: a host-backend checkpoint resumed
    under the device backend (and vice versa) continues the exact
    straight-run trajectory — the stacked state pytrees are the same
    structure either way."""
    from repro.checkpoint import io as ckpt_io
    env = get_env("catch")
    policy = models.get_policy("mlp", env)
    params = policy.init(jax.random.key(0))
    opt = rmsprop(7e-4, eps=1e-5)
    cfg = HTSConfig(alpha=4, n_envs=4, seed=3)
    mk = lambda be: engine.make_runtime(
        "mesh", env, policy.apply, params, opt,
        cfg._replace(env_backend=be))
    straight = mk("host").run(4)
    for src, dst in [("host", "device"), ("device", "host")]:
        a = mk(src)
        a.run(2)
        path = str(tmp_path / f"xfer_{src}")
        ckpt_io.save(path, a.state())
        b = mk(dst)
        out = b.run_from(ckpt_io.restore(path, b.state()), 2)
        md = max(float(jnp.max(jnp.abs(x - y)))
                 for x, y in zip(jax.tree.leaves(straight.params),
                                 jax.tree.leaves(out.params)))
        assert md == 0.0, (src, dst)


# --------------------------------------------------------- spec surface
def test_spec_rejects_unknown_backend():
    with pytest.raises(ValueError, match="unknown env_backend"):
        api.ExperimentSpec(hts={"env_backend": "tpu"})


def test_spec_rejects_device_backend_without_port():
    """Spec construction time, not trace time: the error names the envs
    that DO have ports."""
    with pytest.raises(ValueError) as e:
        api.ExperimentSpec(env="football",
                           hts={"env_backend": "device"})
    assert "no device-resident port" in str(e.value)
    for name in PORTED:
        assert name in str(e.value)


def test_build_rejects_device_port_as_workload():
    """Naming "catch_device" as the spec env is a category error — the
    message points at the hts knob instead of a shape failure later."""
    with pytest.raises(ValueError, match="env_backend"):
        api.build(api.ExperimentSpec(env="catch_device"))


def test_spec_device_backend_builds_and_runs():
    spec = api.ExperimentSpec(
        env="gridmaze", runtime="mesh",
        hts={"alpha": 4, "n_envs": 4, "seed": 0,
             "env_backend": "device"},
        intervals=2)
    out = api.build(spec).run()
    assert out.steps == 2 * 4 * 4
    # the knob round-trips through canonical JSON like any other
    assert api.loads(api.dumps(spec)) == spec


def test_host_default_fingerprint_unchanged():
    """Leaving env_backend unset must serialize identically to the
    pre-backend-axis spec form — committed BENCH_sps.json baselines stay
    comparable."""
    import sys, os
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.engine_sps import bench_spec, config_fingerprint
    fp = api.workload_fingerprint(bench_spec())
    assert "env_backend" not in fp["hts"]
    assert "env_backend" not in config_fingerprint()["hts"]


# ------------------------------------------- seeded-scenario equivalence
@pytest.mark.parametrize("scenario_seed", [3, 7])
def test_seeded_gridmaze_device_port_matches_host(scenario_seed):
    """Satellite of the tenancy PR: procedurally-sampled gridmaze
    layouts honor the same oracle contract as the default board — the
    device port steps the SAME sampled world bit-exactly, because both
    factories share one ``resolve_board`` and ``batched_env`` forwards
    the host env's ``make_kwargs``."""
    env = get_env("gridmaze", scenario_seed=scenario_seed)
    assert env.make_kwargs == {"scenario_seed": scenario_seed}
    hv = vectorize(env, 4)
    dv = batched_env(env, 4, "device")
    master = jax.random.key(11)
    keys0 = jax.random.split(jax.random.fold_in(master, 0), 4)
    hs, ho = hv.reset(keys0)
    ds, do = dv.reset(keys0)
    np.testing.assert_array_equal(np.asarray(ho), np.asarray(do))
    for t in range(30):
        k = jax.random.fold_in(master, t + 1)
        actions = jax.random.randint(k, (4,), 0, env.n_actions)
        keys = jax.random.split(k, 4)
        hs, ho, hr, hd = hv.step(hs, actions, keys)
        ds, do, dr, dd = dv.step(ds, actions, keys)
        np.testing.assert_array_equal(np.asarray(ho), np.asarray(do))
        np.testing.assert_array_equal(np.asarray(hr), np.asarray(dr))
        np.testing.assert_array_equal(np.asarray(hd), np.asarray(dd))


def test_seeded_gridmaze_spec_trains_same_on_both_backends():
    """End-to-end: one seeded-maze spec, host vs device env_backend,
    identical trajectories and params (the runtime-level cell of the
    scenario_seed axis)."""
    env = get_env("gridmaze", scenario_seed=7)
    outs = []
    for backend in ("host", "device"):
        cfg = HTSConfig(alpha=4, n_envs=4, seed=3, algorithm="ppo",
                        env_backend=backend)
        policy = models.get_policy("mlp", env)
        params = policy.init(jax.random.key(0))
        rt = engine.make_runtime("mesh", env, policy.apply, params,
                                 rmsprop(7e-4, eps=1e-5), cfg)
        outs.append(rt.run(3))
    _assert_same(*outs)
