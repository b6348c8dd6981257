"""The one-chip training cells' correctness check, driven through the
harness at a small size on the CPU: a sound run passes; the
lower-precision control and each fault planted in the timed path come
out not correct."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness, run as bench_run
from bench import session as S
from bench.drivers import fit
from bench.reference import hts, nets

CELLS = [w["name"] for w in harness.Benchmark().doc["workloads"]
         if w["chips"] == 1
         and harness.Benchmark().cell(w["name"]).traffic["kind"] == "fit"]
SMALL = {"n_envs": 8}


def small(name, n_envs=SMALL["n_envs"]):
    cell = harness.Benchmark().cell(name)
    cell.traffic["spec"]["hts"]["n_envs"] = n_envs
    cell.traffic["ckpt_every"] = 2
    cell.traffic["intervals_per_s"] = 2
    return cell


def cell_rate_metric(name):
    return harness.Benchmark().cell(name).traffic.get("reports",
                                                      "env_steps_per_s")


def drive(cell, devices=None):
    return bench_run.run_cell(cell, 2**31 + 11, 1.0, False,
                              devices or jax.devices()[:1], time.time())


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    out = drive(small(name))
    assert out["correct"], out["checks"]
    rate = cell_rate_metric(name)
    assert out["metrics"][rate]["value"] > 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_lower_precision_control_is_not_correct(name):
    cell = small(name)
    spec = cell.spec_dict()
    from bench.reference import envs as ref_envs
    env = ref_envs.make(spec["env"]["name"], spec["env"].get("kwargs", {}),
                        cell.bench_dir)
    _, apply = nets.make(S.ref_policy(spec), env.obs_shape, env.n_actions,
                         cell.bench_dir)
    params0 = S.host(S.make_weights(spec, env.obs_shape, env.n_actions, 3,
                                    cell.bench_dir))
    hp = S.ref_hp(spec)
    rec = hts.simulate(apply, env, params0, hp, SMALL["n_envs"],
                       fit.CHECK_INTERVALS, cell.config["control"])
    ok, checks = bench_run.judge(hts.check(apply, env, params0, hp, rec),
                                 cell.limits["limits"])
    assert not ok, checks


def _unchanged(monkeypatch):
    monkeypatch.setattr("repro.core.delayed_grad.apply_updates",
                        lambda params, updates: params)


def _half_batch(monkeypatch):
    from repro.core import mesh_runtime
    orig = mesh_runtime.make_grad_sum_fn

    def make(policy_apply, cfg, grad_accumulation=1):
        grad_sum = orig(policy_apply, cfg, grad_accumulation)

        def half(params, traj):
            n = traj["actions"].shape[1] // 2
            kept = {k: (v[:n] if k == "bootstrap_obs" else v[:, :n])
                    for k, v in traj.items()}
            return jax.tree.map(lambda g: 2 * g, grad_sum(params, kept))
        return half
    monkeypatch.setattr(mesh_runtime, "make_grad_sum_fn", make)


def _altered_action(monkeypatch):
    from repro.core import rollout
    orig = rollout.actor_forward

    def altered(policy_apply, params, obs, keys):
        actions, logp = orig(policy_apply, params, obs, keys)
        n = policy_apply(params, obs[:1])[0].shape[-1]
        return (actions + 1) % n, logp
    monkeypatch.setattr(rollout, "actor_forward", altered)
    monkeypatch.setattr("repro.core.host_runtime.actor_forward", altered)


def _window_unchanged(monkeypatch):
    """Only the window's segments of several intervals leave the
    learner's state as they found it; the one-interval segments that
    the reference replays stay sound."""
    from repro.core import delayed_grad, engine, host_runtime
    copy = lambda algo: jax.tree.map(jnp.copy, algo)
    for cls in (engine.ScanRuntimeBase, host_runtime.HostHTSRL):
        orig = cls.run_from

        def run_from(self, state, n, finalize=True, _orig=orig):
            out = _orig(self, state, n, finalize)
            if n > 1 and hasattr(self, "carry"):
                self.carry = (copy(state.algo),) + tuple(self.carry[1:])
            elif n > 1:
                self.dg = delayed_grad.DelayedGradState(
                    *copy(tuple(state.algo)))
            return out
        monkeypatch.setattr(cls, "run_from", run_from)


FAULTS = {"state_unchanged": _unchanged, "half_batch": _half_batch,
          "altered_action": _altered_action,
          "window_state_unchanged": _window_unchanged}


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(name, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = drive(small(name))
    assert not out["correct"], out["checks"]
