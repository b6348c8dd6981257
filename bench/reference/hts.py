"""HTS-RL's update rule and rollout, written plainly.

Per interval j (staleness K = 1): the learner differentiates the
interval loss at the behavior parameters of interval j-1 on the data
interval j-1 produced, and applies the gradient to the current
parameters with RMSProp (the first interval has nothing to learn from);
the actors meanwhile roll out interval j with the pre-update parameters.
The loss is the mean over envs of each env's own loss over its alpha
steps (A2C with n-step returns, or PPO-clip with GAE and advantages
normalised within the env's steps).

``simulate`` runs that loop; ``check`` replays a recorded run against
it, transition by transition, and returns the numbers the benchmark
compares.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np
import jax
import jax.numpy as jnp

from bench.reference import keys as K
from bench.reference.nets import arith


# ------------------------------------------------------------- losses
def _returns(rew, done, boot, gamma):
    def back(ret, inp):
        r, d = inp
        ret = r + gamma * (1 - d) * ret
        return ret, ret
    return jax.lax.scan(back, boot, (rew, done), reverse=True)[1]


def _gae(rew, done, values, boot, gamma, lam):
    nxt = jnp.concatenate([values[1:], boot[None]], axis=0)
    delta = rew + gamma * (1 - done) * nxt - values

    def back(acc, inp):
        dl, nd = inp
        acc = dl + gamma * lam * nd * acc
        return acc, acc
    adv = jax.lax.scan(back, jnp.zeros_like(boot), (delta, 1 - done),
                       reverse=True)[1]
    return adv, adv + values


def env_loss(apply, params, traj, hp, mode):
    """One env's loss over its alpha steps; traj leaves (alpha, ...),
    bootstrap_obs (...)."""
    dtype = arith(mode)
    alpha = traj["actions"].shape[0]
    logits, values = apply(params, traj["obs"], mode)
    _, boot = apply(params, traj["bootstrap_obs"][None], mode)
    boot = jax.lax.stop_gradient(boot[0])
    rew = traj["rewards"].astype(dtype)
    done = traj["dones"].astype(dtype)
    gamma = jnp.asarray(hp["gamma"], dtype)
    if hp.get("use_gae"):
        adv, rets = _gae(rew, done, jax.lax.stop_gradient(values), boot,
                         gamma, jnp.asarray(hp["gae_lambda"], dtype))
    else:
        rets = _returns(rew, done, boot, gamma)
        adv = rets - jax.lax.stop_gradient(values)
    adv = jax.lax.stop_gradient(adv)
    logp = jax.nn.log_softmax(logits)
    lp = jnp.take_along_axis(logp, traj["actions"][:, None], axis=-1)[:, 0]
    ent = -jnp.sum(jnp.exp(logp) * logp, axis=-1)
    if hp["algorithm"] == "ppo":
        adv = (adv - adv.mean()) / (adv.std() + jnp.asarray(1e-8, dtype))
        ratio = jnp.exp(lp - traj["behavior_logprob"].astype(dtype))
        clip = hp["ppo_clip"]
        pg = -jnp.minimum(ratio * adv,
                          jnp.clip(ratio, 1 - clip, 1 + clip) * adv).sum()
    else:
        pg = -(lp * adv).sum()
    v = jnp.square(values - rets).sum()
    n = jnp.asarray(alpha, dtype)
    return (pg + hp["value_coef"] * v - hp["entropy_coef"] * ent.sum()) / n


def make_grad(apply, hp, mode):
    """grad(params, traj) of the mean over envs of ``env_loss``; traj
    leaves are (alpha, n_envs, ...), bootstrap_obs (n_envs, ...)."""
    def mean_loss(params, traj):
        per = {k: (v if k == "bootstrap_obs" else jnp.moveaxis(v, 1, 0))
               for k, v in traj.items()}
        losses = jax.vmap(lambda t: env_loss(apply, params, t, hp, mode))(
            per)
        return losses.mean()
    return jax.jit(jax.grad(mean_loss))


def rmsprop_step(params, sq, grads, hp, mode):
    """RMSProp without momentum: sq <- rho sq + (1-rho) g^2,
    p <- p - lr g / (sqrt(sq) + eps)."""
    rho, lr, eps = hp["rho"], hp["lr"], hp["eps"]
    dtype = arith(mode)
    cast = lambda t: jax.tree.map(lambda a: a.astype(dtype), t)
    params, sq, grads = cast(params), cast(sq), cast(grads)
    sq = jax.tree.map(lambda s, g: rho * s + (1 - rho) * g * g, sq, grads)
    params = jax.tree.map(lambda p, g, s: p - lr * g / (jnp.sqrt(s) + eps),
                          params, grads, sq)
    return params, sq


# ------------------------------------------------------------ rollout
def make_rollout(apply, env, hp, mode):
    """rollout(params, state, obs, j) -> (traj, state, obs): interval j
    of n_envs replicas, alpha steps, actions sampled with the per-(env,
    step) keys of the determinism contract."""
    alpha, master = hp["alpha"], K.seed_key(hp["seed"])

    @jax.jit
    def rollout(params, state, obs, j):
        ids = jnp.arange(obs.shape[0])

        def one(carry, t):
            state, obs = carry
            g = j * alpha + t
            akeys = jax.vmap(lambda e: K.action_key(master, e, g))(ids)
            ekeys = jax.vmap(lambda e: K.env_key(master, e, g))(ids)
            # the model runs in ``mode``; sampling and the behavior
            # logprob take its logits in float32
            logits = apply(params, obs, mode)[0].astype(jnp.float32)
            acts = jax.vmap(jax.random.categorical)(akeys, logits)
            blp = jnp.take_along_axis(jax.nn.log_softmax(logits),
                                      acts[:, None], axis=-1)[:, 0]
            state, nobs, r, d = env.step(state, acts, ekeys)
            return (state, nobs), {"obs": obs, "actions": acts,
                                   "rewards": r, "dones": d,
                                   "behavior_logprob": blp}

        (state, obs), traj = jax.lax.scan(one, (state, obs),
                                          jnp.arange(alpha))
        traj["bootstrap_obs"] = obs
        return traj, state, obs

    return rollout


def initial(env, hp, n_envs):
    """The envs' first state: reset with keys split from seed ^ 0x5EED."""
    ks = jax.random.split(K.seed_key(hp["seed"] ^ 0x5EED), n_envs)
    return env.reset(ks)


FAULTS = ("half_batch", "altered_action")


def simulate(apply, env, params0, hp, n_envs, n_intervals, mode,
             fault=None):
    """Run HTS-RL from ``params0`` in ``mode``; returns what the
    benchmark records of a run: per interval the trajectory, and the
    RMSProp state and parameters at the end of every interval.

    ``fault`` plants one of ``FAULTS``, to read what the check makes of
    it: ``half_batch`` takes the gradient over the first half of the
    envs only (their mean); ``altered_action`` records every action one
    higher (mod the action count) than the one sampled."""
    rollout = make_rollout(apply, env, hp, mode)
    grad = make_grad(apply, hp, mode)
    if fault == "half_batch":
        full, h = grad, n_envs // 2
        grad = lambda p, t: full(p, {k: (v[:h] if k == "bootstrap_obs"
                                         else v[:, :h])
                                     for k, v in t.items()})
    elif fault is not None and fault != "altered_action":
        raise ValueError(f"unknown fault {fault!r}; have {FAULTS}")
    theta = jax.tree.map(lambda a: a.astype(arith(mode)), params0)
    sq = jax.tree.map(jnp.zeros_like, theta)
    prev, pending = theta, None
    state, obs = initial(env, hp, n_envs)
    rec = []
    for j in range(n_intervals):
        new = theta
        if pending is not None:
            new, sq = rmsprop_step(theta, sq, grad(prev, pending), hp, mode)
        traj, state, obs = rollout(theta, state, obs, j)
        if fault == "altered_action":
            traj["actions"] = (traj["actions"] + 1) % env.n_actions
        prev, theta, pending = theta, new, traj
        rec.append({"traj": jax.device_get(traj),
                    "sq": jax.device_get(sq),
                    "params": jax.device_get(theta)})
    return rec


# -------------------------------------------------------------- check
def _leaf_gaps(prog: Dict[str, float], ref: Dict[str, float], keep):
    """Per leaf, |prog norm - ref norm| over max(ref norm, median ref
    norm)."""
    med = float(np.median([ref[k] for k in keep]))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep}


def check(apply, env, params0, hp, rec: List[dict], detail: bool = False):
    """Replay the first three learner steps of a recorded run (``rec``:
    the first four intervals, as ``simulate`` returns them) in float32
    at the highest precision, teacher-forced on the recorded
    trajectories. Returns:

    * ``action_gap`` -- widest gap by which a recorded action's
      Gumbel-perturbed reference logit lies below the best one, under
      that (env, step)'s own key, over three intervals of rollout;
    * ``env_mismatch`` -- transitions whose recorded reward, done flag
      or next observation differ from the rules (and first observations
      that differ from the reset);
    * ``grad_norm_gap`` -- the first gradient as the optimizer got it
      (from RMSProp's state after one step), worst leaf of the gap of
      norms against the reference's gradient;
    * ``grad_dev`` -- the same first gradient, worst leaf of the norm of
      the difference of magnitudes, component by component, over the
      same denominator;
    * ``update_norm_gap`` -- the parameters' change after three steps,
      worst leaf of the gap of norms, over leaves whose reference
      gradient is at least a thousandth of the median leaf's.
    """
    f32 = jnp.float32
    alpha, master = hp["alpha"], K.seed_key(hp["seed"])
    grad = make_grad(apply, hp, "highest")
    trajs = [jax.tree.map(jnp.asarray, r["traj"]) for r in rec[:3]]
    n_envs = trajs[0]["actions"].shape[1]

    @jax.jit
    def gaps(params, traj, j):
        ids = jnp.arange(n_envs)

        def one(t):
            g = j * alpha + t
            akeys = jax.vmap(lambda e: K.action_key(master, e, g))(ids)
            logits, _ = apply(params, traj["obs"][t])
            z = logits + jax.vmap(
                lambda k: jax.random.gumbel(k, (logits.shape[-1],)))(akeys)
            picked = jnp.take_along_axis(z, traj["actions"][t][:, None],
                                         axis=-1)[:, 0]
            return jnp.max(z.max(-1) - picked)
        return jnp.max(jax.vmap(one)(jnp.arange(alpha)))

    @jax.jit
    def env_replay(traj, t0, j):
        ids = jnp.arange(n_envs)

        def one(t_since, t):
            g = j * alpha + t
            ekeys = jax.vmap(lambda e: K.env_key(master, e, g))(ids)
            st = env.state_from_obs(traj["obs"][t], t_since)
            _, nobs, r, d = env.step(st, traj["actions"][t], ekeys)
            want = jnp.where(t == alpha - 1, traj["bootstrap_obs"],
                             traj["obs"][jnp.minimum(t + 1, alpha - 1)])
            bad = ((r != traj["rewards"][t]) | (d != traj["dones"][t])
                   | jnp.any(jnp.abs(nobs - want).reshape(n_envs, -1)
                             > 1e-5, axis=-1))
            t_next = jnp.where(traj["dones"][t] > 0, 0, t_since + 1)
            return t_next, bad.sum()
        t_end, bad = jax.lax.scan(one, t0, jnp.arange(alpha))
        return t_end, bad.sum()

    # rollout: behavior params theta_0, theta_0, theta_1 (delay 1)
    theta0 = jax.tree.map(lambda a: jnp.asarray(a, f32), params0)
    sq0 = jax.tree.map(jnp.zeros_like, theta0)
    g0 = grad(theta0, trajs[0])
    theta1, sq1 = rmsprop_step(theta0, sq0, g0, hp, "highest")
    g1 = grad(theta0, trajs[1])
    theta2, sq2 = rmsprop_step(theta1, sq1, g1, hp, "highest")
    g2 = grad(theta1, trajs[2])
    theta3, _ = rmsprop_step(theta2, sq2, g2, hp, "highest")
    behavior = [theta0, theta0, theta1]

    by_interval = [float(gaps(behavior[j], trajs[j], j)) for j in range(3)]
    _, reset_obs = initial(env, hp, n_envs)
    mismatch = int(jnp.sum(jnp.any(
        jnp.abs(trajs[0]["obs"][0] - reset_obs).reshape(n_envs, -1) > 1e-5,
        axis=-1)))
    t_since = jnp.zeros((n_envs,), jnp.int32)
    for j in range(3):
        t_since, bad = env_replay(trajs[j], t_since, j)
        mismatch += int(bad)
        if j < 2:   # an interval ends where the next begins
            mismatch += int(jnp.sum(jnp.any(jnp.abs(
                trajs[j]["bootstrap_obs"] - trajs[j + 1]["obs"][0]).reshape(
                    n_envs, -1) > 1e-5, axis=-1)))

    norm = lambda t: {k: float(jnp.linalg.norm(jnp.ravel(v)))
                      for k, v in t.items()}
    rho = hp["rho"]
    g_prog = norm(jax.tree.map(
        lambda s: jnp.sqrt(jnp.asarray(s, f32) / (1 - rho)), rec[1]["sq"]))
    g_ref = norm(g0)
    all_leaves = sorted(g_ref)
    med_g = float(np.median([g_ref[k] for k in all_leaves]))
    moving = [k for k in all_leaves if g_ref[k] >= 1e-3 * med_g]
    d_prog = norm(jax.tree.map(lambda a, b: jnp.asarray(a, f32) - b,
                               rec[3]["params"], theta0))
    d_ref = norm(jax.tree.map(lambda a, b: a - b, theta3, theta0))
    # how far each first-gradient magnitude lies from the reference's:
    # the gap of norms above cancels unbiased rounding, this does not
    g_dev = norm(jax.tree.map(
        lambda s, g: jnp.sqrt(jnp.asarray(s, f32) / (1 - rho))
        - jnp.abs(g), rec[1]["sq"], g0))
    grad_dev = max(g_dev[k] / max(g_ref[k], med_g) for k in all_leaves)
    grad_gaps = _leaf_gaps(g_prog, g_ref, all_leaves)
    update_gaps = _leaf_gaps(d_prog, d_ref, moving)
    numbers = {"action_gap": max(by_interval),
               "env_mismatch": mismatch,
               "grad_norm_gap": max(grad_gaps.values()),
               "grad_dev": grad_dev,
               "update_norm_gap": max(update_gaps.values())}
    if not detail:
        return numbers
    return numbers, {"action_gap_by_interval": by_interval,
                     "grad_gap_by_leaf": grad_gaps,
                     "update_gap_by_leaf": update_gaps,
                     "grad_norm_ref": g_ref, "update_norm_ref": d_ref}
