"""HTS-RL as a single fused step (TPU-mesh-native adaptation).

Per synchronization interval j, one XLA program computes BOTH:

  * learner:  g = grad J(theta_{j-K}, D^{theta_{j-K}}) from the oldest
              ring slot, applied to theta_j (delay-K gradient — Eq. 6 at
              the default staleness K=1);
  * rollout:  D^{theta_j} collected with the *pre-update* params.

The two halves share no dataflow (grads depend on (theta_{j-K}, D_{j-K});
rollout on (theta_j, env_state)), so XLA is free to schedule them
concurrently — the compiler-level equivalent of the paper's process-level
concurrency, with identical update semantics (verified bit-exact against
the threaded host runtime in tests/test_equivalence.py).

The slab ring is positional in the scan carry: at K=1 the freshly
produced trajectory replaces the read slot for the next interval (the
paper's double buffer); at K>1 the carry holds a K-deep stacked ring —
the oldest slot is consumed, the fresh trajectory appended.

The update math itself lives in repro.algorithms (selected by
``cfg.algorithm``); this module is pure scheduling. ``make_hts_step``
accepts an optional ``axis_name`` so the same fused step runs data-parallel
under shard_map (core/sharded_runtime.py): gradients are all-reduced over
that mesh axis and the rollout offsets its env ids by the shard index so
the executor-seed determinism contract is preserved across any device
count.
"""
from __future__ import annotations

from typing import Callable, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro import algorithms
from repro.core import delayed_grad, spans
from repro.core.batch import (bit_reversed_order, halves_tree_sum,
                              pairwise_tree_sum)
from repro.core.engine import (HTSConfig, RunResult,  # noqa: F401 (re-export)
                               ScanRuntimeBase, register_runtime)
from repro.core.rollout import RolloutConfig, rollout_interval
from repro.envs.device import batched_env
from repro.envs.interfaces import Env
from repro.optim import Optimizer


def _interval_loss(policy_apply, params, traj, cfg: HTSConfig):
    """Loss over one interval's trajectory (alpha, n_envs, ...) — resolved
    through the algorithm registry (kept as a function for callers that
    predate repro.algorithms)."""
    return algorithms.get_algorithm(cfg.algorithm).loss(
        policy_apply, params, traj, cfg)


def _split_envs(traj):
    """Rearrange an interval trajectory so the env axis leads: regular
    leaves (alpha, N, ...) -> (N, alpha, 1, ...), bootstrap_obs
    (N, ...) -> (N, 1, ...). Row e is a complete width-1 trajectory —
    exactly what env e alone would have produced, because every model
    forward and every algorithm loss is row-independent across envs."""
    def mv(k, x):
        if k == "bootstrap_obs":
            return x[:, None]
        return jnp.moveaxis(x, 1, 0)[:, :, None]
    return {k: mv(k, v) for k, v in traj.items()}


def make_grad_sum_fn(policy_apply: Callable, cfg: HTSConfig,
                     grad_accumulation: int = 1):
    """``grad_sum(params, traj)``: the canonical SUM of per-env
    gradients over the local trajectory — the geometry-invariant half
    of the learner's gradient (repro.core.batch, DESIGN.md §12).

    Per-env gradients (a vmap of grad over width-1 env slices) are cast
    to fp32 and combined by the adjacent-pairwise tree over the env
    index. Note the deliberate absence of a divide: replicas combine
    SUMS, and the single divide by the global batch happens in
    make_grad_fn / make_learner_update.

    With ``grad_accumulation = A > 1`` and blocks of ``n_local / A >= 2``
    envs, the blocks run in sequence, in a ``lax.scan``: a vmap of grad
    over one contiguous block, that block's subtree sum, then the next,
    so one block's stack of per-env gradients is live at a time. The A
    block sums are then combined by the tree: power-of-two blocks are
    exact subtrees, so these are the flat tree's adds in its order.
    With A = 1, or blocks of one env, ONE vmap runs at the full local
    width: a width-1 vmap inside ``lax.scan`` is simplified to the
    unbatched lowering (gemv), whose products add in another order.

    A per-env gradient is row-independent, but its lowering depends on
    the vmap's width and, on XLA:CPU, on whether it runs in a loop body
    (where a convolution keeps the layout autodiff gave it and takes
    another kernel). The mlp policy's bits move with neither; the conv
    policy's move with both, on the CPU as on a TPU. So the mlp policy's
    sum is bit-equal across every geometry, while the conv policy's is
    bit-exact for one geometry, across segmentations and runtimes, and
    is checked against a float32 reference across geometries.

    With a power-of-two block (the whole local slice, without blocks)
    the trajectory's env axis is permuted to bit-reversed order within
    each block before the vmap (a gather on the trajectory, never on the
    per-env stack), and ``halves_tree_sum`` reduces the stack in
    contiguous halves: the same adds in the same order, so the same
    float as the strided tree. Any other width takes the strided tree.
    The scopes ``tree_halves`` and ``tree_strided`` inside
    ``grad_reduce`` say which path ran."""
    g1 = jax.grad(
        lambda p, traj: _interval_loss(policy_apply, p, traj, cfg)[0],
        has_aux=False)

    def per_env_grads(params, per):
        with jax.named_scope(spans.PER_ENV_GRAD):
            g = jax.vmap(g1, in_axes=(None, 0))(params, per)
            return jax.tree.map(lambda x: x.astype(jnp.float32), g)

    def grad_sum(params, traj):
        per = _split_envs(traj)
        n_local = jax.tree.leaves(per)[0].shape[0]
        A = grad_accumulation
        if A > 1 and n_local % A:
            raise ValueError(
                f"grad_accumulation={A} does not divide the local env "
                f"count {n_local}")
        blocks = A if A > 1 and n_local // A >= 2 else 1
        width = n_local // blocks
        halves = (width & (width - 1)) == 0
        tree = spans.TREE_HALVES if halves else spans.TREE_STRIDED
        block_sum = halves_tree_sum if halves else pairwise_tree_sum
        if halves:
            # bit-reversed order within each contiguous block
            order = (np.arange(blocks)[:, None] * width
                     + bit_reversed_order(width)).reshape(-1)
            with jax.named_scope(spans.GRAD_REDUCE), \
                    jax.named_scope(tree):
                per = jax.tree.map(lambda x: x[order], per)
        if blocks > 1:
            def one_block(_, block):
                g = per_env_grads(params, block)
                with jax.named_scope(spans.GRAD_REDUCE), \
                        jax.named_scope(tree):
                    return None, jax.tree.map(block_sum, g)

            _, sums = jax.lax.scan(one_block, None, jax.tree.map(
                lambda x: x.reshape((blocks, width) + x.shape[1:]), per))
            with jax.named_scope(spans.GRAD_REDUCE), \
                    jax.named_scope(tree):
                return jax.tree.map(pairwise_tree_sum, sums)
        per_env = per_env_grads(params, per)
        with jax.named_scope(spans.GRAD_REDUCE), jax.named_scope(tree):
            return jax.tree.map(block_sum, per_env)

    return grad_sum


def make_grad_fn(policy_apply: Callable, cfg: HTSConfig,
                 grad_accumulation: int = 1,
                 total_envs: Optional[int] = None):
    """``grad(params, traj)`` of the registry algorithm's interval loss —
    the ONE copy of the learner's gradient expression. Both the fused
    learner (make_learner_update, below) and the host runtime's split
    gradient pass build on this, which is what makes the cross-runtime
    bit-exactness contract a property of one function rather than of two
    copies staying in sync.

    The value is the canonical per-env tree sum (make_grad_sum_fn)
    divided once by ``total_envs`` (default: ``cfg.n_envs``) — equal to
    the gradient of the mean interval loss, with a reduction order
    that is invariant across (micro_batch, grad_accumulation,
    n_replicas) factorizations of the global batch."""
    grad_sum = make_grad_sum_fn(policy_apply, cfg, grad_accumulation)
    denom = float(total_envs if total_envs is not None else cfg.n_envs)

    def grad_fn(params, traj):
        s = grad_sum(params, traj)
        return jax.tree.map(
            lambda g, p: (g / denom).astype(p.dtype), s, params)

    return grad_fn


def make_learner_update(policy_apply: Callable, opt: Optimizer,
                        cfg: HTSConfig, axis_name: Optional[str] = None,
                        grad_accumulation: int = 1,
                        total_envs: Optional[int] = None):
    """The learner half: ``learn(dg, traj, skip) -> dg'``.

    Differentiates the registry algorithm at ``behavior_params(dg)`` (the
    oldest behavior snapshot theta_{j-K} — Eq. 6 generalized to delay K)
    on ``traj`` and applies the delay-K update. Exactly ONE update per
    interval (and one optimizer step per LOGICAL interval regardless of
    ``grad_accumulation`` — microbatches accumulate inside the gradient,
    they never see the optimizer): with both the differentiation point
    (theta_{j-K}) and the PPO clip reference (behavior_logprob) fixed,
    re-running "epochs" on the same interval data would reproduce the
    identical gradient — true multi-epoch PPO needs updates *between*
    epochs, which the delayed-gradient schedule (and the cross-runtime
    bit-exactness contract) deliberately excludes.

    Data-parallel (``axis_name``): each replica contributes its
    canonical tree SUM; sums are all-gathered in replica (= env-block)
    order and tree-combined — one collective per logical step, never
    per microbatch — and the single divide by the global env count
    (``total_envs``, default ``cfg.n_envs``) happens after the
    cross-replica combine. This replaces the old per-shard-mean +
    ``pmean`` (whose reduction order was backend-defined): the update
    is now bit-identical to the single-device run for any replica
    count whose blocks align with the canonical tree (DESIGN.md §12).
    """
    grad_sum = make_grad_sum_fn(policy_apply, cfg, grad_accumulation)
    denom = float(total_envs if total_envs is not None else cfg.n_envs)

    def learn(dg, traj, skip=None):
        bp = delayed_grad.behavior_params(dg)
        s = grad_sum(bp, traj)
        if axis_name is not None:
            with jax.named_scope(spans.GRAD_REDUCE):
                s = jax.tree.map(
                    lambda g: pairwise_tree_sum(
                        jax.lax.all_gather(g, axis_name)), s)
        grads = jax.tree.map(
            lambda g, p: (g / denom).astype(p.dtype), s, bp)
        # The gradient/update boundary is a ROUNDING boundary of the
        # cross-runtime contract: the host runtime materializes grads
        # between its split grad and apply jits, so the fused learner
        # must not let XLA fuse gradient arithmetic into the optimizer
        # update (e.g. FMA-combining the divide with rmsprop's g*g) —
        # that shifts opt_state by ulps and the runtimes drift apart.
        grads = jax.lax.optimization_barrier(grads)
        with jax.named_scope(spans.OPTIMIZER):
            return delayed_grad.update(dg, grads, opt, skip=skip)

    return learn


def ring_read(buf, staleness: int):
    """The ring slot the next learner pass consumes: the single pending
    trajectory at K=1, the oldest stacked slot otherwise."""
    return buf if staleness == 1 else jax.tree.map(lambda x: x[0], buf)


def ring_append(buf, traj, staleness: int):
    """Advance the positional ring: drop the consumed oldest slot, append
    the freshly produced trajectory. At K=1 the ring IS the trajectory."""
    if staleness == 1:
        return traj
    return jax.tree.map(
        lambda r, t: jnp.concatenate([r[1:], t[None]], axis=0), buf, traj)


def make_ring_drain(learn, staleness: int, wrap=None):
    """The reporting-only trailing pass, generalized: consume the K
    pending ring slots in interval order so ``run(n)`` reflects exactly
    ``n`` updates. Pass p consumes the data of global interval
    ``j - K + p``; ``skip`` guards slots that no interval has filled yet
    (the n < K edge, and the n = 0 edge at K=1). Shared by the host,
    mesh, and sharded runtimes — one drain, three schedulers.

    ONE compiled program PER pass, dispatched K times (``wrap`` compiles
    the single-pass body; default ``jax.jit``, the sharded runtime hands
    in its shard_map wrapper). Fusing the chained passes into one
    program is NOT value-stable across compilation contexts: XLA lays
    out the later passes' backward differently under shard_map than
    under plain jit (ulp drift at K > 2 that optimization_barrier
    between passes does not pin), while a single pass per dispatch
    compiles identically everywhere — the drain is reporting-only, so
    K extra dispatches cost nothing that matters."""
    def one_pass_scoped(dg, traj, skip):
        with jax.named_scope(spans.DRAIN):
            return learn(dg, traj, skip=skip)

    one_pass = (wrap or jax.jit)(one_pass_scoped)

    def drain(dg, buf, j):
        for p in range(staleness):
            traj = (buf if staleness == 1
                    else jax.tree.map(lambda x, _p=p: x[_p], buf))
            dg = one_pass(dg, traj, j - staleness + p < 0)
        return dg

    # surface the compiled program so cache-size guards (and callers
    # inspecting compile counts) can see through the dispatcher
    drain.one_pass = one_pass
    return drain


def make_hts_step(policy_apply: Callable, env: Env, opt: Optimizer,
                  cfg: HTSConfig, axis_name: Optional[str] = None,
                  grad_accumulation: int = 1,
                  total_envs: Optional[int] = None):
    """Build the fused HTS-RL interval step (pure, jit-able, pjit-able).

    With ``axis_name`` the step is shard_map-ready: ``cfg.n_envs`` is the
    *per-shard* replica count and env ids are globally offset by the shard
    index, so seeds — and therefore trajectories — match the single-device
    run exactly. ``grad_accumulation``/``total_envs`` thread the batch
    geometry into the learner half (make_learner_update).
    """
    rcfg = RolloutConfig(cfg.alpha, cfg.n_envs)
    master = jax.random.key(cfg.seed)
    learn = make_learner_update(policy_apply, opt, cfg, axis_name,
                                grad_accumulation, total_envs)
    K = cfg.staleness

    def step(carry, _):
        dg, env_state, obs, buf_ring, j = carry
        # ---- learner half: delay-K gradient at theta_{j-K} on D_{j-K}
        # (the oldest ring slot; the first K intervals have nothing to
        # consume yet, so their updates are skipped — run(n) still
        # reflects n updates because _finalize drains the K pending
        # passes)
        with jax.named_scope(spans.LEARNER):
            dg_next = learn(dg, ring_read(buf_ring, K), skip=(j < K))
        # ---- rollout half: behavior policy is theta_j (pre-update)
        offset = (jax.lax.axis_index(axis_name) * cfg.n_envs
                  if axis_name is not None else 0)
        with jax.named_scope(spans.ROLLOUT):
            traj, env_state, obs = rollout_interval(
                policy_apply, env, dg.params, env_state, obs, master,
                j * cfg.alpha, rcfg, env_offset=offset)
        metrics = {"rewards": traj["rewards"], "dones": traj["dones"]}
        return (dg_next, env_state, obs, ring_append(buf_ring, traj, K),
                j + 1), metrics

    return step


def init_carry(policy_params, opt: Optimizer, env: Env, cfg: HTSConfig,
               policy_apply: Callable):
    """Initial (dg_state, env_state, obs, zero read ring, j=0).

    ``policy_params`` is copied: the carry is donated into the interval
    program (engine.ScanRuntimeBase._program), and in-place updates must
    never invalidate the caller's parameter tree — run() replays and
    cross-runtime comparisons hand the same params to many runtimes."""
    keys = jax.random.split(jax.random.key(cfg.seed ^ 0x5EED), cfg.n_envs)
    env_state, obs = env.reset(keys)
    dg = delayed_grad.init(jax.tree.map(jnp.copy, policy_params), opt,
                           staleness=cfg.staleness)
    zero_traj = {
        "obs": jnp.zeros((cfg.alpha,) + obs.shape, obs.dtype),
        "actions": jnp.zeros((cfg.alpha, cfg.n_envs), jnp.int32),
        "rewards": jnp.zeros((cfg.alpha, cfg.n_envs), jnp.float32),
        "dones": jnp.ones((cfg.alpha, cfg.n_envs), jnp.float32),
        "behavior_logprob": jnp.zeros((cfg.alpha, cfg.n_envs), jnp.float32),
        "bootstrap_obs": jnp.zeros_like(obs),
    }
    if cfg.staleness > 1:
        zero_traj = jax.tree.map(
            lambda x: jnp.stack([x] * cfg.staleness), zero_traj)
    return (dg, env_state, obs, zero_traj, jnp.zeros((), jnp.int32))


def train(policy_params, policy_apply, env: Env, opt: Optimizer,
          cfg: HTSConfig, n_intervals: int, unroll: int = 1):
    """Run n_intervals HTS-RL intervals. Returns (final carry, metrics).

    NOTE: the final interval's trajectory is left unconsumed in the carry
    (its update would belong to interval n). ``MeshRuntime.run`` adds the
    trailing learner pass so update counts line up across runtimes.
    """
    step = make_hts_step(policy_apply, env, opt, cfg)
    carry = init_carry(policy_params, opt, env, cfg, policy_apply)

    @jax.jit
    def run(carry):
        return jax.lax.scan(step, carry, None, length=n_intervals)

    carry, metrics = run(carry)
    return carry, metrics


@register_runtime("mesh")
class MeshRuntime(ScanRuntimeBase):
    """Engine port of the fused runtime (one XLA program per interval).

    ``batch`` (a ``repro.core.batch.BatchConfig``) is accepted as pure
    factorization bookkeeping: a single fused program reproduces an
    (n_replicas x grad_accumulation) geometry by running the gradient
    over ``chunks = grad_accumulation * n_replicas`` microbatch blocks
    in sequence — the canonical reduction is geometry-invariant, so the
    mesh runtime is the single-process oracle for any validated
    multi-replica run (bit-exact where the per-env bits do not depend
    on the block's width, as the mlp policy's; DESIGN.md §12)."""

    name = "mesh"

    def __init__(self, env: Env, policy_apply: Callable, params,
                 opt: Optimizer, cfg: HTSConfig, batch=None):
        super().__init__(env, policy_apply, params, opt, cfg)
        if cfg.staleness < 1:
            raise ValueError(f"staleness must be >= 1, got {cfg.staleness}")
        from repro.core.batch import BatchConfig
        self.batch = BatchConfig.of(batch)
        self.geometry = self.batch.resolve(cfg.n_envs, default_replicas=1)
        # env_backend resolves HERE (construction), not at trace time:
        # "host" vmaps the scalar env, "device" steps the natively-
        # batched port inside the same scan body
        self.venv = batched_env(env, cfg.n_envs, cfg.env_backend)

    def _build(self) -> None:
        # chunks = A x R: emulating R replicas in-process means R more
        # microbatch blocks — same blocks, same tree, same float
        self._step = make_hts_step(self.policy_apply, self.venv, self.opt,
                                   self.cfg,
                                   grad_accumulation=self.geometry.chunks)
        self._learn = make_learner_update(
            self.policy_apply, self.opt, self.cfg,
            grad_accumulation=self.geometry.chunks)
        # reporting-only trailing learner passes draining the K pending
        # ring slots, so run(n) applies exactly n updates (matching the
        # host runtime); skip guards the not-yet-filled slots (n < K).
        # Kept OUT of _program: the scan carry must stay mid-stream so
        # state()/run_from never double-consume an interval.
        self._final_fn = make_ring_drain(self._learn, self.cfg.staleness)

    def _initial_carry(self):
        return init_carry(self.params0, self.opt, self.venv, self.cfg,
                          self.policy_apply)

    def _finalize(self, carry):
        dg, env_state, obs, buf, j = carry
        return (self._final_fn(dg, buf, j), env_state, obs, buf, j)

    def _result_state(self, carry):
        return carry[0].params, carry[0]


def episode_returns(metrics) -> jnp.ndarray:
    """Completed-episode returns from stacked (intervals, alpha, n_envs)
    reward/done streams."""
    r = metrics["rewards"].reshape(-1, metrics["rewards"].shape[-1])
    d = metrics["dones"].reshape(-1, r.shape[-1])

    def step(acc, inp):
        rr, dd = inp
        acc = acc + rr
        out = jnp.where(dd > 0, acc, jnp.nan)
        acc = jnp.where(dd > 0, 0.0, acc)
        return acc, out

    _, outs = jax.lax.scan(step, jnp.zeros(r.shape[-1]), (r, d))
    return outs   # (steps, n_envs) with NaN where no episode completed
