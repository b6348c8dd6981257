"""Data-parallel HTS-RL: the fused interval step under ``shard_map``.

The first runtime that scales ``n_envs`` past one device. Environment
replicas are sharded along the mesh's ``data`` axis (launch/mesh.py);
each shard runs the SAME fused learner+rollout program as the mesh
runtime over its local slice, and the delayed gradient crosses replicas
through a single all-gather-and-tree-combine per logical step — the
only inter-device communication per interval (params stay replicated,
matching the paper's learner/actor split where only the update is
global).

Replica count comes from the batch geometry
(``repro.core.batch.BatchConfig``): an explicit ``batch.n_replicas``
sizes the data axis to EXACTLY that many devices (erroring when the
platform has fewer); the legacy default (``n_replicas=None``) keeps the
pre-BatchConfig behavior of spanning every local device. Within each
replica, ``grad_accumulation`` microbatch blocks of two or more envs run
in sequence (``mesh_runtime.make_grad_sum_fn``), so one block's per-env
gradients are live at a time, before the cross-replica combine — grads
cross replicas once per logical step, never per microbatch.

Determinism is preserved across device counts AND processes: rollout
env ids are offset by ``axis_index('data') * n_envs_local``, so env
replica e draws exactly the (run_seed, e, step) keys it would on one
device, whichever shard (or process) hosts it. Trajectories are
therefore bit-exact for any factorization — and since PR 9 the PARAMS
are too: the canonical per-env tree-sum gradient (repro.core.batch,
DESIGN.md §12) makes the d-device run bit-identical to the mesh
runtime for every validated geometry, not merely float-close.

Multi-process meshes (core/distributed.py): when the data axis spans
processes, the initial carry — computed identically on every process
from the shared seed — is assembled into global ``jax.Array``s per the
carry specs, and metric streams are all-gathered back to every host.
"""
from __future__ import annotations

from typing import Callable

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import mesh_runtime
from repro.core.batch import BatchConfig
from repro.core.engine import (HTSConfig, ScanRuntimeBase,
                               register_runtime)
from repro.envs.device import batched_env
from repro.envs.interfaces import Env
from repro.launch.mesh import make_host_mesh
from repro.optim import Optimizer


@register_runtime("sharded")
class ShardedHTSRL(ScanRuntimeBase):
    name = "sharded"

    def __init__(self, env: Env, policy_apply: Callable, params,
                 opt: Optimizer, cfg: HTSConfig, mesh=None,
                 axis: str = "data", batch=None):
        super().__init__(env, policy_apply, params, opt, cfg)
        if cfg.staleness < 1:
            raise ValueError(f"staleness must be >= 1, got {cfg.staleness}")
        self.batch = BatchConfig.of(batch)
        self.axis = axis
        if mesh is None:
            if self.batch.n_replicas is not None:
                # explicit geometry sizes the replica axis EXACTLY —
                # "however many devices happen to exist" is the thing
                # BatchConfig exists to remove
                want = self.batch.n_replicas
                devices = jax.devices()
                if len(devices) < want:
                    raise ValueError(
                        f"batch.n_replicas={want} but only "
                        f"{len(devices)} device(s) are visible; start "
                        f"more processes (core/distributed.py) or "
                        f"force host devices "
                        f"(--xla_force_host_platform_device_count)")
                mesh = Mesh(np.array(devices[:want]), (axis,))
            else:
                mesh = make_host_mesh()
        elif (self.batch.n_replicas is not None
              and mesh.shape[axis] != self.batch.n_replicas):
            raise ValueError(
                f"batch.n_replicas={self.batch.n_replicas} != the "
                f"{mesh.shape[axis]}-way '{axis}' axis of the provided "
                f"mesh; size the mesh from the batch geometry")
        self.mesh = mesh
        n_shards = self.mesh.shape[axis]
        # geometry checks (divisibility; power-of-two alignment for
        # explicit configs) with the spec-style field-named errors
        self.geometry = self.batch.resolve(cfg.n_envs,
                                           default_replicas=n_shards)
        if cfg.n_envs % n_shards:
            raise ValueError(
                f"n_envs={cfg.n_envs} not divisible by the {n_shards}-way "
                f"'{axis}' mesh axis")
        self.n_shards = n_shards
        self.lcfg = cfg._replace(n_envs=cfg.n_envs // n_shards)
        # does the data axis span OS processes? (core/distributed.py)
        self._multiprocess = len(
            {d.process_index for d in self.mesh.devices.flat}) > 1
        # a DeviceEnv steps any leading batch width, so the same port
        # serves both the per-shard body and the global init
        self.venv_local = batched_env(env, self.lcfg.n_envs,
                                      cfg.env_backend)
        self.venv_global = batched_env(env, cfg.n_envs, cfg.env_backend)

    def _build(self) -> None:
        # per-shard accumulation plus the global divide: gradients are
        # canonical tree SUMS locally, combined across the axis once
        # per logical step, divided by the GLOBAL env count at the end
        self._step = mesh_runtime.make_hts_step(
            self.policy_apply, self.venv_local, self.opt, self.lcfg,
            axis_name=self.axis,
            grad_accumulation=self.geometry.grad_accumulation,
            total_envs=self.cfg.n_envs)
        self._learn = mesh_runtime.make_learner_update(
            self.policy_apply, self.opt, self.lcfg, axis_name=self.axis,
            grad_accumulation=self.geometry.grad_accumulation,
            total_envs=self.cfg.n_envs)
        self._final_prog = None     # built lazily (needs carry specs)

    def _initial_carry(self):
        # global carry (identical to the mesh runtime's); shard_map slices
        # the env/trajectory leaves along the data axis per in_specs
        carry = mesh_runtime.init_carry(
            self.params0, self.opt, self.venv_global, self.cfg,
            self.policy_apply)
        if self._multiprocess:
            carry = self._globalize(carry)
        return carry

    def _globalize(self, carry):
        """Assemble per-process (identically computed) carry leaves into
        global ``jax.Array``s laid out per the carry specs. Every
        process computes the FULL logical carry from the shared seed —
        cheap at init — and contributes the shards its local devices
        own, so no cross-host transfer happens at all."""
        specs = self._carry_specs(carry)

        def wrap(x, spec):
            x = np.asarray(x)
            sharding = NamedSharding(self.mesh, spec)
            return jax.make_array_from_callback(
                x.shape, sharding, lambda idx, _x=x: _x[idx])

        return jax.tree.map(wrap, carry, specs)

    def _host_metrics(self, metrics):
        # metric streams are sharded over the data axis; on a
        # multi-process mesh each host holds only its slice, so gather
        # the global streams back to every process (they are reporting
        # data — tiny next to the training state)
        if self._multiprocess:
            from jax.experimental import multihost_utils
            metrics = multihost_utils.process_allgather(metrics,
                                                        tiled=True)
        return metrics

    def _carry_specs(self, carry):
        dg, env_state, obs, buf, j = carry
        rep = lambda tree: jax.tree.map(lambda _: P(), tree)
        shard0 = lambda tree: jax.tree.map(lambda _: P(self.axis), tree)
        # ring slots (K>1) prepend a replicated staleness axis in front
        # of the (alpha, n_envs, ...) trajectory leaves
        ring = (None,) if self.cfg.staleness > 1 else ()
        buf_spec = {k: (P(*ring, self.axis) if k == "bootstrap_obs"
                        else P(*ring, None, self.axis)) for k in buf}
        return (rep(dg), shard0(env_state), P(self.axis), buf_spec, P())

    def _program(self, n_intervals: int):
        carry_specs = self._carry_specs(self.carry)
        metric_specs = {"rewards": P(None, None, self.axis),
                        "dones": P(None, None, self.axis)}

        def body(carry):
            return jax.lax.scan(self._step, carry, None,
                                length=n_intervals)

        # carry donated like every scan runtime (see
        # engine.ScanRuntimeBase._program): params/opt-state/trajectory
        # shards update in place across the program boundary
        return jax.jit(jax.shard_map(body, mesh=self.mesh,
                                     in_specs=(carry_specs,),
                                     out_specs=(carry_specs, metric_specs),
                                     check_vma=False),
                       donate_argnums=0)

    def _finalize(self, carry):
        # reporting-only trailing learner passes draining the K pending
        # ring slots (same update-count contract as host/mesh; skip
        # guards the not-yet-filled slots). Its collective needs the
        # mesh axis, so it is its own shard_map program — separate from
        # the scan, which must leave the carry mid-stream for run_from.
        # make_ring_drain's pass-per-dispatch structure (see its
        # docstring: chained passes fused into one program are not
        # value-stable across compilation contexts), with the
        # single-pass program wrapped in shard_map for the collective.
        if self._final_prog is None:
            dg_spec, _, _, buf_spec, j_spec = self._carry_specs(carry)
            slot_spec = {k: (P(self.axis) if k == "bootstrap_obs"
                             else P(None, self.axis)) for k in carry[3]}
            wrap = lambda f: jax.jit(jax.shard_map(
                f, mesh=self.mesh,
                in_specs=(dg_spec, slot_spec, P()),
                out_specs=dg_spec, check_vma=False))
            self._final_prog = mesh_runtime.make_ring_drain(
                self._learn, self.cfg.staleness, wrap=wrap)
        dg, env_state, obs, buf, j = carry
        return (self._final_prog(dg, buf, j), env_state, obs, buf, j)

    def _result_state(self, carry):
        return carry[0].params, carry[0]
