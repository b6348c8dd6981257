"""BatchConfig and the scale-out determinism contract (DESIGN.md §12).

Three layers of pinning:

  * validation — accepted (global_batch, grad_accumulation, n_replicas)
    triples round-trip the spec's canonical JSON; rejected ones name
    the offending ``batch.<field>`` and suggest the nearest valid
    factorization (fuzzed with hypothesis when installed, plus an
    always-on exhaustive sweep over small global batches);
  * bit-exactness — for a fixed global batch, final params and
    episode-return streams are IDENTICAL across every
    (n_replicas, grad_accumulation) cell, on the mesh (in-process
    factorization bookkeeping), host (accumulated gradient pass), and
    sharded (real 2-device data parallelism, subprocess) runtimes —
    including a checkpoint capsule restored onto a different replica
    count (the mlp policy; the conv policy's sums move with the block
    width within float32 rounding);
  * multi-process — a 2-process ``jax.distributed`` run
    (repro.launch.distributed) produces the single-process mesh
    digest, bit-exact, on both processes.
"""
import json
import os
import re
import socket
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import api, models
from repro.core import engine
from repro.core import batch as batch_mod
from repro.core import mesh_runtime, spans
from repro.core.batch import (BatchConfig, bit_reversed_order,
                              halves_tree_sum, pairwise_tree_sum)
from repro.core.engine import HTSConfig
from repro.envs import catch, gridmaze, gridmaze84
from repro.optim import rmsprop

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# --------------------------------------------------------------- helpers
def _setup():
    env1 = catch.make()
    cfg = HTSConfig(alpha=5, n_envs=4, seed=3)
    policy = models.get_policy("mlp", env1)
    params = policy.init(jax.random.key(0))
    return env1, cfg, policy.apply, params, rmsprop(7e-4, eps=1e-5)


def _maxdiff(a, b):
    return max(float(jnp.max(jnp.abs(x - y)))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ------------------------------------------------------------ validation
def test_field_level_errors():
    with pytest.raises(ValueError, match="batch.micro_batch"):
        BatchConfig(micro_batch=0)
    with pytest.raises(ValueError, match="batch.grad_accumulation"):
        BatchConfig(grad_accumulation=-1)
    with pytest.raises(ValueError, match="batch.n_replicas"):
        BatchConfig(n_replicas=True)      # bools are not counts
    with pytest.raises(ValueError, match="unknown batch field"):
        BatchConfig.of({"replicas": 2})


def test_resolve_divisibility_and_alignment():
    # divisibility: A*R must divide the global batch
    with pytest.raises(ValueError, match="batch.n_replicas=3"):
        BatchConfig(n_replicas=3).resolve(8)
    # alignment: A must be a power of two when the geometry is explicit
    with pytest.raises(ValueError, match="power of\\s+two"):
        BatchConfig(grad_accumulation=3).resolve(12)
    # ...but R is unconstrained beyond divisibility (the cross-replica
    # pairwise combine continues the global tree for any R)
    g = BatchConfig(n_replicas=3).resolve(12)
    assert g == (4, 1, 3, 12)
    # micro_batch derives replicas when they are omitted
    g = BatchConfig(micro_batch=2, grad_accumulation=2).resolve(16)
    assert (g.micro_batch, g.n_replicas) == (2, 4)
    # ...and is cross-checked when both are given
    with pytest.raises(ValueError, match="batch.micro_batch=4 inconsist"):
        BatchConfig(micro_batch=4, n_replicas=4).resolve(8)
    # legacy default geometry: divisibility only, no pow2 constraint
    assert BatchConfig().resolve(12, default_replicas=3).chunks == 3


def test_rejections_name_nearest_valid_factorization():
    with pytest.raises(ValueError, match="nearest valid factorization"):
        BatchConfig(n_replicas=5).resolve(8)
    try:
        BatchConfig(grad_accumulation=3, n_replicas=2).resolve(8)
    except ValueError as e:
        msg = str(e)
        assert "batch.grad_accumulation=3" in msg
        assert "grad_accumulation=" in msg and "n_replicas=" in msg
    else:
        pytest.fail("A=3,R=2 over 8 envs must be rejected")


def test_exhaustive_small_global_batches():
    """Always-on sweep (the hypothesis fuzz below needs the optional
    dep): every (N <= 16, A <= N, R <= N) triple either resolves —
    and then round-trips the spec's canonical JSON — or raises a
    ValueError naming a batch.<field> and suggesting a factorization."""
    for n_envs in (1, 2, 3, 4, 6, 8, 12, 16):
        for a in range(1, n_envs + 1):
            for r in range(1, n_envs + 1):
                bc = BatchConfig(grad_accumulation=a, n_replicas=r)
                try:
                    g = bc.resolve(n_envs)
                except ValueError as e:
                    assert "batch." in str(e)
                    assert "nearest valid factorization" in str(e)
                    continue
                assert g.micro_batch * a * r == n_envs
                spec = api.ExperimentSpec(
                    runtime="mesh", hts={"n_envs": n_envs}, batch=bc)
                again = api.loads(api.dumps(spec))
                assert again == spec
                assert again.batch.resolve(n_envs) == g


def test_hypothesis_fuzz_roundtrip():
    pytest.importorskip(
        "hypothesis", reason="optional dep: fuzz needs hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(n_envs=st.integers(1, 256), a=st.integers(1, 32),
           r=st.integers(1, 32))
    def fuzz(n_envs, a, r):
        bc = BatchConfig(grad_accumulation=a, n_replicas=r)
        try:
            g = bc.resolve(n_envs)
        except ValueError as e:
            assert "batch." in str(e)
            assert "nearest valid factorization" in str(e)
            return
        assert g.micro_batch * a * r == n_envs
        # accepted triples survive the canonical JSON round-trip
        spec = api.ExperimentSpec(runtime="mesh",
                                  hts={"n_envs": n_envs}, batch=bc)
        assert api.loads(api.dumps(spec)) == spec

    fuzz()


def test_pairwise_tree_sum_subtree_property():
    """Power-of-two blocks are exact subtrees: hierarchical reduction
    equals the flat one bit-for-bit (float32, adversarial magnitudes)."""
    rng = np.random.default_rng(0)
    x = jnp.asarray((rng.standard_normal(16)
                     * 10.0 ** rng.integers(-6, 6, 16)).astype(np.float32))
    flat = pairwise_tree_sum(x)
    for blocks in (2, 4, 8):
        sums = jax.vmap(pairwise_tree_sum)(x.reshape(blocks, -1))
        assert float(pairwise_tree_sum(sums)) == float(flat), blocks


def _wide(rng, shape):
    """float32 values spread over 18 decades, both signs."""
    return (rng.standard_normal(shape)
            * 10.0 ** rng.integers(-9, 9, shape)).astype(np.float32)


def _cnn(**widths):
    env = gridmaze.make()
    return models.get_policy("cnn", env, conv_sizes=(3, 3, 3),
                             conv_strides=(1, 1, 1), **widths)


def _same_bits(a, b) -> bool:
    return all(np.array_equal(np.asarray(x).view(np.uint32),
                              np.asarray(y).view(np.uint32))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def test_bit_reversed_order():
    assert list(bit_reversed_order(1)) == [0]
    assert list(bit_reversed_order(8)) == [0, 4, 2, 6, 1, 5, 3, 7]
    for n in (2, 64, 2048):
        order = bit_reversed_order(n)
        assert sorted(order) == list(range(n))
        assert list(order[order]) == list(range(n))     # an involution
    with pytest.raises(ValueError, match="power of two"):
        bit_reversed_order(48)
    with pytest.raises(ValueError, match="power-of-two"):
        halves_tree_sum(jnp.zeros((6, 2)))


@pytest.mark.parametrize("n", [2 ** e for e in range(12)])
def test_halves_tree_sum_is_pairwise_tree_sum_bit_for_bit(n, monkeypatch):
    """On a stack stored in bit-reversed order the contiguous-halves tree
    computes ``pairwise_tree_sum``'s float, bit for bit: on each of the
    cnn policy's leaves (narrowed, so that a stack of 2048 stays small)
    and three of its leaves at the paper's widths, and for every number
    of levels per pass from 1 to 11 on those leaves side by side (the
    adds run along the env axis alone, one column at a time)."""
    rng = np.random.default_rng(n)
    params = jax.eval_shape(
        lambda: _cnn(conv_filters=(4, 8, 8), hidden=8).init(
            jax.random.key(0)))
    shapes = [l.shape for l in jax.tree.leaves(params)]
    shapes += [(3,), (576, 8), (3, 3, 3, 32)]
    stack = [_wide(rng, (n,) + shape) for shape in shapes]
    want = jax.jit(lambda s: [pairwise_tree_sum(x) for x in s])(stack)
    order = bit_reversed_order(n)
    stored = [x[order] for x in stack]
    got = jax.jit(lambda s: [halves_tree_sum(x) for x in s])(stored)
    assert _same_bits(want, got)
    columns = np.concatenate([x.reshape(n, -1) for x in stored], axis=1)
    want = np.concatenate([np.asarray(w).reshape(-1) for w in want])
    for k in range(1, 12):
        monkeypatch.setattr(batch_mod, "HALVES_PER_PASS", k)
        got = jax.jit(lambda s: halves_tree_sum(s))(columns)
        assert _same_bits(want, got), k


def _old_grad_sum(policy_apply, cfg, A):
    """The strided formulation: vmap in natural env order, then the
    adjacent-pairwise tree; for A > 1, block by block in sequence (a
    scan over blocks of n / A envs, as ``make_grad_sum_fn`` runs them),
    then the tree over the block sums."""
    g1 = jax.grad(lambda p, t: mesh_runtime._interval_loss(
        policy_apply, p, t, cfg)[0])

    def block_sum(params, per):
        g = jax.vmap(g1, in_axes=(None, 0))(params, per)
        return jax.tree.map(
            lambda x: pairwise_tree_sum(x.astype(jnp.float32)), g)

    def grad_sum(params, traj):
        per = mesh_runtime._split_envs(traj)
        if A == 1:
            return block_sum(params, per)
        n = jax.tree.leaves(per)[0].shape[0]
        _, sums = jax.lax.scan(
            lambda _, b: (None, block_sum(params, b)), None,
            jax.tree.map(lambda x: x.reshape((A, n // A) + x.shape[1:]),
                         per))
        return jax.tree.map(pairwise_tree_sum, sums)
    return grad_sum


def _random_traj(n, alpha, seed):
    rng = np.random.default_rng(seed)
    return {
        "obs": jnp.asarray(rng.random((alpha, n, 9, 9, 3), np.float32)),
        "actions": jnp.asarray(rng.integers(0, 4, (alpha, n)), jnp.int32),
        "rewards": jnp.asarray(rng.standard_normal((alpha, n)),
                               jnp.float32),
        "dones": jnp.asarray(rng.random((alpha, n)) < 0.2, jnp.float32),
        "behavior_logprob": jnp.asarray(-rng.random((alpha, n)),
                                        jnp.float32),
        "bootstrap_obs": jnp.asarray(rng.random((n, 9, 9, 3), np.float32)),
    }


@pytest.mark.parametrize("n,A", [(8, 1), (8, 2), (8, 4), (64, 1),
                                 (64, 2), (64, 4), (48, 1), (48, 2),
                                 (48, 4)])
def test_grad_sum_keeps_the_strided_bits(n, A):
    """``make_grad_sum_fn`` on the cnn policy gives the strided
    formulation's gradient sum bit for bit, at the same geometry:
    bit-reversed env order and contiguous halves at a power-of-two block
    (the whole slice without accumulation), the strided tree itself at
    blocks of 48, 24 and 12 envs."""
    policy = _cnn()
    params = policy.init(jax.random.key(1))
    cfg = HTSConfig(alpha=5, n_envs=n, seed=3)
    traj = _random_traj(n, cfg.alpha, seed=n + A)
    new = jax.jit(mesh_runtime.make_grad_sum_fn(policy.apply, cfg, A))
    old = jax.jit(_old_grad_sum(policy.apply, cfg, A))
    assert _same_bits(old(params, traj), new(params, traj))
    paths = re.findall(r'op_name="([^"]*)"',
                       new.lower(params, traj).compile().as_text())
    path = spans.TREE_HALVES if n & (n - 1) == 0 else spans.TREE_STRIDED
    for name in (spans.TREE_HALVES, spans.TREE_STRIDED):
        under = [p for p in paths if name in p.split("/")]
        assert bool(under) == (name == path), name
        assert all(spans.GRAD_REDUCE in p.split("/")[:p.split("/").index(
            name)] for p in under), name


@pytest.mark.parametrize("n", [8, 64])
def test_conv_blocks_move_the_bits_within_rounding(n):
    """The cost of running blocks in sequence for the conv policy: on
    9x9x3 boards its sum at A = 2 is not the full-width sum bit for bit
    (its per-env bits move with the vmap's width and the loop body), so
    a conv run continued onto another geometry leaves the bits of the
    first; the two sums agree to float32 rounding."""
    policy = _cnn()
    params = policy.init(jax.random.key(1))
    cfg = HTSConfig(alpha=5, n_envs=n, seed=3)
    traj = _random_traj(n, cfg.alpha, seed=n)
    got, want = (jax.jit(mesh_runtime.make_grad_sum_fn(
        policy.apply, cfg, A))(params, traj) for A in (2, 1))
    assert not _same_bits(got, want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(w).max()))


def _frames_traj(n, alpha, seed):
    """A random interval of 84x84x4 uint8 frames (levels 0-3), as the
    gridmaze84 env emits them."""
    rng = np.random.default_rng(seed)
    frames = lambda *lead: jnp.asarray(
        rng.integers(0, 4, lead + (84, 84, 4)), jnp.uint8)
    traj = _random_traj(n, alpha, seed)
    traj.update(obs=frames(alpha, n), bootstrap_obs=frames(n))
    return traj


def _frames_policy(name):
    return models.get_policy(name, gridmaze84.make())


@pytest.mark.parametrize("n", [8, 16])
@pytest.mark.parametrize("A", [2, 4])
@pytest.mark.parametrize("name", ["mlp", "cnn"])
def test_blocks_in_sequence_keep_the_sum(name, n, A):
    """Blocks of n / A envs run in sequence (a scan over blocks) on
    84x84x4 frames. The mlp policy's sum equals the one full-width vmap's
    bit for bit. The conv trunk's per-env bits move with the vmap's
    width, so its sum equals the strided formulation at the same blocks
    bit for bit, and the full-width sum to float32 rounding."""
    policy = _frames_policy(name)
    params = policy.init(jax.random.key(1))
    cfg = HTSConfig(alpha=5, n_envs=n, seed=3)
    traj = _frames_traj(n, cfg.alpha, seed=n + A)
    blocks = jax.jit(mesh_runtime.make_grad_sum_fn(policy.apply, cfg, A))
    flat = jax.jit(mesh_runtime.make_grad_sum_fn(policy.apply, cfg, 1))
    got, want = blocks(params, traj), flat(params, traj)
    if name == "mlp":
        assert _same_bits(got, want)
    else:
        strided = jax.jit(_old_grad_sum(policy.apply, cfg, A))
        assert _same_bits(got, strided(params, traj))
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(g, w, rtol=1e-4,
                                       atol=1e-5 * float(jnp.abs(w).max()))
    text = blocks.lower(params, traj).compile().as_text()
    assert "while" in text


@pytest.mark.parametrize("name", ["mlp", "cnn"])
def test_blocks_in_sequence_hold_one_blocks_stack(name):
    """The per-env stack dominates these programs (16 envs of 84x84x4
    frames: 14.5 MB of mlp and 6.7 MB of conv-trunk gradients per env);
    with four blocks in sequence the compiled program needs at most half
    the temporary memory of the one full-width vmap."""
    policy = _frames_policy(name)
    params = policy.init(jax.random.key(1))
    cfg = HTSConfig(alpha=5, n_envs=16, seed=3)
    traj = _frames_traj(16, cfg.alpha, seed=0)
    temp = {A: jax.jit(mesh_runtime.make_grad_sum_fn(policy.apply, cfg, A))
            .lower(params, traj).compile().memory_analysis()
            .temp_size_in_bytes for A in (1, 4)}
    stack = 16 * sum(x.size for x in jax.tree.leaves(params)) * 4
    assert temp[1] >= stack // 2, temp
    assert temp[4] <= temp[1] // 2, temp


# --------------------------------------------------- spec / fingerprint
def test_spec_validates_geometry_eagerly():
    with pytest.raises(ValueError, match="batch.n_replicas=3"):
        api.ExperimentSpec(runtime="sharded", hts={"n_envs": 8},
                           batch={"n_replicas": 3})


def test_fingerprint_default_popped_nondefault_kept():
    base = api.ExperimentSpec(runtime="mesh", hts={"n_envs": 8})
    fp_default = api.workload_fingerprint(base)
    assert "batch" not in fp_default     # committed baselines unchanged
    fp_r2 = api.workload_fingerprint(
        base.replace(batch={"n_replicas": 2}))
    assert fp_r2["batch"]["n_replicas"] == 2
    assert fp_default != fp_r2           # never compared across geometries


def test_baselines_reject_nondefault_batch():
    spec = api.ExperimentSpec(runtime="sync", hts={"n_envs": 8},
                              batch={"grad_accumulation": 2})
    with pytest.raises(ValueError, match="batch-geometry"):
        api.build(spec)


# -------------------------------------------------- in-process bit-exact
def test_mesh_factorization_cells_bitexact():
    """Fixed global batch: every (n_replicas, grad_accumulation) cell in
    {1,2}^2 produces the default geometry's params and episode-return
    streams bit-exactly (mesh = the single-process oracle)."""
    env1, cfg, papply, params, opt = _setup()
    base = engine.make_runtime("mesh", env1, papply, params, opt,
                               cfg).run(3)
    for R in (1, 2):
        for A in (1, 2):
            out = engine.make_runtime(
                "mesh", env1, papply, params, opt, cfg,
                batch={"n_replicas": R, "grad_accumulation": A}).run(3)
            assert _maxdiff(base.params, out.params) == 0.0, (R, A)
            np.testing.assert_array_equal(base.rewards, out.rewards)
            np.testing.assert_array_equal(base.dones, out.dones)


def test_host_accumulation_bitexact():
    env1, cfg, papply, params, opt = _setup()
    base = engine.make_runtime("host", env1, papply, params, opt,
                               cfg).run(3)
    out = engine.make_runtime("host", env1, papply, params, opt, cfg,
                              batch={"grad_accumulation": 2}).run(3)
    assert _maxdiff(base.params, out.params) == 0.0
    np.testing.assert_array_equal(base.rewards, out.rewards)


def test_sharded_replica_axis_sized_and_validated():
    env1, cfg, papply, params, opt = _setup()
    with pytest.raises(ValueError, match="n_replicas=2 but only"):
        # single visible device cannot host an explicit 2-replica axis
        engine.make_runtime("sharded", env1, papply, params, opt, cfg,
                            batch={"n_replicas": 2})
    from jax.sharding import Mesh
    mesh1 = Mesh(np.array(jax.devices()[:1]), ("data",))
    with pytest.raises(ValueError, match="mesh"):
        engine.make_runtime("sharded", env1, papply, params, opt, cfg,
                            mesh=mesh1, batch={"n_replicas": 2})


def test_trainer_manifest_records_geometry(tmp_path):
    env1, cfg, papply, params, opt = _setup()
    spec = api.ExperimentSpec(
        runtime="mesh", hts={"alpha": 5, "n_envs": 4, "seed": 3},
        optimizer={"name": "rmsprop",
                   "kwargs": {"lr": 7e-4, "eps": 1e-5}},
        checkpoint={"dir": str(tmp_path), "every": 2},
        batch={"grad_accumulation": 2})
    api.build(spec).fit(2)
    manifest = sorted(tmp_path.glob("step_*.json"))[-1]
    meta = json.loads(manifest.read_text())["metadata"]
    assert meta["batch"] == {"micro_batch": 2, "grad_accumulation": 2,
                             "n_replicas": 1, "global_batch": 4}
    # resume onto a DIFFERENT factorization: loud note, bit-exact result
    # (same global batch — the n_envs check pins that)
    resumed = api.build(spec.replace(batch={"grad_accumulation": 1}))
    out = resumed.fit(4, resume=True)
    straight = api.build(spec.replace(
        checkpoint={"dir": None}, batch=None)).fit(4)
    assert _maxdiff(out.params, straight.params) == 0.0


# ------------------------------------------------- 2-device (subprocess)
_TWO_DEVICE_SCRIPT = textwrap.dedent("""
    import numpy as np, jax, jax.numpy as jnp
    assert len(jax.devices()) == 2, jax.devices()
    from repro import models
    from repro.core import engine
    from repro.core.engine import HTSConfig
    from repro.envs import catch, gridmaze
    from repro.optim import rmsprop
    env1 = catch.make()
    cfg = HTSConfig(alpha=5, n_envs=4, seed=3)
    policy = models.get_policy("mlp", env1)
    papply = policy.apply
    params = policy.init(jax.random.key(0))
    opt = rmsprop(7e-4, eps=1e-5)
    def md(a, b):
        return max(float(jnp.max(jnp.abs(x - y))) for x, y in
                   zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    m = engine.make_runtime("mesh", env1, papply, params, opt, cfg).run(4)
    # (n_replicas=2) x (grad_accumulation 1, 2): real 2-device data
    # parallelism, bit-exact to the mesh oracle
    for A in (1, 2):
        s = engine.make_runtime(
            "sharded", env1, papply, params, opt, cfg,
            batch={"n_replicas": 2, "grad_accumulation": A}).run(4)
        assert np.array_equal(m.rewards, s.rewards), A
        assert md(m.params, s.params) == 0.0, (A, md(m.params, s.params))
    # checkpoint capsule round-trip onto a DIFFERENT replica count:
    # 2 mesh intervals -> capsule -> 2 more on 2-replica sharded
    rt1 = engine.make_runtime("mesh", env1, papply, params, opt, cfg)
    rt1.run(2)
    cap = rt1.state()
    rt2 = engine.make_runtime("sharded", env1, papply, params, opt, cfg,
                              batch={"n_replicas": 2})
    out = rt2.run_from(cap, 2)
    assert md(m.params, out.params) == 0.0, md(m.params, out.params)
    print("OK")
""")


def test_two_device_geometry_cells_and_restore():
    """The acceptance matrix on real devices: sharded (R=2) x (A in
    {1,2}) bit-exact to mesh, plus a capsule restored from a 1-replica
    mesh run onto a 2-replica sharded runtime continuing bit-exactly."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", _TWO_DEVICE_SCRIPT],
                       env=env, capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.strip().endswith("OK")


# --------------------------------------------- 2-process jax.distributed
def test_two_process_distributed_matches_mesh(tmp_path):
    """Two OS processes join a jax.distributed cluster
    (repro.launch.distributed; gloo CPU collectives) and run the same
    spec sharded over one global 2-device mesh: every process prints
    the SAME final-params sha256, equal to the 1-process mesh digest."""
    from repro.launch.distributed import params_digest
    spec = api.ExperimentSpec(
        runtime="sharded",
        hts={"alpha": 5, "n_envs": 4, "seed": 3},
        optimizer={"name": "rmsprop", "kwargs": {"lr": 7e-4,
                                                 "eps": 1e-5}},
        intervals=3, batch={"n_replicas": 2})
    path = tmp_path / "spec.json"
    api.save(spec, str(path))
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)       # 1 local device per process
    procs = [subprocess.Popen(
        [sys.executable, "-m", "repro.launch.distributed",
         "--spec", str(path), "--coordinator", f"127.0.0.1:{port}",
         "--num-processes", "2", "--process-id", str(i)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True) for i in range(2)]
    outs = [p.communicate(timeout=600) for p in procs]
    for p, (so, se) in zip(procs, outs):
        assert p.returncode == 0, se[-3000:]
    # the JSON record is the LAST stdout line: collective backends may
    # log connection lines to stdout before it
    digests = [json.loads(so.strip().splitlines()[-1])["params_sha256"]
               for so, _ in outs]
    assert digests[0] == digests[1]
    # ...and equals the single-process mesh run of the same workload
    mesh_out = api.build(
        spec.replace(runtime="mesh", batch=None)).run(3)
    assert digests[0] == params_digest(mesh_out.params)
