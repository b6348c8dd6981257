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
    count;
  * multi-process — a 2-process ``jax.distributed`` run
    (repro.launch.distributed) produces the single-process mesh
    digest, bit-exact, on both processes.
"""
import json
import os
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
from repro.core.batch import BatchConfig, pairwise_tree_sum
from repro.core.engine import HTSConfig
from repro.envs import catch
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
    from repro.envs import catch
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
