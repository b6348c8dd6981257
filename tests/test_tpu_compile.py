"""Compile rehearsal for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode accepts: block shapes off
the (8, 128) tiling, dynamic offsets into packed tiles, loop forms
Mosaic cannot lower, programs that do not fit. Each test compiles one
main-path program at real widths for one chip of a described
``v5e:2x2`` and checks what the compiler produced. Nothing runs.

The topology is described only inside the module fixture, never while
a module is imported: the TPU library may be loaded by one process at a
time, and every test worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for an absent chip is written to the persistent cache
    # but cannot be read back, so keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _compile_kernel(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_flash_attention_compiles_starcoder2_3b(one_chip):
    from repro.kernels.flash_attention.ops import attend
    B, S, H, KV, Dh = 1, 2048, 24, 2, 128
    q = jax.ShapeDtypeStruct((B, S, H, Dh), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B, S, KV, Dh), jnp.bfloat16,
                              sharding=one_chip)
    _compile_kernel(
        lambda q, k, v: attend(q, k, v, use_pallas=True, interpret=False),
        q, kv, kv)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_lru_scan_compiles_recurrentgemma_9b(one_chip, dtype):
    from repro.kernels.lru_scan.ops import scan
    B, S, D = 4, 2048, 4096
    ab = jax.ShapeDtypeStruct((B, S, D), dtype, sharding=one_chip)
    h0 = jax.ShapeDtypeStruct((B, D), jnp.float32, sharding=one_chip)
    _compile_kernel(
        lambda a, b, h: scan(a, b, h, use_pallas=True, interpret=False),
        ab, ab, h0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_wkv6_compiles_rwkv6_7b(one_chip, dtype):
    from repro.kernels.wkv6.ops import mix
    B, T, H, N = 1, 1024, 64, 64
    rkv = jax.ShapeDtypeStruct((B, T, H, N), dtype, sharding=one_chip)
    w = jax.ShapeDtypeStruct((B, T, H, N), jnp.float32, sharding=one_chip)
    u = jax.ShapeDtypeStruct((H, N), jnp.float32, sharding=one_chip)
    s0 = jax.ShapeDtypeStruct((B, H, N, N), jnp.float32, sharding=one_chip)
    _compile_kernel(
        lambda r, k, v, w, u, s: mix(r, k, v, w, u, s, use_pallas=True,
                                     interpret=False),
        rkv, rkv, rkv, w, u, s0)


def test_cnn_learner_update_compiles_at_full_width(one_chip):
    """The fused learner half at the main path's size: the paper's conv
    policy (32/64/64 filters, fc 512) on gridmaze, n_envs=512, alpha=5 —
    per-env fp32 gradients of 354,309 params before the tree sum."""
    from repro import envs, models, optim
    from repro.core import mesh_runtime
    from repro.core.engine import HTSConfig
    from repro.envs.device import batched_env
    env = envs.get_env_factory("gridmaze")()
    policy = models.get_policy("cnn", env, conv_sizes=(3, 3, 3),
                               conv_strides=(1, 1, 1))
    opt = optim.get_optimizer("rmsprop", lr=7e-4, eps=1e-5)
    cfg = HTSConfig(alpha=5, n_envs=512, env_backend="device")
    venv = batched_env(env, cfg.n_envs, cfg.env_backend)
    dg, _, _, traj, _ = jax.eval_shape(
        lambda: mesh_runtime.init_carry(
            policy.init(jax.random.key(0)), opt, venv, cfg, policy.apply))
    assert sum(x.size for x in jax.tree.leaves(dg.params)) == 354_309
    learn = mesh_runtime.make_learner_update(policy.apply, opt, cfg)
    skip = jax.ShapeDtypeStruct((), jnp.bool_, sharding=one_chip)
    compiled = jax.jit(learn).lower(_on(one_chip, dg), _on(one_chip, traj),
                                    skip).compile()
    mem = compiled.memory_analysis()
    print(mem)
    # the per-env gradient stack is 512 x 354,309 x 4 B. The tree sum's
    # first pass may fuse into the producers of its smaller leaves, but
    # the fc layer's per-env gradients (512 x 576 x 512 x 4 B) are held
    # whole, and the sum holds no second copy of the stack
    assert mem.temp_size_in_bytes >= 512 * 576 * 512 * 4
    assert mem.temp_size_in_bytes <= 512 * 354_309 * 4
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < 16e9
