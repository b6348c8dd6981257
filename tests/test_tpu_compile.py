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


def test_paper_trunk_learner_runs_blocks_in_sequence(one_chip):
    """The fused learner half of the paper's trunk at its published
    widths (8x8/4, 4x4/2, 3x3/1, fc 512: 1,686,693 parameters) on 2048
    envs of 84x84x4 frames, with two gradient blocks of 1024 envs in
    sequence. The per-env stack of all 2048 envs is 13.8 GB; the
    compiled program holds one block's, and fits the chip."""
    from repro import envs, models, optim
    from repro.core import mesh_runtime
    from repro.core.engine import HTSConfig
    from repro.envs.device import batched_env
    env = envs.get_env("gridmaze84")
    policy = models.get_policy("cnn", env)
    opt = optim.get_optimizer("rmsprop", lr=7e-4, eps=1e-5)
    cfg = HTSConfig(alpha=5, n_envs=2048, env_backend="device")
    venv = batched_env(env, cfg.n_envs, cfg.env_backend)
    dg, _, _, traj, _ = jax.eval_shape(
        lambda: mesh_runtime.init_carry(
            policy.init(jax.random.key(0)), opt, venv, cfg, policy.apply))
    n_params = sum(x.size for x in jax.tree.leaves(dg.params))
    assert n_params == 1_686_693
    learn = mesh_runtime.make_learner_update(policy.apply, opt, cfg,
                                             grad_accumulation=2)
    skip = jax.ShapeDtypeStruct((), jnp.bool_, sharding=one_chip)
    compiled = jax.jit(learn).lower(_on(one_chip, dg), _on(one_chip, traj),
                                    skip).compile()
    mem = compiled.memory_analysis()
    block = 1024 * n_params * 4
    # one block's fc gradients (1024 x 3136 x 512 x 4 B) are held whole;
    # a second block's stack is never live beside the first
    assert mem.temp_size_in_bytes >= 1024 * 3136 * 512 * 4
    assert mem.temp_size_in_bytes < 2 * block
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < 14e9


def test_checkpoint_pack_gives_rows_of_words(one_chip):
    """``checkpoint/io``'s pack program on the capsule of the paper's
    trunk at 2048 envs of 84x84x4 frames: every output is (n, 128)
    uint32 in row-major (8, 128) tiles, a layout the device-to-host
    copy moves as it lies, and the program fits the chip."""
    from repro import envs, models, optim
    from repro.checkpoint import io as ckpt_io
    from repro.core import mesh_runtime
    from repro.core.engine import HTSConfig
    from repro.envs.device import batched_env
    env = envs.get_env("gridmaze84")
    policy = models.get_policy("cnn", env)
    opt = optim.get_optimizer("rmsprop", lr=7e-4, eps=1e-5)
    cfg = HTSConfig(alpha=5, n_envs=2048, env_backend="device")
    venv = batched_env(env, cfg.n_envs, cfg.env_backend)
    carry = jax.eval_shape(
        lambda: mesh_runtime.init_carry(
            policy.init(jax.random.key(0)), opt, venv, cfg, policy.apply))
    leaves = jax.tree.leaves(_on(one_chip, carry))
    packed = [x for x in leaves if ckpt_io.packs(x)]
    n_bytes, _ = ckpt_io.split(leaves)
    compiled = ckpt_io._pack.lower(packed).compile()
    for fmt in compiled.output_formats:
        assert fmt.layout.major_to_minor == (0, 1)
        assert fmt.layout.tiling == ((8, 128),)
    outs = jax.eval_shape(ckpt_io._pack, packed)
    assert all(o.dtype == jnp.uint32 and o.shape[1:] == (128,)
               for o in outs)
    assert sum(o.size * 4 for o in outs) == n_bytes
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < 4e9
