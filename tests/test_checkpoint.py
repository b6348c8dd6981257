"""Checkpoint io: the corruption/error taxonomy (DESIGN.md §11).

Two disjoint failure families, because they demand different responses:

* ``CheckpointCorrupt`` (RuntimeError) — the BYTES cannot be trusted:
  torn capsule (manifest without npz), truncated/corrupt archive, a
  leaf failing its manifest crc32. Survivable: supervisors fall back to
  an older complete checkpoint (core/trainer.Trainer does).
* ``ValueError`` — the STRUCTURE disagrees with the restore template:
  leaf count, tree shape, leaf shapes, dtypes, a missing manifest.
  A caller error no amount of retrying fixes.

Plus the selection helpers (``complete_checkpoints`` / ``latest`` skip
torn capsules), the ``restore_prefix`` error paths serving relies on,
and the save's transfer: leaves moved as (n, 128) words write the
bytes, shapes, dtypes and checksums a leaf-by-leaf ``device_get`` would.
"""
import json
import os
import subprocess
import sys
import textwrap
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.checkpoint import io as ckpt_io


def _tree(seed=0):
    rng = np.random.RandomState(seed)
    return {"b": rng.randn(3).astype(np.float32),
            "w": rng.randn(4, 3).astype(np.float32),
            "extra": rng.randn(2, 2).astype(np.float32)}


def _save(tmp_path, name="step_00000001", tree=None):
    path = str(tmp_path / name)
    ckpt_io.save(path, tree if tree is not None else _tree(),
                 metadata={"intervals": 1})
    return path


# ------------------------------------------------------------- checksums
def test_manifest_records_per_leaf_crc32(tmp_path):
    path = _save(tmp_path)
    m = ckpt_io.load_manifest(path)
    assert len(m["crc32"]) == m["n_leaves"] == 3
    restored = ckpt_io.restore(path, _tree(1))
    for k, want in _tree().items():
        np.testing.assert_array_equal(np.asarray(restored[k]), want)


def test_truncated_npz_raises_checkpoint_corrupt(tmp_path):
    path = _save(tmp_path)
    npz = path + ".npz"
    with open(npz, "r+b") as f:
        size = f.seek(0, os.SEEK_END)
        f.truncate(size // 2)
    with pytest.raises(ckpt_io.CheckpointCorrupt):
        ckpt_io.restore(path, _tree())


def test_modified_leaf_fails_its_checksum(tmp_path):
    """Content corruption the zip layer cannot see — the npz rewritten
    internally consistent but with one leaf's values changed — is
    exactly what the manifest's per-leaf crc32 exists to catch."""
    path = _save(tmp_path)
    npz = path + ".npz"
    arrays = dict(np.load(npz))
    arrays["leaf_1"] = arrays["leaf_1"] + 1.0
    with open(npz, "wb") as f:
        np.savez(f, **arrays)
    with pytest.raises(ckpt_io.CheckpointCorrupt, match="checksum"):
        ckpt_io.restore(path, _tree())


def test_missing_npz_is_torn_not_selectable(tmp_path):
    old = _save(tmp_path, "step_00000001")
    torn = _save(tmp_path, "step_00000002")
    os.remove(torn + ".npz")
    # restore of the torn capsule: corrupt (survivable), naming the tear
    with pytest.raises(ckpt_io.CheckpointCorrupt, match="torn"):
        ckpt_io.restore(torn, _tree())
    # selection skips it entirely: latest() is the older COMPLETE one
    assert ckpt_io.complete_checkpoints(str(tmp_path)) == [old]
    assert ckpt_io.latest(str(tmp_path)) == old


def test_complete_checkpoints_newest_first(tmp_path):
    paths = [_save(tmp_path, f"step_{i:08d}") for i in (1, 2, 3)]
    assert ckpt_io.complete_checkpoints(str(tmp_path)) == paths[::-1]
    assert ckpt_io.complete_checkpoints(str(tmp_path / "nowhere")) == []


def test_corrupt_is_not_a_valueerror():
    """The taxonomy is load-bearing: supervisors catch CheckpointCorrupt
    (fall back) while letting ValueError (config mismatch) propagate."""
    assert issubclass(ckpt_io.CheckpointCorrupt, RuntimeError)
    assert not issubclass(ckpt_io.CheckpointCorrupt, ValueError)


# ------------------------------------------------------ structural errors
def test_restore_validates_structure_loudly(tmp_path):
    path = _save(tmp_path)
    with pytest.raises(ValueError, match="leaves"):
        ckpt_io.restore(path, {"only": np.zeros(3, np.float32)})
    bad_shape = dict(_tree(), w=np.zeros((5, 3), np.float32))
    with pytest.raises(ValueError, match="shape"):
        ckpt_io.restore(path, bad_shape)


# ---------------------------------------------------- restore_prefix paths
def _prefix_template():
    t = _tree()
    return {"b": t["b"], "extra": t["extra"]}   # first 2 of 3 flat leaves


def test_restore_prefix_happy_path(tmp_path):
    path = _save(tmp_path)
    got = ckpt_io.restore_prefix(path, _prefix_template())
    want = _tree()
    np.testing.assert_array_equal(np.asarray(got["b"]), want["b"])
    np.testing.assert_array_equal(np.asarray(got["extra"]), want["extra"])


def test_restore_prefix_requires_manifest(tmp_path):
    path = _save(tmp_path)
    os.remove(path + ".json")
    with pytest.raises(ValueError, match="no manifest"):
        ckpt_io.restore_prefix(path, _prefix_template())


def test_restore_prefix_requires_n_leaves_field(tmp_path):
    path = _save(tmp_path)
    m = ckpt_io.load_manifest(path)
    del m["n_leaves"]
    with open(path + ".json", "w") as f:
        json.dump(m, f)
    with pytest.raises(ValueError, match="n_leaves"):
        ckpt_io.restore_prefix(path, _prefix_template())


def test_restore_prefix_template_larger_than_capsule(tmp_path):
    path = _save(tmp_path)
    big = dict(_tree(), more=np.zeros(2, np.float32))
    with pytest.raises(ValueError, match="needs"):
        ckpt_io.restore_prefix(path, big)


def test_restore_prefix_shape_mismatch(tmp_path):
    path = _save(tmp_path)
    bad = dict(_prefix_template(), b=np.zeros(7, np.float32))
    with pytest.raises(ValueError, match="prefix leaf"):
        ckpt_io.restore_prefix(path, bad)


def test_restore_prefix_dtype_mismatch(tmp_path):
    path = _save(tmp_path)
    bad = {k: v.astype(np.float64)
           for k, v in _prefix_template().items()}
    with pytest.raises(ValueError, match="dtype"):
        ckpt_io.restore_prefix(path, bad)


def test_restore_prefix_corrupt_leaf_is_checkpoint_corrupt(tmp_path):
    path = _save(tmp_path)
    os.remove(path + ".npz")
    with pytest.raises(ckpt_io.CheckpointCorrupt, match="torn"):
        ckpt_io.restore_prefix(path, _prefix_template())


# ---------------------------------------------------- the save's transfer
def _kinds():
    """One leaf of each kind the capsules hold, by name: (leaf, whether
    ``save`` moves it as words)."""
    rng = np.random.RandomState(0)
    return {
        # a frame stack of 64 envs, uint8 with minor dimension 4
        "uint8_frames": (jnp.asarray(rng.randint(0, 256, (64, 84, 84, 4)),
                                     jnp.uint8), True),
        # float32 boards of minor dimension 3
        "float32_boards": (jnp.asarray(rng.randn(1024, 9, 9, 3),
                                       jnp.float32), True),
        "bfloat16": (jnp.asarray(rng.randn(64, 128), jnp.bfloat16), True),
        "int8": (jnp.asarray(rng.randint(-128, 128, (4096,)), jnp.int8),
                 True),
        "int32_scalar": (jnp.int32(7), False),
        # 3 x 84 x 4 bytes: no whole tile of words
        "odd_size": (jnp.asarray(rng.randint(0, 256, (3, 84, 4)),
                                 jnp.uint8), False),
        "float32_bias": (jnp.asarray(rng.randn(512), jnp.float32), False),
        "numpy": (rng.randn(16, 128).astype(np.float32), False),
    }


def _plain(leaf) -> np.ndarray:
    """What the leaf-by-leaf save stored for ``leaf``."""
    arr = np.asarray(jax.device_get(leaf))
    return arr.astype(np.float32) if arr.dtype.name == "bfloat16" else arr


@pytest.mark.parametrize("kind", sorted(_kinds()) + ["all"])
def test_save_stores_what_the_plain_transfer_gives(tmp_path, kind):
    kinds = _kinds()
    names = sorted(kinds) if kind == "all" else [kind]
    tree = {n: kinds[n][0] for n in names}
    assert [ckpt_io.packs(tree[n]) for n in names] == [kinds[n][1]
                                                       for n in names]
    path = str(tmp_path / "step_00000001")
    ckpt_io.save(path, tree)
    leaves = jax.tree.leaves(tree)
    stored = np.load(path + ".npz")
    manifest = ckpt_io.load_manifest(path)
    for i, leaf in enumerate(leaves):
        want = _plain(leaf)
        got = stored[f"leaf_{i}"]
        assert (got.dtype, got.shape) == (want.dtype, want.shape)
        assert got.tobytes() == want.tobytes()
        assert manifest["crc32"][i] == zlib.crc32(want.tobytes())
        assert manifest["dtypes"][i] == str(np.asarray(leaf).dtype)
    restored = jax.tree.leaves(ckpt_io.restore(path, tree))
    for leaf, back in zip(leaves, restored):
        want = np.asarray(jax.device_get(leaf))
        back = np.asarray(back)
        assert back.dtype == want.dtype
        assert back.tobytes() == want.tobytes()


def test_save_moves_every_leaf_in_one_transfer(tmp_path, monkeypatch):
    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: calls.append(x) or real(x))
    tree = {n: leaf for n, (leaf, _) in _kinds().items()}
    ckpt_io.save(str(tmp_path / "step_00000001"), tree)
    assert len(calls) == 1 and len(calls[0]) == len(tree)


def _old_save(path, tree):
    """The leaf-by-leaf save that wrote format 1 before the batched
    transfer: one ``device_get`` per leaf, crc32 of ``.tobytes()``."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    arrays, dtypes, shapes, crcs = {}, [], [], []
    for i, a in enumerate(leaves):
        arr = np.asarray(jax.device_get(a))
        dtypes.append(str(arr.dtype))
        shapes.append(list(arr.shape))
        if arr.dtype.name == "bfloat16":
            arr = arr.astype(np.float32)
        crcs.append(zlib.crc32(np.ascontiguousarray(arr).tobytes()))
        arrays[f"leaf_{i}"] = arr
    with open(path + ".npz", "wb") as f:
        np.savez(f, **arrays)
    with open(path + ".json", "w") as f:
        json.dump({"version": 1, "n_leaves": len(leaves),
                   "treedef": str(treedef), "dtypes": dtypes,
                   "shapes": shapes, "crc32": crcs, "metadata": {}}, f)


def test_checkpoint_of_the_leaf_by_leaf_save_restores_unchanged(tmp_path):
    tree = {n: leaf for n, (leaf, _) in _kinds().items()}
    old = str(tmp_path / "step_00000001")
    _old_save(old, tree)
    restored = ckpt_io.restore(old, tree)
    for leaf, back in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        want = np.asarray(jax.device_get(leaf))
        assert np.asarray(back).tobytes() == want.tobytes()
    # and the new save writes the same manifest and members
    new = str(tmp_path / "step_00000002")
    ckpt_io.save(new, tree)
    assert ckpt_io.load_manifest(new) == ckpt_io.load_manifest(old)
    a, b = np.load(old + ".npz"), np.load(new + ".npz")
    assert a.files == b.files
    for k in a.files:
        assert (a[k].dtype, a[k].shape) == (b[k].dtype, b[k].shape)
        assert a[k].tobytes() == b[k].tobytes()


def _fused_spec(env, sizes, strides, grad_accumulation):
    """The sharded runtime at 2048 device envs with the paper's conv
    trunk, as the benchmark's two fused cells run it."""
    return {"env": {"name": env},
            "policy": {"name": "cnn", "kwargs": {
                "conv_filters": [32, 64, 64], "conv_sizes": sizes,
                "conv_strides": strides, "hidden": 512}},
            "optimizer": {"name": "rmsprop", "kwargs": {
                "lr": 7e-4, "eps": 1e-5, "decay": 0.99}},
            "algorithm": "a2c",
            "hts": {"alpha": 5, "n_envs": 2048, "env_backend": "device"},
            "runtime": {"name": "sharded"},
            "batch": {"n_replicas": 1,
                      "grad_accumulation": grad_accumulation}}


@pytest.mark.parametrize("spec,n_leaves,n_bytes", [
    (_fused_spec("gridmaze", [3, 3, 3], [1, 1, 1], 1), 48, 18_374_724),
    (_fused_spec("gridmaze84", [8, 4, 3], [4, 2, 1], 2), 50, 425_097_156),
], ids=["gridmaze", "gridmaze84"])
def test_fused_capsules_move_as_words(spec, n_leaves, n_bytes):
    """The capsule's shapes alone (no arrays): at least 99% of its bytes
    move as words, the frames and the weights among them."""
    rt = api.build(api.loads(json.dumps(spec))).runtime
    capsule = jax.eval_shape(lambda: rt._carry_to_state(rt._initial_carry()))
    leaves = jax.tree.leaves(capsule)
    packed, plain = ckpt_io.split(leaves)
    assert (len(leaves), packed + plain) == (n_leaves, n_bytes)
    assert packed >= 0.99 * n_bytes
    assert ckpt_io.packs(capsule.obs)
    assert all(ckpt_io.packs(b) for b in jax.tree.leaves(capsule.buffer)
               if b.size * b.dtype.itemsize >= 1 << 20)


_SHARDED_SCRIPT = textwrap.dedent("""
    import tempfile
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.checkpoint import io as ckpt_io
    assert len(jax.devices()) == 4, jax.devices()
    mesh = Mesh(np.array(jax.devices()), ("data",))
    frames = np.random.RandomState(0).randint(0, 256, (64, 84, 84, 4))
    tree = {"sharded": jax.device_put(jnp.asarray(frames, jnp.uint8),
                                      NamedSharding(mesh, P("data"))),
            "one": jnp.asarray(frames[:64], jnp.uint8)}
    assert not ckpt_io.packs(tree["sharded"]) and ckpt_io.packs(tree["one"])
    n = frames.size
    assert ckpt_io.split(jax.tree.leaves(tree)) == (n, n)
    path = tempfile.mkdtemp() + "/step_00000001"
    ckpt_io.save(path, tree)
    back = ckpt_io.restore(path, tree)
    for k in tree:
        assert np.asarray(back[k]).tobytes() == frames.astype(np.uint8).tobytes()
    print("OK")
""")


def test_leaf_sharded_over_four_devices_moves_plain_and_restores():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    r = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.startswith("OK")
