"""Checkpointing: flat .npz with a versioned pytree manifest (orbax is
not available offline; this is self-contained and deterministic).

A checkpoint is two files: ``<path>.npz`` with the leaves and
``<path>.json`` with the manifest — format version, the flattened
treedef, per-leaf dtypes/shapes, and caller metadata. ``restore``
validates leaf count, tree structure, shapes, and dtypes against the
``like`` template and fails with a precise error instead of silently
unflattening mismatched leaves in flatten order.

Works on any pure-array pytree: a full ``DelayedGradState`` (params,
params_prev — the behavior snapshot matters: restoring only params would
silently reset the one-step delay), or an engine ``TrainState`` capsule
(core/engine.py). Sharded ``jax.Array`` leaves (e.g. from the sharded
runtime's shard_map programs) are gathered with ``jax.device_get`` before
writing, so a checkpoint taken on an N-device mesh restores on any
device count.

``save`` moves every leaf to the host in one batched ``device_get``.
A leaf on one device whose bytes fill whole (8, 128) tiles of 32-bit
words goes as such words, shaped (n, 128). A TPU lays an array out in
tiles, and for a small minor dimension it may make another one minor:
a uint8 frame stack (n_envs, 84, 84, 4) lies with its env axis minor,
so a row-major host copy of it is reordered byte by byte on the host.
(n, 128) words lie row-major on the device and are copied as they lie.
The host views the words back as the leaf (no copy); the file is the
same either way.
"""
from __future__ import annotations

import json
import os
import zlib
from pathlib import Path
from typing import Any

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from repro.core import spans

FORMAT_VERSION = 1
# one (8, 128) tile of 32-bit words, in bytes
_TILE_BYTES = 8 * 128 * 4


class CheckpointCorrupt(RuntimeError):
    """The checkpoint's BYTES cannot be trusted: the npz half is
    unreadable (truncated write, disk corruption, a copy that dropped
    bytes) or a leaf's content fails its manifest checksum. Distinct
    from ValueError (structural mismatch against the restore template —
    wrong config, wrong model), because the two demand different
    responses: corruption is survivable by falling back to an older
    complete checkpoint (core/trainer.Trainer does exactly that), a
    structural mismatch is a caller error no amount of retrying fixes."""


def _nbytes(leaf) -> int:
    if not hasattr(leaf, "dtype"):          # a python scalar
        leaf = np.asarray(leaf)
    return int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize


def packs(leaf) -> bool:
    """Whether ``save`` moves ``leaf`` (a ``jax.Array`` or a
    ``ShapeDtypeStruct``) to the host as (n, 128) 32-bit words: one
    device holds it, its dtype is an int, uint or float of 1, 2 or 4
    bytes, and its bytes fill whole (8, 128) tiles of words. Sharded
    leaves and numpy leaves go as they are."""
    if not isinstance(leaf, (jax.Array, jax.ShapeDtypeStruct)):
        return False
    sharding = getattr(leaf, "sharding", None)
    if sharding is not None and len(sharding.device_set) != 1:
        return False
    dtype = np.dtype(leaf.dtype)
    nbytes = _nbytes(leaf)
    return ((dtype.kind in "iuf" or dtype == jnp.bfloat16)
            and dtype.itemsize in (1, 2, 4)
            and nbytes > 0 and nbytes % _TILE_BYTES == 0)


def split(leaves) -> tuple[int, int]:
    """(packed, plain) bytes of ``leaves``, as ``packs`` divides them."""
    packed = plain = 0
    for leaf in leaves:
        nbytes = _nbytes(leaf)
        if packs(leaf):
            packed += nbytes
        else:
            plain += nbytes
    return packed, plain


def _words(x):
    """``x`` as (n, 128) uint32, 4 bytes or 2 half-words to a word in
    the order ``lax.bitcast_convert_type`` groups them (the host's
    ``.view`` order)."""
    per = 4 // x.dtype.itemsize
    if per > 1:
        x = x.reshape(-1, per)
    return lax.bitcast_convert_type(x, jnp.uint32).reshape(-1, 128)


# one program per capsule signature: jit caches it by the leaves'
# shapes, dtypes and shardings
_pack = jax.jit(lambda leaves: [_words(x) for x in leaves])


def _gather(leaves) -> list[np.ndarray]:
    """Every leaf as a host array, through one ``device_get`` over the
    list, so the copies are issued together; the leaves that ``packs``
    go as words and are viewed back as themselves."""
    packed = [i for i, leaf in enumerate(leaves) if packs(leaf)]
    sent = list(leaves)
    if packed:
        for i, w in zip(packed, _pack([leaves[i] for i in packed])):
            sent[i] = w
    got = [np.asarray(a) for a in jax.device_get(sent)]
    for i in packed:
        got[i] = got[i].view(leaves[i].dtype).reshape(leaves[i].shape)
    return got


def save(path: str, tree: Any, metadata: dict | None = None) -> None:
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    packed, plain = split(leaves)
    with spans.span(spans.FIT_SAVE_GATHER, packed_bytes=packed,
                    plain_bytes=plain):
        host = _gather(leaves)
    # numpy's savez has no bf16 cast path: store bf16 leaves as f32
    # (lossless upcast) and restore back to the reference dtype.
    arrays = {}
    dtypes, shapes, crcs = [], [], []
    for i, arr in enumerate(host):
        dtypes.append(str(arr.dtype))
        shapes.append(list(arr.shape))
        if arr.dtype.name == "bfloat16":
            arr = arr.astype(np.float32)
        # per-leaf checksum of the STORED bytes (post-upcast), so a
        # flipped bit or truncated page inside the zip is detected at
        # restore as CheckpointCorrupt naming the leaf, not as silently
        # wrong parameters. crc32 reads the buffer in place: the value
        # of ``.tobytes()``, without its copy
        crcs.append(zlib.crc32(
            np.ascontiguousarray(arr).reshape(-1).view(np.uint8)))
        arrays[f"leaf_{i}"] = arr
    manifest = {
        "version": FORMAT_VERSION,
        "n_leaves": len(leaves),
        "treedef": str(treedef),
        "dtypes": dtypes,
        "shapes": shapes,
        "crc32": crcs,
        "metadata": metadata or {},
    }
    # both files go through write-tmp + atomic rename, npz before
    # manifest: a kill mid-save leaves either no .json (fresh path — so
    # latest(), which globs manifests, never selects it) or, when
    # overwriting an existing checkpoint, the intact OLD npz/json pair —
    # never a torn npz behind a valid manifest
    npz_tmp = path.with_suffix(".npz.tmp")
    with open(npz_tmp, "wb") as f:
        np.savez(f, **arrays)
    os.replace(npz_tmp, path.with_suffix(".npz"))
    json_tmp = path.with_suffix(".json.tmp")
    json_tmp.write_text(json.dumps(manifest, indent=1))
    os.replace(json_tmp, path.with_suffix(".json"))


def _open_npz(path: Path):
    """np.load with byte-level failures surfaced as CheckpointCorrupt
    (a truncated/corrupt zip raises half a dozen different exception
    types depending on WHERE the damage sits; callers need one)."""
    try:
        return np.load(path.with_suffix(".npz"))
    except FileNotFoundError:
        raise CheckpointCorrupt(
            f"checkpoint {path.name} is torn: manifest present but "
            f"{path.with_suffix('.npz').name} is missing") from None
    except Exception as e:      # zipfile.BadZipFile, OSError, EOFError...
        raise CheckpointCorrupt(
            f"checkpoint {path.with_suffix('.npz').name} is unreadable "
            f"(truncated or corrupt): {e!r}") from e


def _read_leaf(data, path: Path, i: int, crcs) -> np.ndarray:
    """Extract leaf i, decompressing its bytes now (np.load is lazy —
    corruption inside the zip only surfaces on member access) and
    verifying its manifest checksum when one was recorded."""
    try:
        arr = data[f"leaf_{i}"]
    except KeyError:
        raise CheckpointCorrupt(
            f"checkpoint {path.name} has no leaf_{i} array — the npz "
            f"member list is damaged or the file was truncated") from None
    except Exception as e:      # zlib.error mid-member, struct errors...
        raise CheckpointCorrupt(
            f"checkpoint {path.name} leaf_{i} is unreadable (corrupt "
            f"bytes inside the archive): {e!r}") from e
    if crcs is not None and i < len(crcs):
        got = zlib.crc32(np.ascontiguousarray(arr).tobytes())
        if got != crcs[i]:
            raise CheckpointCorrupt(
                f"checkpoint {path.name} leaf_{i} fails its checksum "
                f"(manifest crc32={crcs[i]}, stored bytes={got}) — the "
                f"array content was corrupted after the write")
    return arr


def load_manifest(path: str) -> dict | None:
    p = Path(path).with_suffix(".json")
    return json.loads(p.read_text()) if p.exists() else None


def load_metadata(path: str) -> dict:
    """The caller-supplied metadata dict saved alongside the arrays."""
    m = load_manifest(path)
    return (m or {}).get("metadata", {})


def restore(path: str, like: Any) -> Any:
    """Restore into the structure of ``like`` (an equal-structure pytree
    of arrays or ShapeDtypeStructs). Tree structure, leaf count, shapes,
    and dtypes are all validated against both the template and the
    manifest before a single leaf is unflattened."""
    path = Path(path)
    data = _open_npz(path)
    leaves, treedef = jax.tree_util.tree_flatten(like)
    manifest = load_manifest(path)
    if manifest is not None:
        n = manifest.get("n_leaves")
        if n is not None and n != len(leaves):
            raise ValueError(
                f"checkpoint {path.name} has {n} leaves but the restore "
                f"template has {len(leaves)} — the pytree structure "
                f"changed (different model/optimizer/runtime config?)")
        want = manifest.get("treedef")
        if want is not None and want != str(treedef):
            raise ValueError(
                f"checkpoint {path.name} tree structure mismatch:\n"
                f"  saved:    {want}\n  template: {treedef}")
    if len(data.files) != len(leaves):
        raise ValueError(
            f"checkpoint {path.name} holds {len(data.files)} arrays but "
            f"the restore template has {len(leaves)} leaves")
    crcs = (manifest or {}).get("crc32")
    out = []
    for i, ref in enumerate(leaves):
        arr = _read_leaf(data, path, i, crcs)
        if tuple(arr.shape) != tuple(ref.shape):
            # a staleness-K capsule differs from a staleness-K' one only
            # in ring depth: same pytree, leading axes off by the ring
            # length. Diagnose that case specifically — it is the config
            # mismatch users actually hit.
            hint = ""
            if (tuple(arr.shape[1:]) == tuple(ref.shape)
                    or tuple(arr.shape) == tuple(ref.shape[1:])
                    or (arr.ndim == ref.ndim and arr.ndim > 0
                        and tuple(arr.shape[1:]) == tuple(ref.shape[1:]))):
                hint = (" — only the leading (ring) axis differs; was "
                        "this checkpoint written with a different "
                        "staleness than the restoring runtime's?")
            raise ValueError(
                f"leaf {i}: checkpoint shape {arr.shape} != {ref.shape}"
                f"{hint}")
        if manifest is not None:
            saved_dt = manifest.get("dtypes", [None] * len(leaves))[i]
            if saved_dt is not None and saved_dt != str(ref.dtype):
                raise ValueError(
                    f"leaf {i}: checkpoint dtype {saved_dt} != template "
                    f"dtype {ref.dtype}")
        out.append(jnp.asarray(arr).astype(ref.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def complete_checkpoints(dirpath: str) -> list[str]:
    """All COMPLETE checkpoints in ``dirpath`` (``step_*.json`` whose
    ``.npz`` half exists), newest first. A manifest without its array
    file is a torn capsule — a kill between the two halves of a
    save/prune, or a copy that dropped the npz — and selecting it would
    make resume crash instead of falling back to the previous complete
    checkpoint. "Complete" here means both files exist; content
    corruption (failed checksum, damaged zip) surfaces at ``restore`` as
    CheckpointCorrupt, and supervisors walk this list newest-first to
    fall back past it (core/trainer.Trainer)."""
    d = Path(dirpath)
    if not d.exists():
        return []
    return [str(p.with_suffix(""))
            for p in sorted(d.glob("step_*.json"), reverse=True)
            if p.with_suffix(".npz").exists()]


def latest(dirpath: str) -> str | None:
    """Newest complete checkpoint in ``dirpath``, or None."""
    found = complete_checkpoints(dirpath)
    return found[0] if found else None


def restore_prefix(path: str, like: Any) -> Any:
    """Restore the FIRST ``len(leaves(like))`` leaves of a checkpoint
    into the structure of ``like`` — the params-only read serving uses
    (repro.serve) on a full ``TrainState`` capsule.

    This leans on a structural invariant of the capsule formats, pinned
    by tests/test_serve.py: params are the first field of every
    update-rule state (``DelayedGradState.params`` for the HTS family,
    element 0 of the baselines' tuples) and ``algo`` is the first field
    of ``TrainState``, so in flatten order the policy parameters are
    exactly the leading leaves — for every runtime and every staleness
    (the K-ring lives in ``params_prev``, after them). Shapes and
    dtypes are validated leaf-by-leaf against the template, so a capsule
    whose layout does NOT start with ``like`` fails loudly here.

    Error taxonomy (pinned by tests/test_checkpoint.py): a missing or
    unreadable npz / failed leaf checksum raises ``CheckpointCorrupt``;
    a missing manifest, a manifest without ``n_leaves``, too few leaves
    for the template, or a shape/dtype mismatch raises ``ValueError``
    naming what disagreed."""
    path = Path(path)
    manifest = load_manifest(path)
    if manifest is None:
        raise ValueError(
            f"checkpoint {path.name} has no manifest "
            f"({path.with_suffix('.json').name} is missing) — cannot "
            f"validate a prefix restore against an unmanifested capsule")
    n = manifest.get("n_leaves")
    if n is None:
        raise ValueError(
            f"checkpoint {path.name} manifest is missing the "
            f"'n_leaves' field (present: {sorted(manifest)})")
    leaves, treedef = jax.tree_util.tree_flatten(like)
    if n < len(leaves):
        raise ValueError(
            f"checkpoint {path.name} holds {n} arrays but the prefix "
            f"template needs {len(leaves)} leaves")
    data = _open_npz(path)
    crcs = manifest.get("crc32")
    dtypes = manifest.get("dtypes", [None] * n)
    out = []
    for i, ref in enumerate(leaves):
        arr = _read_leaf(data, path, i, crcs)
        if tuple(arr.shape) != tuple(ref.shape):
            raise ValueError(
                f"prefix leaf {i}: checkpoint shape {arr.shape} != "
                f"template {tuple(ref.shape)} — the capsule's leading "
                f"leaves are not this policy's parameters (different "
                f"model config?)")
        if dtypes[i] is not None and dtypes[i] != str(ref.dtype):
            raise ValueError(
                f"prefix leaf {i}: checkpoint dtype {dtypes[i]} != "
                f"template dtype {ref.dtype}")
        out.append(jnp.asarray(arr).astype(ref.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)
