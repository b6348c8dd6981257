"""Production mesh definitions (TPU v5e numbers).

A FUNCTION, not a module constant — importing this module must never touch
jax device state (smoke tests run on 1 CPU device; only dryrun.py forces
512 host devices). Every mesh axis is ``Auto``: shardings propagate from
params and inputs, pinned where needed by ``sharding.constraints``.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist locally, as a 1D 'data' mesh (examples/tests
    and the sharded data-parallel runtime)."""
    n = len(jax.devices())
    return _make_mesh((n,), ("data",))


def as_shardings(mesh, spec_tree):
    """PartitionSpec pytree -> NamedSharding pytree for jit in/out_shardings
    (a raw PartitionSpec resolves only against the mesh in context at CALL
    time; a NamedSharding carries its mesh, so the jitted step runs
    anywhere). PartitionSpec subclasses tuple, so mark it as a leaf."""
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s) if isinstance(s, PartitionSpec)
        else s,
        spec_tree, is_leaf=lambda x: isinstance(x, PartitionSpec))


# TPU v5e hardware constants used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link (~per-chip sustained)
CHIPS_PER_POD = 256
