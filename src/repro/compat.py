"""Thin wrappers for the jax APIs this repo leans on.

Meshes and shard_maps are built here, so the choices made here (every
mesh axis Auto, every shard_map axis manual, check_vma on) hold in one
place.  The one exception is the train step's stage 2, which runs with
check_vma off (see `launch.train.build_train_setup`).
"""
from __future__ import annotations

import jax
from jax import lax

__all__ = ["axis_size", "shard_map", "make_mesh", "all_gather_invariant",
           "vma", "vary_alike"]


def axis_size(name: str) -> int:
    """Static size of a mapped mesh axis (usable inside shard_map)."""
    return lax.axis_size(name)


def vma(*xs) -> frozenset:
    """The mesh axes any of `xs` varies over inside a shard_map (empty
    outside one).  Values made from nothing, such as a Pallas kernel's
    outputs or a scan's zero carry, must be declared with these axes."""
    return frozenset().union(*(jax.typeof(x).vma for x in xs))


def vary_alike(*xs):
    """(`xs` cast to vary over the union of their mesh axes, that union).
    A Pallas call inside a shard_map takes operands that vary alike and
    declares its outputs with the union."""
    axes = vma(*xs)
    return tuple(lax.pcast(x, tuple(sorted(axes - vma(x))), to="varying")
                 for x in xs), axes


def shard_map(f, mesh, in_specs, out_specs, axis_names=None):
    """`jax.shard_map` with every mesh axis manual (callers here never use
    partial-manual mode) and jax's varying-axes check on."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs,
                         axis_names=axis_names or set(mesh.axis_names))


def make_mesh(axis_shapes, axis_names, devices=None):
    """`jax.make_mesh` with explicit Auto axis types."""
    return jax.make_mesh(
        axis_shapes, axis_names, devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def all_gather_invariant(x, axis_name: str, *, axis: int = 0,
                         tiled: bool = False):
    """`all_gather` whose result is typed invariant over `axis_name`:
    every rank holds the same gathered array, so values built from it may
    leave a shard_map replicated over that axis with check_vma on (plain
    `lax.all_gather` types its result as varying).  jax 0.9 keeps this
    primitive out of `jax.lax`."""
    from jax._src.lax.parallel import all_gather_invariant as _agi
    return _agi(x, axis_name, axis=axis, tiled=tiled)
