"""The JAX mesh/sharding API surface, in one place.

The repository runs on one JAX release (0.9.0).  Mesh construction, the
ambient mesh, sharding constraints and ``shard_map`` go through this module
so that the conventions the rest of the code relies on live here:

* ``get_abstract_mesh()`` returns ``None`` for "no mesh" (JAX returns an
  empty abstract mesh);
* ``use_mesh(None)`` is a no-op (the single-device path);
* ``with_sharding_constraint`` resolves bare axis names against an explicit
  or ambient mesh and is a no-op without one (CPU unit tests);
* ``shard_map`` manualizes every mesh axis except ``auto``.

This module is the ONLY place in the repo allowed to touch those jax
symbols directly (enforced by a grep test in tests/test_compat.py).

The same module owns kernel-backend selection (``pallas`` / ``interpret``
/ pure-``jnp``) so per-platform dispatch and the ``REPRO_KERNEL_IMPL``
override live next to the rest of the runtime decisions.
"""

from __future__ import annotations

import contextlib
import os
from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec


def auto_axis_types(n: int):
    """``n`` auto axis types — the only variant this codebase uses."""
    return (AxisType.Auto,) * n


# ---------------------------------------------------------------------------
# Mesh construction and the ambient mesh
# ---------------------------------------------------------------------------

def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices=None) -> Mesh:
    """All-auto mesh over ``devices`` (default: every device)."""
    axis_names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), axis_names,
                         axis_types=auto_axis_types(len(axis_names)),
                         devices=devices)


def get_abstract_mesh():
    """The ambient mesh, or ``None`` when no mesh context is active."""
    m = jax.sharding.get_abstract_mesh()
    return m if tuple(m.axis_names) else None


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]):
    """Context manager making ``mesh`` ambient.  ``None`` is a no-op (the
    single-device / CPU-unit-test case)."""
    if mesh is None:
        yield None
        return
    with jax.set_mesh(mesh):
        yield mesh


def unwrap_mesh(mesh_or_ctx):
    """Accept a Mesh/AbstractMesh OR an object carrying one (MeshContext);
    ``None`` passes through.  The single normalization point for APIs that
    take either."""
    return getattr(mesh_or_ctx, "mesh", mesh_or_ctx)


def with_sharding_constraint(x, *spec, mesh=None):
    """Sharding constraint that degrades to a no-op outside a mesh context.

    Bare axis names (or a ready ``PartitionSpec``) are resolved against the
    explicit ``mesh`` when given, else the ambient mesh.  A concrete mesh
    resolves through ``NamedSharding``; otherwise the bare spec is handed
    to jax, which resolves it against the ambient abstract mesh."""
    if len(spec) == 1 and isinstance(spec[0], PartitionSpec):
        sp = spec[0]
    else:
        sp = PartitionSpec(*spec)
    m = mesh if mesh is not None else get_abstract_mesh()
    try:
        if isinstance(m, Mesh):
            return jax.lax.with_sharding_constraint(x, NamedSharding(m, sp))
        return jax.lax.with_sharding_constraint(x, sp)
    except (ValueError, RuntimeError, TypeError):
        return x


def shard_map(f, mesh, in_specs, out_specs, auto=frozenset()):
    """``jax.shard_map`` manual over every mesh axis except ``auto`` (left
    to GSPMD — e.g. the tensor-parallel 'model' axis while the DP gradient
    reduction runs manually over 'data').

    Varying-manual-axes checking is off: the call sites here produce
    post-``psum`` (replicated-by-construction) outputs that the checker
    cannot always prove through dtype casts."""
    mesh = unwrap_mesh(mesh)
    manual = frozenset(mesh.axis_names) - frozenset(auto)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=manual,
                         check_vma=False)


# ---------------------------------------------------------------------------
# Kernel backend selection
# ---------------------------------------------------------------------------

KERNEL_IMPLS = ("pallas", "interpret", "jnp")


def default_kernel_impl(platform: Optional[str] = None) -> str:
    """Per-platform default backend: native Pallas on TPU, the pure-jnp
    butterfly elsewhere.  ``REPRO_KERNEL_IMPL`` overrides (e.g. set
    ``interpret`` to validate the Pallas lowering on CPU)."""
    env = os.environ.get("REPRO_KERNEL_IMPL", "").strip().lower()
    if env and env != "auto":
        if env not in KERNEL_IMPLS:  # fail fast: a typo here would
            # otherwise silently fall back to a different backend
            raise ValueError(
                f"REPRO_KERNEL_IMPL={env!r} invalid; choices: auto|" +
                "|".join(KERNEL_IMPLS))
        return env
    platform = platform or jax.default_backend()
    return "pallas" if platform == "tpu" else "jnp"


def resolve_kernel_impl(impl: Optional[str] = None,
                        platform: Optional[str] = None) -> str:
    """Map ``None``/``'auto'`` to the platform default; validate the rest."""
    if impl in (None, "auto"):
        return default_kernel_impl(platform)
    if impl not in KERNEL_IMPLS:
        raise ValueError(
            f"unknown kernel impl {impl!r}; choices: auto|" +
            "|".join(KERNEL_IMPLS))
    return impl
