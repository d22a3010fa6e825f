"""Production mesh construction (assignment-mandated shapes).

FUNCTIONS, not module constants — importing this module never touches
jax device state.  All mesh construction routes through
:mod:`repro.compat`.
"""

from __future__ import annotations

from repro import compat
from repro.runtime.context import MeshContext


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Elastic helper: any factorization of the available devices works;
    checkpoint restore re-shards on load (see repro.checkpoint)."""
    return compat.make_mesh(tuple(shape), tuple(axes))


def make_mesh_context(shape=None, axes=None, *, multi_pod: bool = False,
                      production: bool = False,
                      kernel_impl: str = "auto") -> MeshContext:
    """One-stop launch helper: build the mesh and wrap it in the explicit
    :class:`MeshContext` threaded through model/optimizer/checkpoint.

    ``shape``/``axes`` build an elastic mesh; ``production=True`` builds the
    assignment-mandated pod mesh; neither gives a single-device context
    (every sharding constraint becomes a no-op — the CPU path)."""
    if shape is not None:
        mesh = make_mesh(shape, axes)
    elif production:
        mesh = make_production_mesh(multi_pod=multi_pod)
    else:
        mesh = None
    return MeshContext.create(mesh=mesh, kernel_impl=kernel_impl)
