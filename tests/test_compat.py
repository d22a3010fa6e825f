"""The runtime layer over JAX's mesh API: mesh construction, the ambient
mesh, kernel-backend resolution, MeshContext, plus regressions that (a)
every src/repro module imports under the installed JAX and (b) no module
outside repro.compat touches the mesh/sharding jax symbols directly."""

import importlib
import os
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.runtime.context import MeshContext

REPO = pathlib.Path(__file__).resolve().parent.parent
SRC = REPO / "src"


# ---------------------------------------------------------------------------
# make_mesh / AxisType
# ---------------------------------------------------------------------------

def test_make_mesh_single_device():
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    assert tuple(mesh.axis_names) == ("data", "model")
    assert dict(mesh.shape) == {"data": 1, "model": 1}


def test_axis_type_symbols_exist():
    assert hasattr(compat.AxisType, "Auto")
    assert len(compat.auto_axis_types(3)) == 3


# ---------------------------------------------------------------------------
# ambient mesh: use_mesh / get_abstract_mesh
# ---------------------------------------------------------------------------

def test_ambient_mesh_none_by_default():
    assert compat.get_abstract_mesh() is None


def test_use_mesh_sets_ambient_and_restores():
    mesh = compat.make_mesh((1,), ("data",))
    with compat.use_mesh(mesh):
        m = compat.get_abstract_mesh()
        assert m is not None and "data" in tuple(m.axis_names)
    assert compat.get_abstract_mesh() is None


def test_use_mesh_none_is_noop():
    with compat.use_mesh(None) as m:
        assert m is None
    assert compat.get_abstract_mesh() is None


# ---------------------------------------------------------------------------
# with_sharding_constraint
# ---------------------------------------------------------------------------

def test_wsc_noop_without_mesh():
    x = jnp.ones((4, 4))
    y = compat.with_sharding_constraint(x, "data", None)
    assert y is x


def test_wsc_resolves_under_concrete_mesh():
    mesh = compat.make_mesh((1,), ("data",))

    @jax.jit
    def f(x):
        return compat.with_sharding_constraint(x, "data", None, mesh=mesh)

    y = f(jnp.ones((4, 4)))
    np.testing.assert_array_equal(np.asarray(y), np.ones((4, 4)))


# ---------------------------------------------------------------------------
# kernel backend selection
# ---------------------------------------------------------------------------

def test_resolve_kernel_impl_auto_cpu():
    assert compat.resolve_kernel_impl("auto", platform="cpu") == "jnp"
    assert compat.resolve_kernel_impl(None, platform="tpu") == "pallas"
    assert compat.resolve_kernel_impl("interpret") == "interpret"


def test_resolve_kernel_impl_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "interpret")
    assert compat.resolve_kernel_impl("auto", platform="tpu") == "interpret"
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "auto")
    assert compat.resolve_kernel_impl("auto", platform="cpu") == "jnp"


def test_env_override_typo_fails_fast(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "cuda")
    with pytest.raises(ValueError, match="REPRO_KERNEL_IMPL"):
        compat.resolve_kernel_impl("auto")


def test_kernel_impl_env_not_frozen_by_trace_cache(monkeypatch):
    """'auto' must re-resolve per call: resolving inside a jitted body with
    impl static would freeze the env read into the first trace."""
    from repro.kernels.haar_dwt import kernel as dkern, ops as dops
    g = jnp.ones((4, 8), jnp.float32)
    a1 = dops.dwt(g, 1)  # traces the platform default (jnp on CPU)

    seen = {}
    real = dkern.haar_dwt_fwd

    def spy(*a, **kw):
        seen["interpret"] = kw.get("interpret", False)
        return real(*a, **kw)

    monkeypatch.setattr(dkern, "haar_dwt_fwd", spy)
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "interpret")
    a2 = dops.dwt(g, 1)  # must take the interpret path NOW
    assert seen.get("interpret") is True
    np.testing.assert_allclose(np.asarray(a1[0]), np.asarray(a2[0]),
                               atol=1e-5)


def test_unwrap_mesh_accepts_mesh_context_or_mesh():
    mesh = compat.make_mesh((1,), ("data",))
    assert compat.unwrap_mesh(mesh) is mesh
    assert compat.unwrap_mesh(MeshContext.create(mesh=mesh)) is mesh
    assert compat.unwrap_mesh(None) is None


def test_resolve_kernel_impl_invalid():
    with pytest.raises(ValueError):
        compat.resolve_kernel_impl("cuda")


# ---------------------------------------------------------------------------
# MeshContext
# ---------------------------------------------------------------------------

def test_mesh_context_single_device_defaults():
    ctx = MeshContext.create()
    assert ctx.mesh is None and ctx.axis_names == ()
    assert ctx.axis_size("data") == 0
    assert ctx.dp_axes(16) is None
    x = jnp.ones((2, 2))
    assert ctx.constrain(x, "data") is x  # no mesh -> no-op


def test_mesh_context_dp_axes_and_sizes():
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    ctx = MeshContext.create(mesh=mesh)
    assert ctx.has_axis("model") and ctx.axis_size("data") == 1
    assert ctx.dp_axes(4) == "data"


def test_mesh_context_ambient_adopts_use_mesh():
    mesh = compat.make_mesh((1,), ("data",))
    with compat.use_mesh(mesh):
        ctx = MeshContext.ambient()
        assert ctx.axis_names == ("data",)
    assert MeshContext.ambient().mesh is None


def test_mesh_context_activate_roundtrip():
    mesh = compat.make_mesh((1,), ("data",))
    ctx = MeshContext.create(mesh=mesh)
    with ctx.activate():
        assert compat.get_abstract_mesh() is not None
    assert compat.get_abstract_mesh() is None


# ---------------------------------------------------------------------------
# regressions
# ---------------------------------------------------------------------------

def _all_repro_modules():
    return sorted(
        ".".join(p.relative_to(SRC).with_suffix("").parts)
        for p in SRC.rglob("*.py") if p.name != "__init__.py")


@pytest.mark.parametrize("mod", _all_repro_modules())
def test_every_module_imports_under_pinned_jax(mod):
    """The original bug class: post-0.5-only jax attribute access at import
    or call time.  Every module must import cleanly on the pinned JAX."""
    xla_flags = os.environ.get("XLA_FLAGS")
    try:
        importlib.import_module(mod)
    finally:  # launch.dryrun guards its XLA_FLAGS write; belt-and-braces
        if xla_flags is None:
            os.environ.pop("XLA_FLAGS", None)
        else:
            os.environ["XLA_FLAGS"] = xla_flags


def test_no_direct_mesh_api_references():
    """Grep-clean: the drifting symbols appear only inside repro/compat.py
    (and this test, which assembles the pattern from fragments)."""
    pat = re.compile("|".join(
        "jax" + re.escape(".") + frag
        for frag in ("sharding.get_abstract_mesh", "sharding.AxisType",
                     "make_mesh", "set_mesh", "sharding.use_mesh")))
    offenders = []
    for sub in ("src", "tests", "benchmarks", "examples", "scripts"):
        base = REPO / sub
        if not base.exists():
            continue
        for p in base.rglob("*.py"):
            if p.name in ("compat.py", "test_compat.py"):
                continue
            if pat.search(p.read_text()):
                offenders.append(str(p.relative_to(REPO)))
    assert not offenders, offenders
