"""Initial weights from the seed, made on the device in one jitted call.

Matrices are drawn ``N(0, initializer_range)`` (the configuration's own
``initializer_range``), norms and biases start at zero (the program's
RMSNorm scales by ``1 + gamma``, so zero is the published unit scale).
Leaf ``i`` of the architecture's ``layout`` draws from ``fold_in(key, i)``:
the program and the reference get the same values from the same seed, and
neither makes them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def key_from_seed(seed: int) -> jax.Array:
    """A key that keeps all 64 bits of ``seed`` (``jax.random.key``
    alone drops the high word)."""
    seed = int(seed) & (2**64 - 1)
    k = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, seed >> 32)


def nest(flat: dict) -> dict:
    """``{"a/b": x} -> {"a": {"b": x}}``."""
    out: dict = {}
    for path, v in flat.items():
        node = out
        *head, last = path.split("/")
        for h in head:
            node = node.setdefault(h, {})
        node[last] = v
    return out


def flatten(tree: dict, prefix: str = "") -> dict:
    """Inverse of :func:`nest`."""
    out = {}
    for k, v in tree.items():
        path = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten(v, path + "/"))
        else:
            out[path] = v
    return out


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _draw(leaves: tuple, dtype_name: str, std: float, key) -> dict:
    dtype = jnp.dtype(dtype_name)
    flat = {}
    for i, lf in enumerate(leaves):
        if lf.init == "zeros":
            flat[lf.path] = jnp.zeros(lf.shape, dtype)
        else:
            x = jax.random.normal(jax.random.fold_in(key, i), lf.shape,
                                  jnp.float32)
            flat[lf.path] = (x * std).astype(dtype)
    return flat


def make_weights(arch, spec, seed: int, level: int) -> dict:
    """Flat ``{path: array}`` of the initial weights of ``spec`` under the
    architecture module ``arch`` (the tree's dtype is the configuration's
    ``torch_dtype``)."""
    return _draw(tuple(arch.layout(spec, level)), spec.dtype, spec.init_std,
                 key_from_seed(seed))
