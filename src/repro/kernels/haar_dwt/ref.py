"""jnp oracle for the haar_dwt kernel.

The butterfly pairs lanes with the kernel's own exact shuffles
(``repro.kernels.lanes``), so each level starts from a materialized array
as it does in the kernel, and the bands match bitwise.  ``core.haar`` (the
reshape butterfly) may differ from both by an ulp where XLA contracts one
level's multiply into the next level's add.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.haar import INV_SQRT2
from repro.kernels import lanes


def _forward(g: jax.Array, level: int) -> Tuple[jax.Array, List[jax.Array]]:
    a, details = g.astype(jnp.float32), []
    for _ in range(level):
        even, odd = lanes.deinterleave(a)
        a = (even + odd) * INV_SQRT2
        details.append((even - odd) * INV_SQRT2)
    details.reverse()  # [D_l, ..., D_1]
    return a, details


def haar_dwt_fwd(g: jax.Array, level: int) -> Tuple[jax.Array, ...]:
    a, details = _forward(g, level)
    return (a.astype(g.dtype), *(d.astype(g.dtype) for d in details))


def haar_dwt_fwd_q(g: jax.Array, level: int, detail_dtype
                   ) -> Tuple[jax.Array, ...]:
    """Oracle for the fused quantize+pack forward: f32 transform, f32
    approximation, detail bands narrowed to ``detail_dtype``."""
    a, details = _forward(g, level)
    return (a, *(d.astype(detail_dtype) for d in details))


def haar_dwt_inv(a: jax.Array, details: Sequence[jax.Array]) -> jax.Array:
    x = a.astype(jnp.float32)
    for d in details:  # D_l first
        d = d.astype(jnp.float32)
        x = lanes.interleave((x + d) * INV_SQRT2, (x - d) * INV_SQRT2)
    return x.astype(a.dtype)
