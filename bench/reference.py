"""Plain reference, the part every architecture shares: the GWT-Adam
update and the training loop around a model's loss and gradients, in
``jax.numpy`` and float32 at ``highest`` matmul precision, plus the pieces
of a decoder that architectures have in common (RMSNorm, rotary
embeddings, the products' precision).

It imports nothing of the program.  An architecture's reference
(``bench/arch/<name>.py``, ``Reference``) subclasses :class:`Trainer` and
gives ``grads(params, batch)``, the model's loss and gradients; it draws
nothing itself: the initial weights come from ``bench.weights`` and the
token batches from ``bench.data``.

Parameters are held in the configuration's storage type (bf16) between
steps, as the program holds them, and computed on in float32.  The update
is the paper's Algorithm 1 (Haar DHT of level ``l`` along the transform
axis, Adam on the approximation band, details scaled by the band's
``1/(sqrt(v)+eps)``, inverse DHT, norm-growth limiter per stored leaf,
bias-corrected step ``lr·alpha``) on the leaves the architecture's
``layout`` marks ``gwt``, plain Adam on the rest, and the launcher's
warm-up-cosine schedule.

``precision="fp8"`` is the control: every product's operands, forward
and backward, rounded to float8 e4m3 with a per-tensor scale — the step
below bf16 that a later change might be tempted to take.
``precision="bf16"`` rounds them to bfloat16 instead, the program's own
operand type: it shows how far that rounding alone moves each number.
``fault`` plants the harness's known faults (``"half_batch"``: the loss,
and so the gradient, taken over the first half of the batch's tokens
only, :meth:`Trainer.mean_nll`).  ``limiter=False`` leaves the
norm-growth limiter out, as the program's ``use_limiter=False`` does.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import Leaf

HIGHEST = jax.lax.Precision.HIGHEST
INV_SQRT2 = 1.0 / math.sqrt(2.0)
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _q8(x):
    """Round to float8 e4m3 under a per-tensor scale (amax -> 448)."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def einsum_fn(precision: str):
    if precision == "f32":
        return lambda eq, a, b: jnp.einsum(eq, a, b, precision=HIGHEST)
    rounders = {"fp8": _q8, "bf16": _bf16}
    if precision not in rounders:
        raise ValueError(f"precision {precision!r}: 'f32', 'bf16' or 'fp8'")
    q = rounders[precision]

    def qeinsum(eq, a, b):
        @jax.custom_vjp
        def f(a, b):
            return jnp.einsum(eq, q(a), q(b), precision=HIGHEST)

        def fwd(a, b):
            qa, qb = q(a), q(b)
            return jnp.einsum(eq, qa, qb, precision=HIGHEST), (qa, qb)

        def bwd(res, g):
            _, vjp = jax.vjp(
                lambda x, y: jnp.einsum(eq, x, y, precision=HIGHEST), *res)
            return vjp(q(g))

        f.defvjp(fwd, bwd)
        return f(a, b)
    return qeinsum


def rms_norm(x, gamma, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + gamma)


def rope_tables(seq: int, head_dim: int, theta: float):
    """``(cos, sin)`` of the rotary angles, shaped ``(1, seq, 1,
    head_dim // 2)`` to broadcast over batch and heads."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))
    ang = np.arange(seq)[:, None] * freqs[None, :]
    return (jnp.asarray(np.cos(ang)[None, :, None, :half], jnp.float32),
            jnp.asarray(np.sin(ang)[None, :, None, :half], jnp.float32))


def rope(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def lr_at(step: int, peak: float, horizon: int, warmup_frac: float = 0.1,
          final_frac: float = 0.1) -> float:
    """The launcher's schedule: linear warm-up over 10% of ``horizon``,
    cosine down to 10% of the peak, then flat."""
    warm = max(1, int(horizon * warmup_frac))
    if step < warm:
        return peak * step / warm
    prog = min(max((step - warm) / max(1, horizon - warm), 0.0), 1.0)
    return peak * (final_frac
                   + (1 - final_frac) * 0.5 * (1 + math.cos(math.pi * prog)))


class Trainer:
    """``run(weights, batches)`` trains ``len(batches)`` steps from
    ``weights`` (flat ``{path: bf16 array}``) and returns what the check
    compares.  A subclass gives ``grads(params, batch) -> (loss, {path:
    f32 gradient})`` for the model of ``leaves``."""

    def __init__(self, spec, opt: dict, seq: int, leaves: List[Leaf],
                 precision: str = "f32", fault: Optional[str] = None,
                 limiter: bool = True):
        self.spec, self.opt, self.seq = spec, opt, seq
        self.limiter = limiter
        self.level = opt["level"]
        self.leaves = leaves
        self.ein = einsum_fn(precision)
        self.fault = fault

    def mean_nll(self, nll):
        """The loss over the tokens' negative log-likelihoods (flat): their
        mean, or with the ``half_batch`` fault the first half's."""
        if self.fault == "half_batch":
            return jnp.mean(nll[: nll.shape[0] // 2])
        return jnp.mean(nll)

    def grads(self, params: Dict[str, jax.Array], batch) -> tuple:
        raise NotImplementedError

    # -- optimizer ---------------------------------------------------------
    def _haar(self, g, axis):
        g = jnp.moveaxis(g, axis, -1)
        a, details = g, []
        for _ in range(self.level):
            e, o = a[..., 0::2], a[..., 1::2]
            details.append((e - o) * INV_SQRT2)
            a = (e + o) * INV_SQRT2
        return a, details            # details finest first

    def _ihaar(self, a, details, axis):
        x = a
        for d in reversed(details):
            e, o = (x + d) * INV_SQRT2, (x - d) * INV_SQRT2
            x = jnp.stack([e, o], -1).reshape(*x.shape[:-1], 2 * x.shape[-1])
        return jnp.moveaxis(x, -1, axis)

    @functools.partial(jax.jit, static_argnums=(0, 7))
    def _gwt_update(self, p, g, m, v, prev, step_size, axis):
        o = self.opt
        a, details = self._haar(g, axis)
        m = o["b1"] * m + (1 - o["b1"]) * a
        v = o["b2"] * v + (1 - o["b2"]) * a * a
        inv = 1.0 / (jnp.sqrt(v) + o["eps"])
        scaled = [d * jnp.repeat(inv, d.shape[-1] // inv.shape[-1], -1)
                  for d in details]
        gt = self._ihaar(m * inv, scaled, axis)
        norm = jnp.sqrt(jnp.sum(gt * gt))
        safe = jnp.where(prev > 0, prev, norm)
        scale = jnp.where(norm > o["gamma"] * safe,
                          o["gamma"] * safe / jnp.maximum(norm, 1e-30), 1.0)
        if not self.limiter:
            scale = jnp.float32(1.0)
        new_prev = jnp.where(norm > 0, norm * scale, prev)
        new_p = p.astype(jnp.float32) - step_size * o["alpha"] * scale * gt
        return new_p.astype(p.dtype), m, v, new_prev

    @functools.partial(jax.jit, static_argnums=(0,))
    def _adam_update(self, p, g, m, v, step_size):
        o = self.opt
        m = o["b1"] * m + (1 - o["b1"]) * g
        v = o["b2"] * v + (1 - o["b2"]) * g * g
        new_p = p.astype(jnp.float32) - step_size * m / (jnp.sqrt(v)
                                                         + o["eps"])
        return new_p.astype(p.dtype), m, v

    def band(self, path: str, g):
        """What the optimizer's state keeps of gradient ``g``: the
        approximation band for a wavelet leaf, ``g`` itself otherwise."""
        lf = next(l for l in self.leaves if l.path == path)
        if lf.rule == "gwt":
            return jnp.moveaxis(self._haar(g, lf.axis)[0], -1, lf.axis)
        return g

    def run(self, weights: Dict[str, jax.Array], batches,
            keep_params: bool = False) -> dict:
        """The losses, the first gradient's norms (its band and whole) and
        the parameters' change norms; with ``keep_params`` the trained
        parameters too (``"params"``)."""
        o = self.opt
        params = dict(weights)
        state = {}
        for lf in self.leaves:
            shape = list(lf.shape)
            if lf.rule == "gwt":   # the band, transform axis moved last
                shape = shape[:lf.axis] + shape[lf.axis + 1:] \
                    + [shape[lf.axis] >> self.level]
            z = jnp.zeros(shape, jnp.float32)
            state[lf.path] = [z, z, jnp.zeros((), jnp.float32)]
        losses, first = [], None
        for step, batch in enumerate(batches):
            loss, g = self.grads(params, batch)
            losses.append(loss)
            if step == 0:
                first = {path: (self.band(path, gv), gv)
                         for path, gv in g.items()}
                first = {path: (slice_norms(path, b), slice_norms(path, f))
                         for path, (b, f) in first.items()}
            lr = lr_at(step, o["lr"], o["horizon"])
            t = step + 1.0
            mult = math.sqrt(1 - o["b2"] ** t) / (1 - o["b1"] ** t)
            for lf in self.leaves:
                m, v, prev = state[lf.path]
                gv = g.pop(lf.path)
                if lf.rule == "gwt":
                    params[lf.path], m, v, prev = self._gwt_update(
                        params[lf.path], gv, m, v, prev,
                        jnp.float32(lr * mult), lf.axis)
                else:
                    params[lf.path], m, v = self._adam_update(
                        params[lf.path], gv, m, v, jnp.float32(lr * mult))
                state[lf.path] = [m, v, prev]
                del gv
        change = {path: slice_norms(path, params[path], weights[path])
                  for path in params}
        out = {"losses": losses,
               "grad_band": {p: b for p, (b, _) in first.items()},
               "grad_full": {p: f for p, (_, f) in first.items()},
               "change": change}
        if keep_params:
            out["params"] = params
        return out


def slice_norms(path: str, x, base=None) -> np.ndarray:
    """Frobenius norms of ``x`` (or of ``x - base``) per layer for a layer
    stack, one norm otherwise; float64 on the host."""
    return np.asarray(_slice_norms(path.startswith("layers/"), x, base),
                      np.float64)


@functools.partial(jax.jit, static_argnums=(0,))
def _slice_norms(stacked, x, base):
    x = x.astype(jnp.float32)
    if base is not None:
        x = x - base.astype(jnp.float32)
    if stacked:
        return jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
    return jnp.sqrt(jnp.sum(x * x))[None]
