"""Plain reference: a dense decoder's loss and gradients and the GWT-Adam
update, in ``jax.numpy`` and float32 at ``highest`` matmul precision.

It imports nothing of the program.  It reads the configuration through
:class:`bench.model.Spec`, draws the initial weights itself
(``bench.weights``) and is fed the same token batches
(``bench.data``).  It follows the architecture the program implements:

* tokens embedded and scaled by ``sqrt(d_model)``;
* pre-norm blocks: RMSNorm with scale ``1 + gamma``, attention with
  rotary embeddings (halves rotated, base ``rope_theta``), grouped KV heads,
  optional q/k/v biases, causal softmax (with the sliding window when it is
  shorter than the sequence), output projection, residual; RMSNorm, SwiGLU
  MLP, residual;
* final RMSNorm, output head (the embedding's transpose when tied), mean
  cross-entropy over every token.

Parameters are held in the configuration's storage type (bf16) between
steps, as the program holds them, and computed on in float32.  The update
is the paper's Algorithm 1 (Haar DHT of level ``l`` along the transform
axis, Adam on the approximation band, details scaled by the band's
``1/(sqrt(v)+eps)``, inverse DHT, norm-growth limiter per stored leaf,
bias-corrected step ``lr·alpha``) on the leaves ``bench.model.layout``
marks ``gwt``, plain Adam on the rest, and the launcher's warm-up-cosine
schedule.

To fit one chip next to nothing else, the gradient is taken layer by
layer (each layer's backward recomputes its forward) and attention one KV
head group at a time.

``precision="fp8"`` is the control: every product's operands, forward
and backward, rounded to float8 e4m3 with a per-tensor scale — the step
below bf16 that a later change might be tempted to take.
``precision="bf16"`` rounds them to bfloat16 instead, the program's own
operand type: it shows how far that rounding alone moves each number.  ``fault`` plants
the harness's known faults (``"half_batch"``: the loss, and so the
gradient, taken over the first half of the batch's tokens only).
``limiter=False`` leaves the norm-growth limiter out, as the program's
``use_limiter=False`` does.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import Spec, layout

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


def _einsum_fn(precision: str):
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


def _rms(x, gamma, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + gamma)


def _rope(x, cos, sin):
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


class Reference:
    """``run(weights, batches)`` trains ``len(batches)`` steps from
    ``weights`` (flat ``{path: bf16 array}``) and returns what the check
    compares."""

    def __init__(self, spec: Spec, opt: dict, seq: int,
                 precision: str = "f32", fault: Optional[str] = None,
                 limiter: bool = True):
        self.spec, self.opt, self.seq = spec, opt, seq
        self.limiter = limiter
        self.level = opt["level"]
        self.leaves = layout(spec, self.level)
        self.ein = _einsum_fn(precision)
        self.fault = fault
        self._build()

    # -- model -------------------------------------------------------------
    def _layer(self, lp, x, cos, sin):
        s, ein = self.spec, self.ein
        B, S, d = x.shape
        G = s.heads // s.kv_heads
        h = _rms(x, lp["norm1"], s.norm_eps)
        q = ein("bsd,dn->bsn", h, lp["mixer/wq"])
        k = ein("bsd,dn->bsn", h, lp["mixer/wk"])
        v = ein("bsd,dn->bsn", h, lp["mixer/wv"])
        if s.qkv_bias:
            q, k, v = (q + lp["mixer/bq"], k + lp["mixer/bk"],
                       v + lp["mixer/bv"])
        q = _rope(q.reshape(B, S, s.heads, s.head_dim), cos, sin)
        k = _rope(k.reshape(B, S, s.kv_heads, s.head_dim), cos, sin)
        v = v.reshape(B, S, s.kv_heads, s.head_dim)
        qg = q.reshape(B, S, s.kv_heads, G, s.head_dim)
        qpos = jnp.arange(S)[:, None]
        tpos = jnp.arange(S)[None, :]
        mask = tpos <= qpos
        if s.sliding_window and s.sliding_window < S:
            mask &= tpos > qpos - s.sliding_window

        @jax.checkpoint
        def group(args):
            qj, kj, vj = args          # (B,S,G,hd), (B,S,hd), (B,S,hd)
            sc = ein("bsgd,btd->bgst", qj, kj) / math.sqrt(s.head_dim)
            sc = jnp.where(mask, sc, -jnp.inf)
            w = jax.nn.softmax(sc, axis=-1)
            return ein("bgst,btd->bsgd", w, vj)

        o = jax.lax.map(group, (jnp.moveaxis(qg, 2, 0), jnp.moveaxis(k, 2, 0),
                                jnp.moveaxis(v, 2, 0)))
        o = jnp.moveaxis(o, 0, 2).reshape(B, S, s.q_width)
        x = x + ein("bsn,nd->bsd", o, lp["mixer/wo"])
        h = _rms(x, lp["norm2"], s.norm_eps)
        a = jax.nn.silu(ein("bsd,df->bsf", h, lp["ffn/w_gate"]))
        u = ein("bsd,df->bsf", h, lp["ffn/w_up"])
        return x + ein("bsf,fd->bsd", a * u, lp["ffn/w_down"])

    def _head_loss(self, x, fnorm, w, labels):
        h = _rms(x, fnorm, self.spec.norm_eps)
        if self.spec.tied:
            logits = self.ein("bsd,vd->bsv", h, w)
        else:
            logits = self.ein("bsd,dv->bsv", h, w)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
        nll = (lse - ll).reshape(-1)
        if self.fault == "half_batch":
            return jnp.mean(nll[: nll.shape[0] // 2])
        return jnp.mean(nll)

    def _build(self):
        s = self.spec
        f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
        half = s.head_dim // 2
        freqs = 1.0 / (s.rope_theta ** (np.arange(0, s.head_dim, 2)
                                        / s.head_dim))
        ang = np.arange(self.seq)[:, None] * freqs[None, :]
        self.cos = jnp.asarray(np.cos(ang)[None, :, None, :half], jnp.float32)
        self.sin = jnp.asarray(np.sin(ang)[None, :, None, :half], jnp.float32)

        @jax.jit
        def embed(table, tokens):
            return table[tokens].astype(jnp.float32) * math.sqrt(s.d)

        @jax.jit
        def layer_fwd(lp, x, cos, sin):
            return self._layer(f32(lp), x, cos, sin)

        @jax.jit
        def layer_bwd(lp, x, dy, cos, sin):
            _, vjp = jax.vjp(lambda p, xx: self._layer(p, xx, cos, sin),
                             f32(lp), x)
            return vjp(dy)

        @jax.jit
        def head(x, fnorm, w, labels):
            return jax.value_and_grad(self._head_loss, argnums=(0, 1, 2))(
                x, fnorm.astype(jnp.float32), w.astype(jnp.float32), labels)

        @jax.jit
        def embed_grad(dx, tokens, dw_head):
            g = jnp.zeros((s.vocab, s.d), jnp.float32).at[tokens].add(
                dx * math.sqrt(s.d))
            return g + dw_head if s.tied else g

        self._embed, self._layer_fwd, self._layer_bwd = embed, layer_fwd, \
            layer_bwd
        self._head, self._embed_grad = head, embed_grad

    def grads(self, params: Dict[str, jax.Array], batch) -> tuple:
        """``(loss, {path: f32 gradient})`` of one batch."""
        s = self.spec
        tokens = jnp.asarray(batch["tokens"])
        labels = jnp.asarray(batch["labels"])
        names = [lf.path[len("layers/b0/"):] for lf in self.leaves
                 if lf.stacked]
        layer = lambda i: {n: params["layers/b0/" + n][i] for n in names}
        xs = [self._embed(params["embed/embedding"], tokens)]
        for i in range(s.layers):
            xs.append(self._layer_fwd(layer(i), xs[-1], self.cos, self.sin))
        w = params["embed/embedding"] if s.tied else params["embed/lm_head"]
        loss, (dx, dfn, dw) = self._head(xs[-1], params["final_norm"], w,
                                         labels)
        per_layer: List[dict] = [None] * s.layers
        for i in reversed(range(s.layers)):
            per_layer[i], dx = self._layer_bwd(layer(i), xs[i], dx, self.cos,
                                               self.sin)
            xs[i + 1] = None
        g = {"final_norm": dfn,
             "embed/embedding": self._embed_grad(dx, tokens,
                                                 dw if s.tied else 0.0)}
        if not s.tied:
            g["embed/lm_head"] = dw
        for n in names:
            g["layers/b0/" + n] = jnp.stack([pl[n] for pl in per_layer])
            for pl in per_layer:
                del pl[n]
        return float(loss), g

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
