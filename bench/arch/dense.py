"""The dense decoder: GQA attention and a SwiGLU MLP in every layer
(Qwen2.5, Mistral, Llama).  The architecture module of configurations with
``"bench_arch": "dense"`` (``bench/model.py`` says what one provides).

:func:`load_spec` reads the public ``config.json`` keys (``hidden_size``,
``num_hidden_layers``, ...).  :func:`layout` lists every parameter the
decoder holds, in the program's tree layout (layer stacks carry a leading
layer axis), with the update rule the benchmark holds the optimizer to:
the wavelet rule for every leaf of an attention or MLP module whose stored
array (layer stacks are at least 2-D) has a last axis divisible by
``2**level``, plain Adam for the rest (embedding, output head, norms).

:class:`Reference` follows the architecture the program implements:

* tokens embedded and scaled by ``sqrt(d_model)``;
* pre-norm blocks: RMSNorm with scale ``1 + gamma``, attention with
  rotary embeddings (halves rotated, base ``rope_theta``), grouped KV heads,
  optional q/k/v biases, causal softmax (with the sliding window when it is
  shorter than the sequence), output projection, residual; RMSNorm, SwiGLU
  MLP, residual;
* final RMSNorm, output head (the embedding's transpose when tied), mean
  cross-entropy over every token.

To fit one chip next to nothing else, the gradient is taken layer by
layer (each layer's backward recomputes its forward) and attention one KV
head group at a time.
"""

from __future__ import annotations

import dataclasses
import json
import math
import pathlib
from typing import Dict, List

import jax
import jax.numpy as jnp

from bench import reference as ref_lib
from bench.model import Leaf


@dataclasses.dataclass(frozen=True)
class Spec:
    name: str
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    tied: bool
    qkv_bias: bool
    rope_theta: float
    norm_eps: float
    init_std: float
    dtype: str
    sliding_window: int = 0

    @property
    def q_width(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_width(self) -> int:
        return self.kv_heads * self.head_dim


def load_spec(path) -> Spec:
    cfg = json.loads(pathlib.Path(path).read_text())
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return Spec(
        name=pathlib.Path(path).name[:-len(".json")],
        layers=cfg["num_hidden_layers"], d=d, heads=heads,
        kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or d // heads,
        ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        tied=bool(cfg["tie_word_embeddings"]),
        qkv_bias=bool(cfg.get("qkv_bias", False)),
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        init_std=float(cfg["initializer_range"]),
        dtype=cfg["torch_dtype"],
        sliding_window=int(cfg.get("sliding_window") or 0)
        if cfg.get("use_sliding_window", True) else 0)


def layout(spec: Spec, level: int) -> List[Leaf]:
    """Every parameter leaf, sorted by path (the order the weights are
    drawn in)."""
    L, d, ff = spec.layers, spec.d, spec.ff
    qw, kw = spec.q_width, spec.kv_width
    module = {"mixer/wq": (d, qw), "mixer/wk": (d, kw), "mixer/wv": (d, kw),
              "mixer/wo": (qw, d), "ffn/w_gate": (d, ff),
              "ffn/w_up": (d, ff), "ffn/w_down": (ff, d)}
    if spec.qkv_bias:
        module.update({"mixer/bq": (qw,), "mixer/bk": (kw,),
                       "mixer/bv": (kw,)})
    block = 1 << level
    leaves = [Leaf("embed/embedding", (spec.vocab, d), "adam", False,
                   "normal"),
              Leaf("final_norm", (d,), "adam", False, "zeros")]
    if not spec.tied:
        leaves.append(Leaf("embed/lm_head", (d, spec.vocab), "adam", False,
                           "normal"))
    for name in ("norm1", "norm2"):
        leaves.append(Leaf(f"layers/b0/{name}", (L, d), "adam", True,
                           "zeros"))
    for name, shape in module.items():
        stored = (L,) + shape
        axis = None
        if level and stored[-1] % block == 0:
            axis = len(stored) - 1
        bias = len(shape) == 1
        leaves.append(Leaf(f"layers/b0/{name}", stored,
                           "gwt" if axis is not None else "adam", True,
                           "zeros" if bias else "normal", axis))
    return sorted(leaves, key=lambda lf: lf.path)


def matmul_params(spec: Spec) -> int:
    """Non-embedding matrix parameters plus the output head (PaLM's N):
    the input embedding is a gather, not a product; norms and biases are
    not matrices."""
    d, ff = spec.d, spec.ff
    per_layer = (d * spec.q_width + 2 * d * spec.kv_width
                 + spec.q_width * d + 3 * d * ff)
    return spec.layers * per_layer + d * spec.vocab


def model_flops_per_token(spec: Spec, seq: int) -> float:
    """PaLM's model FLOPs per trained token (Chowdhery et al. 2022, App. B):
    ``6·N + 12·L·H·Q·T``, forward and backward, recomputation not
    counted."""
    attn = 12 * spec.layers * spec.heads * spec.head_dim * seq
    return 6.0 * matmul_params(spec) + attn


def program_config(spec: Spec, seq: int):
    """The program's configuration object for ``spec``."""
    from repro.configs.base import ModelConfig
    if spec.sliding_window and spec.sliding_window < seq:
        raise ValueError(f"{spec.name}: a sliding window of "
                         f"{spec.sliding_window} below seq {seq} is not run "
                         f"by this benchmark")
    return ModelConfig(
        name=spec.name, n_layers=spec.layers, d_model=spec.d,
        n_heads=spec.heads, n_kv_heads=spec.kv_heads,
        head_dim=spec.head_dim, d_ff=spec.ff, vocab=spec.vocab,
        pattern=("attn",), qkv_bias=spec.qkv_bias,
        rope_theta=spec.rope_theta, tie_embeddings=spec.tied,
        norm_eps=spec.norm_eps, dtype=spec.dtype, remat=True)


class Reference(ref_lib.Trainer):
    """The dense decoder's loss and gradients (see the module docstring);
    the update and the loop are :class:`bench.reference.Trainer`'s."""

    def __init__(self, spec: Spec, opt: dict, seq: int, **kw):
        super().__init__(spec, opt, seq, layout(spec, opt["level"]), **kw)
        self._build()

    def _attention(self, lp, x, cos, sin):
        """``x`` plus the attention sublayer's output."""
        s, ein = self.spec, self.ein
        B, S, d = x.shape
        G = s.heads // s.kv_heads
        h = ref_lib.rms_norm(x, lp["norm1"], s.norm_eps)
        q = ein("bsd,dn->bsn", h, lp["mixer/wq"])
        k = ein("bsd,dn->bsn", h, lp["mixer/wk"])
        v = ein("bsd,dn->bsn", h, lp["mixer/wv"])
        if s.qkv_bias:
            q, k, v = (q + lp["mixer/bq"], k + lp["mixer/bk"],
                       v + lp["mixer/bv"])
        q = ref_lib.rope(q.reshape(B, S, s.heads, s.head_dim), cos, sin)
        k = ref_lib.rope(k.reshape(B, S, s.kv_heads, s.head_dim), cos, sin)
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
        return x + ein("bsn,nd->bsd", o, lp["mixer/wo"])

    def _mlp(self, lp, x):
        """``x`` plus the SwiGLU sublayer's output."""
        ein = self.ein
        h = ref_lib.rms_norm(x, lp["norm2"], self.spec.norm_eps)
        a = jax.nn.silu(ein("bsd,df->bsf", h, lp["ffn/w_gate"]))
        u = ein("bsd,df->bsf", h, lp["ffn/w_up"])
        return x + ein("bsf,fd->bsd", a * u, lp["ffn/w_down"])

    def _layer(self, lp, x, cos, sin):
        return self._mlp(lp, self._attention(lp, x, cos, sin))

    def _head_loss(self, x, fnorm, w, labels):
        h = ref_lib.rms_norm(x, fnorm, self.spec.norm_eps)
        if self.spec.tied:
            logits = self.ein("bsd,vd->bsv", h, w)
        else:
            logits = self.ein("bsd,dv->bsv", h, w)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
        return self.mean_nll((lse - ll).reshape(-1))

    def _build(self):
        s = self.spec
        f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
        self.cos, self.sin = ref_lib.rope_tables(self.seq, s.head_dim,
                                                 s.rope_theta)

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
