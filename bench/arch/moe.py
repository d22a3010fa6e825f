"""The fine-grained mixture-of-experts decoder (Qwen3-MoE): pre-norm GQA
attention with QK-norm, then in every layer an expert FFN of top-k routed
SwiGLU experts with no shared expert.  The architecture module of
configurations with ``"bench_arch": "moe"`` (``bench/model.py`` says what
one provides); it builds on ``dense.py`` beside it.

A configuration is a chip's share of an expert-parallel deployment: its
``num_experts`` is the experts this chip holds (experts ``0 ..
num_experts-1``), ``published.num_experts`` the router's width.  The
router keeps its published width and top-k; the chip computes its own
experts' part of the layer for the tokens routed to them, and what the
absent experts would add is left out, in the program and here alike.

:class:`Reference` follows the architecture the program implements (the
dense decoder's embedding, RMSNorm with scale ``1 + gamma``, rotary
embeddings, causal GQA and head, see ``dense.py``), with:

* QK-norm: RMSNorm over each 128-wide query and key head, before RoPE;
* the router: logits ``h @ W_r`` over all routed experts, softmax, the
  top-k, their gates renormalised to sum to one (``norm_topk_prob``);
* the expert layer: every held expert computed on every token and
  weighted by the token's renormalised gate for it, zero where the expert
  is not among the token's top-k — no sort, no grouped matmul, no
  capacity;
* the load-balancing term, the program's per-layer Switch form over all
  routed experts and the step's tokens, ``E · Σ_e f_e · P_e`` (``f_e``
  the share of the token's top-k picks that went to ``e``, ``P_e`` the
  mean router probability), added to the loss with the published
  ``router_aux_coef``.

Its own fault, beside the harness's ``fp8`` control and ``half_batch``:
``drop`` keeps only the first ``ceil(T·K/E)`` (token, k) pairs of each
expert in token order (capacity factor 1.0) — what a capacity-bounded
dispatch does under skew.  The limits fail it.

:func:`gmm_work` is the grouped matmuls' least work per step, which
``moe_gmm_roofline`` reads.
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
from bench.counts import DTYPE_BYTES
from bench.harness import load_module
from bench.model import Leaf

dense = load_module(pathlib.Path(__file__).with_name("dense.py"))


@dataclasses.dataclass(frozen=True)
class Spec(dense.Spec):
    experts_routed: int = 0        # the router's width, as published
    experts_held: int = 0          # experts 0..experts_held-1 live here
    top_k: int = 0
    expert_ff: int = 0             # moe_intermediate_size
    aux_coef: float = 0.0          # router_aux_coef
    layers_published: int = 0
    vocab_published: int = 0


def load_spec(path) -> Spec:
    cfg = json.loads(pathlib.Path(path).read_text())
    pub = cfg["published"]
    if cfg["decoder_sparse_step"] != 1 or cfg["mlp_only_layers"]:
        raise ValueError("bench arch 'moe' runs an expert FFN in every layer")
    if not cfg["norm_topk_prob"]:
        raise ValueError("the program renormalises the top-k gates")
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return Spec(
        name=pathlib.Path(path).name[:-len(".json")],
        layers=cfg["num_hidden_layers"], d=d, heads=heads,
        kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or d // heads,
        ff=0, vocab=cfg["vocab_size"],
        tied=bool(cfg["tie_word_embeddings"]),
        qkv_bias=bool(cfg["attention_bias"]),
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        init_std=float(cfg["initializer_range"]),
        dtype=cfg["torch_dtype"],
        sliding_window=int(cfg.get("sliding_window") or 0)
        if cfg.get("use_sliding_window", True) else 0,
        experts_routed=pub["num_experts"], experts_held=cfg["num_experts"],
        top_k=cfg["num_experts_per_tok"],
        expert_ff=cfg["moe_intermediate_size"],
        aux_coef=float(cfg["router_aux_loss_coef"]),
        layers_published=pub["num_hidden_layers"],
        vocab_published=pub["vocab_size"])


def layout(spec: Spec, level: int) -> List[Leaf]:
    """Every parameter leaf, sorted by path: the dense decoder's attention,
    norms, embedding and head, QK-norm scales, and per layer the router
    (plain Adam) and the held experts' stacks ``(L, E_held, ...)`` (the
    wavelet rule on their last axis)."""
    L, d, f, H = spec.layers, spec.d, spec.expert_ff, spec.experts_held
    leaves = [lf for lf in dense.layout(spec, level)
              if not lf.path.startswith("layers/b0/ffn/")]
    for name in ("mixer/q_norm", "mixer/k_norm"):
        leaves.append(Leaf(f"layers/b0/{name}", (L, spec.head_dim), "adam",
                           True, "zeros"))
    leaves.append(Leaf("layers/b0/ffn/router", (L, d, spec.experts_routed),
                       "adam", True, "normal"))
    block = 1 << level
    for name, shape in (("w_gate", (H, d, f)), ("w_up", (H, d, f)),
                        ("w_down", (H, f, d))):
        stored = (L,) + shape
        gwt = bool(level) and stored[-1] % block == 0
        leaves.append(Leaf(f"layers/b0/ffn/{name}", stored,
                           "gwt" if gwt else "adam", True, "normal",
                           len(stored) - 1 if gwt else None))
    return sorted(leaves, key=lambda lf: lf.path)


def routed_params(spec: Spec) -> float:
    """PaLM's N for this chip's share: attention's matrices, the router,
    the experts a token reaches here on average (``top_k ·
    held/routed`` of them) and the output head."""
    d, f = spec.d, spec.expert_ff
    attn = (d * spec.q_width + 2 * d * spec.kv_width + spec.q_width * d)
    reached = spec.top_k * spec.experts_held / spec.experts_routed
    per_layer = attn + d * spec.experts_routed + reached * 3 * d * f
    return spec.layers * per_layer + d * spec.vocab


def model_flops_per_token(spec: Spec, seq: int) -> float:
    """PaLM's model FLOPs per trained token (Chowdhery et al. 2022, App. B),
    ``6·N + 12·L·H·Q·T``, N counting only the weights a token is routed to
    on this chip (:func:`routed_params`); recomputation not counted."""
    attn = 12 * spec.layers * spec.heads * spec.head_dim * seq
    return 6.0 * routed_params(spec) + attn


def gmm_work(spec: Spec, traffic: dict) -> tuple:
    """``(flops, bytes)`` per training step of the grouped matmuls: the
    three expert products over the rows routed to held experts (``T·K ·
    held/routed`` per layer, T the step's tokens), each in four passes —
    forward, the rematerialised forward, the backward's data and weight
    products — each pass reading its input rows and one weight matrix per
    held expert and writing its output once, in the configuration's
    dtype."""
    T = traffic["batch"] * traffic["seq"]
    R = T * spec.top_k * spec.experts_held / spec.experts_routed
    H, d, f = spec.experts_held, spec.d, spec.expert_ff
    b = DTYPE_BYTES[spec.dtype]
    flops = nbytes = 0.0
    for a, c in ((d, f), (d, f), (f, d)):
        flops += 4 * 2 * R * a * c
        nbytes += 4 * b * (R * (a + c) + H * a * c)
    return spec.layers * flops, spec.layers * nbytes


def program_config(spec: Spec, seq: int):
    """The program's configuration object for ``spec``."""
    from repro.configs.base import ModelConfig
    if spec.sliding_window and spec.sliding_window < seq:
        raise ValueError(f"{spec.name}: a sliding window of "
                         f"{spec.sliding_window} below seq {seq} is not run "
                         f"by this benchmark")
    return ModelConfig(
        name=spec.name, family="moe", n_layers=spec.layers, d_model=spec.d,
        n_heads=spec.heads, n_kv_heads=spec.kv_heads,
        head_dim=spec.head_dim, d_ff=0, vocab=spec.vocab,
        pattern=("attn+moe",), n_experts=spec.experts_routed,
        top_k=spec.top_k, d_ff_expert=spec.expert_ff,
        experts_held=spec.experts_held, router_aux_coef=spec.aux_coef,
        qk_norm=True, qkv_bias=spec.qkv_bias, rope_theta=spec.rope_theta,
        tie_embeddings=spec.tied, norm_eps=spec.norm_eps, dtype=spec.dtype,
        remat=True)


class Reference(dense.Reference):
    """The MoE decoder's loss and gradients (see the module docstring);
    the update and the loop are :class:`bench.reference.Trainer`'s.  After
    a :meth:`run`, ``held_share`` holds for each step, per layer, the share
    of the routed (token, k) pairs whose expert is held here."""

    FAULTS = ("drop",)        # this architecture's own planted faults

    def __init__(self, spec: Spec, opt: dict, seq: int, **kw):
        if kw.get("fault") not in (None, "half_batch") + self.FAULTS:
            raise ValueError(f"unknown fault {kw['fault']!r}")
        super().__init__(spec, opt, seq, **kw)
        self.leaves = layout(spec, opt["level"])
        self.held_share: List[List[float]] = []

    def run(self, weights, batches, keep_params: bool = False) -> dict:
        self.held_share = []
        return super().run(weights, batches, keep_params)

    def _attention(self, lp, x, cos, sin):
        """``x`` plus the attention sublayer's output, QK-norm before
        RoPE."""
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
        q = ref_lib.rms_norm(q.reshape(B, S, s.heads, s.head_dim),
                             lp["mixer/q_norm"], s.norm_eps)
        k = ref_lib.rms_norm(k.reshape(B, S, s.kv_heads, s.head_dim),
                             lp["mixer/k_norm"], s.norm_eps)
        q, k = ref_lib.rope(q, cos, sin), ref_lib.rope(k, cos, sin)
        v = v.reshape(B, S, s.kv_heads, s.head_dim)
        qg = q.reshape(B, S, s.kv_heads, G, s.head_dim)
        mask = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]

        @jax.checkpoint
        def group(args):
            qj, kj, vj = args          # (B,S,G,hd), (B,S,hd), (B,S,hd)
            sc = ein("bsgd,btd->bgst", qj, kj) / math.sqrt(s.head_dim)
            w = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
            return ein("bgst,btd->bsgd", w, vj)

        o = jax.lax.map(group, (jnp.moveaxis(qg, 2, 0), jnp.moveaxis(k, 2, 0),
                                jnp.moveaxis(v, 2, 0)))
        o = jnp.moveaxis(o, 0, 2).reshape(B, S, s.q_width)
        return x + ein("bsn,nd->bsd", o, lp["mixer/wo"])

    def route(self, lp, h):
        """``(gates (B,S,E_held), aux, held share)``: each token's
        renormalised top-k gate for each held expert (zero where not
        picked, or with the ``drop`` fault where past capacity)."""
        s = self.spec
        E, K, H = s.experts_routed, s.top_k, s.experts_held
        B, S, _ = h.shape
        T = B * S
        probs = jax.nn.softmax(self.ein("bsd,de->bse", h, lp["ffn/router"]),
                               axis=-1)
        gate, idx = jax.lax.top_k(probs, K)
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)     # (B,S,K,E)
        f = jnp.sum(onehot, axis=(0, 1, 2)) / T
        aux = E * jnp.sum(f * jnp.mean(probs, axis=(0, 1)))
        if self.fault == "drop":
            flat = jax.nn.one_hot(idx.reshape(T * K), E, dtype=jnp.int32)
            slot = jnp.sum((jnp.cumsum(flat, axis=0) - flat) * flat, axis=1)
            gate = gate * (slot < math.ceil(T * K / E)).reshape(B, S, K)
        gates = jnp.sum(onehot[..., :H] * gate[..., None], axis=2)
        held = jnp.sum(onehot[..., :H]) / (T * K)
        return gates, aux, held

    def _moe(self, lp, x):
        """``(x + the held experts' part of the expert FFN, aux, held
        share)``."""
        ein = self.ein
        h = ref_lib.rms_norm(x, lp["norm2"], self.spec.norm_eps)
        gates, aux, held = self.route(lp, h)

        @jax.checkpoint
        def expert(acc, args):
            wg, wu, wd, g = args
            a = jax.nn.silu(ein("bsd,df->bsf", h, wg)) \
                * ein("bsd,df->bsf", h, wu)
            return acc + g[..., None] * ein("bsf,fd->bsd", a, wd), None

        out, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                              (lp["ffn/w_gate"], lp["ffn/w_up"],
                               lp["ffn/w_down"], jnp.moveaxis(gates, -1, 0)))
        return x + out, aux, held

    def _layer(self, lp, x, cos, sin):
        y, aux, held = self._moe(lp, self._attention(lp, x, cos, sin))
        return y, (aux, held)

    def _build(self):
        super()._build()
        f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
        coef = jnp.float32(self.spec.aux_coef)

        @jax.jit
        def layer_fwd(lp, x, cos, sin):
            return self._layer(f32(lp), x, cos, sin)

        @jax.jit
        def layer_bwd(lp, x, dy, cos, sin):
            _, vjp = jax.vjp(lambda p, xx: self._layer(p, xx, cos, sin),
                             f32(lp), x)
            return vjp((dy, (coef, jnp.float32(0.0))))

        self._layer_fwd, self._layer_bwd = layer_fwd, layer_bwd

    def grads(self, params: Dict[str, jax.Array], batch) -> tuple:
        """``(loss, {path: f32 gradient})`` of one batch: the mean
        cross-entropy plus ``router_aux_coef`` times the layers' aux
        terms."""
        s = self.spec
        tokens = jnp.asarray(batch["tokens"])
        labels = jnp.asarray(batch["labels"])
        names = [lf.path[len("layers/b0/"):] for lf in self.leaves
                 if lf.stacked]
        layer = lambda i: {n: params["layers/b0/" + n][i] for n in names}
        xs = [self._embed(params["embed/embedding"], tokens)]
        aux, held = [], []
        for i in range(s.layers):
            x, (a, hs) = self._layer_fwd(layer(i), xs[-1], self.cos,
                                         self.sin)
            xs.append(x)
            aux.append(a)
            held.append(hs)
        w = params["embed/embedding"] if s.tied else params["embed/lm_head"]
        loss, (dx, dfn, dw) = self._head(xs[-1], params["final_norm"], w,
                                         labels)
        loss = float(loss) + s.aux_coef * float(sum(aux))
        self.held_share.append([float(v) for v in held])
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
        return loss, g
