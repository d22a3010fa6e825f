"""A configuration file as the benchmark reads it, and the parameter layout
it implies.

A configuration is a JSON file under ``bench/configs/``: the model's public
``config.json`` numbers under their own keys (``hidden_size``,
``num_hidden_layers``, ...), with the keys changed from the source listed
in ``reduced``.  :class:`Spec` is the benchmark's own reading of it; the
driver turns a Spec into the program's configuration object, and the plain
reference (``bench/reference.py``) and the counters (``bench/counts.py``)
read the Spec alone.

:func:`layout` lists every parameter the decoder holds, in the program's
tree layout (layer stacks carry a leading layer axis), with the update rule
the benchmark holds the optimizer to: the wavelet rule for every leaf of an
attention or MLP module whose stored array (layer stacks are at least
2-D) has a last axis divisible by ``2**level``, plain Adam for the rest
(embedding, output head, norms).
"""

from __future__ import annotations

import dataclasses
import json
import math
import pathlib
from typing import List, Optional, Tuple


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


@dataclasses.dataclass(frozen=True)
class Leaf:
    path: str                 # '/'-joined, as the program's tree has it
    shape: Tuple[int, ...]    # as stored (layer stacks lead with L)
    rule: str                 # "gwt" | "adam"
    stacked: bool             # leading axis is the layer axis
    init: str                 # "normal" | "zeros"
    axis: Optional[int] = None  # wavelet transform axis (gwt only: last)


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


def numel(shape) -> int:
    return math.prod(shape)
