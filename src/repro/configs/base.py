"""Config system: ModelConfig (architecture) + ShapeConfig (workload cell).

Every assigned architecture is a ``ModelConfig`` in ``repro/configs/<id>.py``;
the four assigned input shapes are ``SHAPES`` below.  ``input_specs()``
produces ShapeDtypeStruct stand-ins (no allocation) for the dry-run.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class ModelConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    # layer-kind pattern for ONE period; entries: "attn", "attn_local",
    # "mamba", "mlstm", "slstm"; "+moe" suffix swaps the MLP for MoE.
    pattern: Tuple[str, ...] = ("attn",)
    arch_class: str = "decoder"          # decoder | encdec
    family: str = "dense"                # dense | moe | hybrid | ssm | vlm | audio
    # MoE
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    d_ff_expert: int = 0
    capacity_factor: float = 1.25
    # pad the expert WEIGHT arrays to n_experts+padding (router stays at
    # n_experts; padded experts are never routed).  Lets a 16-∤ expert count
    # (qwen2-moe's 60) shard EP-cleanly over the 16-way model axis instead
    # of falling back to TP-in-expert (beyond-paper optimization, §Perf).
    expert_padding: int = 0
    # experts whose weights this chip holds, 0..experts_held-1 (0: all of
    # n_experts).  The router keeps its n_experts outputs and top_k; the
    # layer adds the part of the result its own experts give (the chip's
    # share under expert parallelism, without the exchange).
    experts_held: int = 0
    router_aux_coef: float = 0.01        # weight of the load-balance loss
    # attention details
    window: int = 0                      # sliding window for attn_local
    attn_softcap: float = 0.0            # gemma-2 logit soft-capping
    final_softcap: float = 0.0
    qkv_bias: bool = False
    qk_norm: bool = False                # qwen3-style
    rope_theta: float = 1e4
    mrope_sections: Tuple[int, ...] = () # qwen2-vl M-RoPE (t,h,w) head_dim split
    # SSM (mamba)
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_expand: int = 2
    # enc-dec split (seamless): n_layers = n_enc + n_dec
    n_enc_layers: int = 0
    n_dec_layers: int = 0
    # misc
    tie_embeddings: bool = True
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    sub_quadratic: bool = False          # eligible for long_500k
    remat: bool = True

    @property
    def period(self) -> int:
        return len(self.pattern)

    @property
    def n_periods(self) -> int:
        return self.n_layers // self.period

    @property
    def rem_layers(self) -> int:
        return self.n_layers % self.period

    @property
    def d_inner(self) -> int:  # mamba inner width
        return self.ssm_expand * self.d_model

    def with_(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str            # "train" | "prefill" | "decode"
    accum_steps: int = 1 # gradient-accumulation microbatches (train only)


# The four assigned LM shapes (assignment block).  ``accum_steps`` here is a
# default; per-arch overrides live in the arch configs (memory-budget driven).
SHAPES = {
    "train_4k":    ShapeConfig("train_4k", 4096, 256, "train", accum_steps=16),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k":  ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k":   ShapeConfig("long_500k", 524288, 1, "decode"),
}


def input_specs(cfg: ModelConfig, shape: ShapeConfig):
    """ShapeDtypeStruct stand-ins for every model input — no allocation."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if shape.kind == "train":
        batch = {"tokens": jax.ShapeDtypeStruct((B, S), i32),
                 "labels": jax.ShapeDtypeStruct((B, S), i32)}
        if cfg.arch_class == "encdec":
            # audio frontend stub: precomputed frame embeddings (assignment)
            batch["enc_embeds"] = jax.ShapeDtypeStruct(
                (B, S // 4, cfg.d_model), jnp.bfloat16)
        if cfg.mrope_sections:
            batch["mrope_positions"] = jax.ShapeDtypeStruct((3, B, S), i32)
        return batch
    if shape.kind == "prefill":
        batch = {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
        if cfg.arch_class == "encdec":
            batch["enc_embeds"] = jax.ShapeDtypeStruct(
                (B, S // 4, cfg.d_model), jnp.bfloat16)
        if cfg.mrope_sections:
            batch["mrope_positions"] = jax.ShapeDtypeStruct((3, B, S), i32)
        return batch
    # decode: one new token against a seq_len-deep cache
    batch = {"tokens": jax.ShapeDtypeStruct((B, 1), i32)}
    if cfg.mrope_sections:
        batch["mrope_positions"] = jax.ShapeDtypeStruct((3, B, 1), i32)
    return batch


def skip_reason(cfg: ModelConfig, shape: ShapeConfig) -> Optional[str]:
    """Assignment skip rules (documented in DESIGN.md §6)."""
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return ("pure full-attention arch: long_500k needs sub-quadratic "
                "attention (assignment rule)")
    return None
