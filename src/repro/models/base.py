"""Model zoo foundation: config, parameter pytrees, logical sharding axes.

Every architecture is described by one `ModelConfig`. Parameters are built as
*stacked* pytrees: layers are grouped into repeating periods (dense LMs have
period 1; Jamba has period 8; Llama-3.2-Vision has period 5) and each leaf
carries a leading `groups` dimension so the forward pass is a single
`lax.scan` — HLO size is O(1) in depth, which is what makes 72-layer/398B
configs lower+compile in the 512-device dry-run.

Each parameter leaf has a parallel *logical axes* annotation (a tuple of axis
names like ("layers", "embed", "heads")); `repro.distributed.sharding` maps
logical axes onto the production mesh.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0  # 0 -> d_model // n_heads
    qkv_bias: bool = False
    qk_norm: bool = False
    norm_type: str = "rmsnorm"  # rmsnorm | layernorm
    gated_mlp: bool = True  # SwiGLU vs plain MLP
    mlp_act: str = "gelu"  # non-gated MLP activation: gelu | relu2
    rope_theta: float = 1e4
    sliding_window: int = 0  # 0 -> full attention
    tie_embeddings: bool = False
    # MoE
    n_experts: int = 0
    top_k: int = 0
    moe_every: int = 1  # every k-th layer position is MoE (within a period)
    shared_expert: bool = False
    capacity_factor: float = 1.25
    # hybrid / ssm
    attn_every: int = 0  # jamba: one attention layer per this many layers
    d_state: int = 16
    d_conv: int = 4
    ssm_expand: int = 2
    rwkv_head_dim: int = 64
    # hybrid SSM layers: "mamba" (Mamba-1, Jamba) | "mamba2" (SSD, Granite-4.0-H)
    ssm_mixer: str = "mamba"
    ssm_head_dim: int = 64  # Mamba-2: d_inner // ssm_head_dim heads, one B/C group
    ssm_chunk: int = 256  # Mamba-2 chunked-SSD prefill chunk
    # vlm
    cross_attn_every: int = 0  # one cross-attn layer per this many layers
    n_img_tokens: int = 0
    # audio
    n_codebooks: int = 0
    # numerics
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32
    # dry-run analysis mode: fully unroll every lax.scan so XLA cost analysis
    # (which visits While bodies once) counts true totals. Never used for the
    # memory pass or real execution.
    scan_unroll: bool = False
    flash_chunk: int = 1024  # q/kv chunk for flash-style attention
    kv_quant: bool = False  # int8 KV cache (+per-token scales) for decode
    # Granite-4.0 scalars; each default is a no-op that adds no operation
    rope: bool = True  # False: attention without position encoding (NoPE)
    attn_scale: float = 0.0  # attention score scale; 0 -> 1/sqrt(head_dim)
    norm_eps: float = 1e-6  # RMSNorm epsilon
    embed_mult: float = 1.0  # embeddings times this
    residual_mult: float = 1.0  # each residual add is x + residual_mult * f(x)
    logits_div: float = 1.0  # logits divided by this

    @property
    def unroll(self):
        return True if self.scan_unroll else 1

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def period(self) -> int:
        """Layers per scan step (the repeating block pattern)."""
        if self.family == "hybrid":
            return self.attn_every  # e.g. jamba: 8 (1 attn : 7 mamba)
        if self.family == "vlm":
            return self.cross_attn_every  # e.g. 5 (4 self + 1 cross)
        if self.n_experts and self.moe_every > 1:
            return self.moe_every
        return 1

    @property
    def n_groups(self) -> int:
        assert self.n_layers % self.period == 0, (self.n_layers, self.period)
        return self.n_layers // self.period

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    def layer_kind(self, pos: int) -> dict:
        """Describe period position `pos`: mixer type + ffn type."""
        if self.family == "hybrid":
            mixer = "attn" if pos == self.attn_every // 2 else self.ssm_mixer
            ffn = "moe" if (self.n_experts and pos % 2 == 1) else "mlp"
        elif self.family == "vlm":
            mixer = "cross" if pos == self.period - 1 else "attn"
            ffn = "mlp"
        elif self.family == "ssm":
            mixer, ffn = "rwkv", "rwkv_cm"
        elif self.family == "moe":
            mixer = "attn"
            ffn = "moe" if (pos % self.moe_every == self.moe_every - 1) else "mlp"
        else:
            mixer, ffn = "attn", "mlp"
        return {"mixer": mixer, "ffn": ffn}

    def param_count(self) -> tuple[int, int]:
        """(total, active-per-token) parameter counts, computed analytically."""
        total = active = 0
        for pos in range(self.period):
            kind = self.layer_kind(pos)
            t, a = _layer_params(self, kind)
            total += t * self.n_groups
            active += a * self.n_groups
        emb = self.vocab * self.d_model * max(1, self.n_codebooks or 1)
        head = 0 if self.tie_embeddings else self.vocab * self.d_model * max(
            1, self.n_codebooks or 1
        )
        total += emb + head
        active += emb + head
        if self.cross_attn_every:
            pass  # cross-attn weights counted in _layer_params
        return total, active


def _layer_params(cfg: ModelConfig, kind: dict) -> tuple[int, int]:
    d, f, hd = cfg.d_model, cfg.d_ff, cfg.hd
    t = a = 0
    if kind["mixer"] in ("attn", "cross"):
        qkv = d * cfg.n_heads * hd + 2 * d * cfg.n_kv_heads * hd + cfg.n_heads * hd * d
        t += qkv
        a += qkv
    elif kind["mixer"] == "mamba":
        di, ds = cfg.d_inner, cfg.d_state
        m = d * 2 * di + di * cfg.d_conv + di * (2 * ds + math.ceil(d / 16)) + di * d + di
        t += m
        a += m
    elif kind["mixer"] == "mamba2":
        di, ds, k = cfg.d_inner, cfg.d_state, cfg.d_conv
        h, conv = di // cfg.ssm_head_dim, di + 2 * ds
        m = d * (di + conv + h) + conv * (k + 1) + 3 * h + di + di * d
        t += m
        a += m
    elif kind["mixer"] == "rwkv":
        n = 5 * d * d + d * 64 * 2  # r/k/v/g/o projections + lora adapters (approx)
        t += n
        a += n
    if kind["ffn"] == "moe":
        per_exp = (3 if cfg.gated_mlp else 2) * d * f
        t += cfg.n_experts * per_exp + d * cfg.n_experts
        a += cfg.top_k * per_exp + d * cfg.n_experts
        if cfg.shared_expert:
            t += per_exp
            a += per_exp
    elif kind["ffn"] == "rwkv_cm":
        n = d * int(3.5 * d) * 2
        t += n
        a += n
    else:
        per = (3 if cfg.gated_mlp else 2) * d * f
        t += per
        a += per
    return t, a


# ---------------------------------------------------------------------------
# Parameter tree construction. Leaves are `Spec(shape, logical_axes, init)`;
# `materialize` turns a spec tree into arrays, `struct` into ShapeDtypeStructs.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Spec:
    shape: tuple
    axes: tuple  # logical axis names, same length as shape
    init: str = "normal"  # normal | zeros | ones | decay
    scale: float = 1.0


def spec_tree_map(fn, tree):
    return jax.tree_util.tree_map(
        fn, tree, is_leaf=lambda x: isinstance(x, Spec)
    )


def materialize(spec_tree, key, dtype):
    leaves, treedef = jax.tree_util.tree_flatten(
        spec_tree, is_leaf=lambda x: isinstance(x, Spec)
    )
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, s in zip(keys, leaves):
        if s.init == "zeros":
            a = jnp.zeros(s.shape, dtype)
        elif s.init == "ones":
            a = jnp.ones(s.shape, dtype)
        elif s.init == "decay":  # rwkv/mamba decay logits: small negatives
            a = jnp.linspace(-6.0, -0.5, num=int(np.prod(s.shape))).reshape(s.shape).astype(dtype)
        else:
            fan_in = s.shape[-2] if len(s.shape) >= 2 else s.shape[-1]
            a = (jax.random.normal(k, s.shape) * (s.scale / math.sqrt(fan_in))).astype(dtype)
        out.append(a)
    return jax.tree_util.tree_unflatten(treedef, out)


def struct(spec_tree, dtype):
    return spec_tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype), spec_tree
    )


def axes_tree(spec_tree):
    return spec_tree_map(lambda s: s.axes, spec_tree)
