"""Assigned input-shape sets and ShapeDtypeStruct builders for the dry-run.

Every LM-family arch is paired with four shapes:
  train_4k    seq 4096,   global_batch 256  -> train_step
  prefill_32k seq 32768,  global_batch 32   -> prefill_step
  decode_32k  seq 32768 (KV), global_batch 128 -> serve_step (1 new token)
  long_500k   seq 524288 (KV), global_batch 1  -> serve_step; sub-quadratic
              archs only (rwkv6 SSM, mixtral SWA, jamba hybrid) — skips are
              recorded in DESIGN.md §Arch-applicability.

`input_specs` returns ShapeDtypeStructs only: the dry-run never allocates.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models import lm
from repro.models.base import ModelConfig


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    kind: str  # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES = {
    "train_4k": ShapeSpec("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeSpec("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeSpec("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeSpec("long_500k", "decode", 524288, 1),
}

# Sub-quadratic bar for long_500k: SSM / SWA / hybrid only.
LONG_CONTEXT_ARCHS = {"rwkv6-3b", "mixtral-8x22b", "jamba-1.5-large-398b"}


# ---------------------------------------------------------------------------
# Memory domains (multi-rail undervolting, DESIGN.md §10)
# ---------------------------------------------------------------------------
# The BRAM arena is partitioned into named voltage domains; each domain gets
# its own rail, fault-field slice, and ECC counter row. Order is the counter
# row order everywhere (kernel, telemetry, controller). `MEMORY_DOMAINS` is
# the registry; `domain_of` classifies a flattened-pytree leaf key into one.
# Substrings are matched in order, so e.g. "['blocks']['p0']['attn']['wq']"
# lands in "attention" before the "mlp" patterns are consulted.
MEMORY_DOMAINS: tuple[str, ...] = ("embedding", "attention", "mlp", "kv", "ssm")

_DOMAIN_PATTERNS: tuple[tuple[str, tuple[str, ...]], ...] = (
    ("kv", ("kv", "cache")),
    ("embedding", ("embed", "unembed", "vocab")),
    # Mamba-2 projections and recurrent state (core/statestore.py)
    ("ssm", ("mamba2", "ssm")),
    ("attention", ("attn", "attention", "w_r", "w_k", "w_v", "w_g", "w_o")),
    ("mlp", ("mlp", "ffn", "moe", "expert", "in_proj", "out_proj")),
)


def domain_of(key: str, default: str = "mlp") -> str:
    """Map a pytree leaf key (jax.tree_util.keystr) to its memory domain."""
    low = key.lower()
    for name, pats in _DOMAIN_PATTERNS:
        if any(p in low for p in pats):
            return name
    return default


# Default ECC scheme per memory domain (DESIGN.md §12). The built-in BRAM
# SECDED everywhere, matching the paper; engines override per domain via
# ReliabilityConfig.codecs, and the controller escalation ladder may move a
# domain up at runtime.
from repro.codes import DEFAULT_CODEC  # noqa: E402 (single source of truth)


def domain_codecs(overrides=None) -> dict[str, str]:
    """Resolve a codec choice into a full {domain: codec name} mapping.

    ``overrides`` may be None (all defaults), a codec name (every domain),
    or a {domain: name} mapping (unnamed domains keep the default). Codec
    names are validated against the registry, domain names against
    MEMORY_DOMAINS — a typo'd domain silently keeping its default codec is
    exactly the misconfiguration this helper exists to prevent.
    """
    from repro import codes

    out = {d: DEFAULT_CODEC for d in MEMORY_DOMAINS}
    if overrides is None:
        pass
    elif isinstance(overrides, str):
        out = {d: overrides for d in out}
    else:
        for d, name in dict(overrides).items():
            assert d in out, f"unknown memory domain {d!r}; known: {sorted(out)}"
            out[d] = str(name)
    for name in out.values():
        codes.get(name)  # fail fast on unknown codecs
    return out


def rail_policy(name: str) -> str:
    """Validate a mesh rail policy name (DESIGN.md §13).

    ``uniform``: one voltage per domain across every chip, locked at the
    worst shard's first DED. ``per_shard``: every chip walks its own V_min.
    Validated here, next to the memory-domain registry, for the same reason
    as ``domain_codecs``: a typo'd policy silently falling back to a default
    is the misconfiguration to prevent.
    """
    from repro.core.controller import RAIL_POLICIES

    name = str(name)
    assert name in RAIL_POLICIES, (
        f"unknown rail policy {name!r}; known: {RAIL_POLICIES}"
    )
    return name


def has_state_layers(cfg: ModelConfig) -> bool:
    """Whether some mixer keeps a per-lane recurrent state in the SECDED
    state store (core/statestore.py) instead of pages."""
    return any(
        cfg.layer_kind(j)["mixer"] == "mamba2" for j in range(cfg.period)
    )


def supports_paged_kv(cfg: ModelConfig) -> bool:
    """Whether the protected serve() path covers this arch.

    Paging fixed-size token pages assumes every attention mixer is
    full-context with a position-indexed cache; the only other mixer
    admitted is Mamba-2, whose O(1)-per-lane state lives in the SECDED state
    store beside the pages. Mamba-1 and RWKV state has no protected store,
    SWA ring buffers and quantized caches keep their own layouts, and
    codebook decoders interleave tokens.
    """
    mixers = {cfg.layer_kind(j)["mixer"] for j in range(cfg.period)}
    return (
        "attn" in mixers
        and mixers <= {"attn", "mamba2"}
        and not cfg.sliding_window
        and not cfg.kv_quant
        and not cfg.n_codebooks
    )


def supported_shapes(arch: str) -> list[str]:
    names = ["train_4k", "prefill_32k", "decode_32k"]
    if arch in LONG_CONTEXT_ARCHS:
        names.append("long_500k")
    return names


def _tok_struct(cfg: ModelConfig, b: int, s: int):
    if cfg.n_codebooks:
        return jax.ShapeDtypeStruct((b, cfg.n_codebooks, s), jnp.int32)
    return jax.ShapeDtypeStruct((b, s), jnp.int32)


def input_specs(cfg: ModelConfig, shape_name: str, *, batch_override: int = 0):
    """ShapeDtypeStruct stand-ins for every input of the step function."""
    sh = SHAPES[shape_name]
    b = batch_override or sh.global_batch
    s = sh.seq_len

    if sh.kind == "train":
        specs = {
            "tokens": _tok_struct(cfg, b, s),
            "labels": _tok_struct(cfg, b, s),
        }
        if cfg.family == "vlm":
            specs["img"] = jax.ShapeDtypeStruct(
                (b, cfg.n_img_tokens, cfg.d_model), cfg.compute_dtype
            )
        return specs

    if sh.kind == "prefill":
        specs = {
            "tokens": _tok_struct(cfg, b, s),
            "cache": cache_struct(cfg, b, s),
        }
        if cfg.family == "vlm":
            specs["img"] = jax.ShapeDtypeStruct(
                (b, cfg.n_img_tokens, cfg.d_model), cfg.compute_dtype
            )
        return specs

    # decode: one new token against a seq_len-deep cache/state
    specs = {
        "tokens": _tok_struct(cfg, b, 1),
        "cache": cache_struct(cfg, b, s),
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }
    if cfg.family == "vlm":
        specs["img"] = jax.ShapeDtypeStruct(
            (b, cfg.n_img_tokens, cfg.d_model), cfg.compute_dtype
        )
    return specs


def cache_struct(cfg: ModelConfig, batch: int, max_len: int):
    """ShapeDtypeStruct tree of the decode cache (no allocation)."""
    return jax.eval_shape(
        lambda: lm.init_cache(
            cfg, batch, max_len, img_tokens=cfg.n_img_tokens
        )
    )
