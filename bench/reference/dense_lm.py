"""Plain float32 reference of the Qwen2 / Qwen3 decoder, in ``jax.numpy``.

Written from the published architecture, not from the program under test:
token embedding; per layer RMSNorm, Q/K/V projections (with biases in
Qwen2), per-head RMSNorm of Q and K (Qwen3), rotary embedding on the two
halves of each head (theta from the config), causal grouped-query attention,
output projection, residual; RMSNorm, SwiGLU MLP (silu(x W_gate) * x W_up)
W_down, residual; final RMSNorm; logits against the tied embedding (Qwen3)
or the untied head (Qwen2).

Weights are the benchmark's canonical tree (bench/benchlib/weights.py).
The configuration states int8 storage for the protected matrices
(``precision.protected``), so the reference applies the same
dequantisation: per output column, scale = max|w| / 127 over the input
axis, w ~ clip(round(w / scale), -127, 127) * scale. Everything else is
float32 with matmuls at ``Precision.HIGHEST``.

``rounding`` rounds every matmul operand, the attention probabilities and
the residual stream to a lower precision: the control that a precision
cut must fail (``float8_e4m3fn`` for a configuration that states bfloat16).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def dequant_int8(w):
    """Symmetric int8 per output column (reduction over axis -2)."""
    w = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=-2, keepdims=True), 1e-12) / 127.0
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _protected(name: str, groups) -> bool:
    group = {
        "wq": "attn", "wk": "attn", "wv": "attn", "wo": "attn",
        "gate": "mlp", "up": "mlp", "down": "mlp", "embed": "embed",
    }.get(name)
    return group in groups


def prepare(w: dict, cfg: dict) -> dict:
    """Canonical weights -> float32 reference weights (int8 dequantised
    where the configuration protects the matrix)."""
    groups = cfg["precision"]["protected"]
    return {
        k: dequant_int8(v) if _protected(k, groups) else v.astype(jnp.float32)
        for k, v in w.items()
    }


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x: (S, H, hd) at positions 0..S-1; rotate the two halves."""
    s, _, hd = x.shape
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _identity(x):
    return x


def rounder(dtype_name: str | None):
    """x -> x rounded through ``dtype_name`` and back to float32."""
    if dtype_name is None:
        return _identity
    dt = jnp.dtype(dtype_name)
    return lambda x: x.astype(dt).astype(jnp.float32)


def hidden_states(rw: dict, cfg: dict, tokens, rd=_identity):
    """Final-norm hidden states (S, D) for one token sequence (S,)."""
    nh, nkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    s = tokens.shape[0]
    mm = lambda a, b: jnp.matmul(rd(a), rd(b), precision=HIGHEST)
    causal = jnp.tril(jnp.ones((s, s), bool))
    layer_keys = [
        k for k in ("ln1", "ln2", "wq", "wk", "wv", "wo", "gate", "up", "down",
                    "bq", "bk", "bv", "q_norm", "k_norm")
        if k in rw
    ]

    def layer(x, p):
        h = _rms(x, p["ln1"], eps)
        q, k, v = mm(h, p["wq"]), mm(h, p["wk"]), mm(h, p["wv"])
        if "bq" in p:
            q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
        q = q.reshape(s, nh, hd)
        k = k.reshape(s, nkv, hd)
        v = v.reshape(s, nkv, hd)
        if "q_norm" in p:
            q = _rms(q, p["q_norm"], eps)
            k = _rms(k, p["k_norm"], eps)
        q, k = _rope(q, theta), _rope(k, theta)
        rep = nh // nkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", rd(q), rd(k), precision=HIGHEST) / jnp.sqrt(
            jnp.float32(hd)
        )
        sc = jnp.where(causal[None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", rd(pr), rd(v), precision=HIGHEST)
        x = rd(x + mm(o.reshape(s, nh * hd), p["wo"]))
        h = _rms(x, p["ln2"], eps)
        y = mm(jax.nn.silu(mm(h, p["gate"])) * mm(h, p["up"]), p["down"])
        return rd(x + y), None

    x = rd(rw["embed"][tokens])
    x, _ = jax.lax.scan(layer, x, {k: rw[k] for k in layer_keys})
    return _rms(x, rw["final_norm"], eps)


def head(rw: dict, cfg: dict):
    """(D, V) unembedding: the tied embedding or the untied head."""
    return rw["embed"].T if cfg["tie_word_embeddings"] else rw["lm_head"]


@functools.partial(jax.jit, static_argnames=("cfg_items", "rounding"))
def _logits(rw, tokens, *, cfg_items, rounding):
    cfg = dict(cfg_items)
    rd = rounder(rounding)
    h = hidden_states(rw, cfg, tokens, rd)
    return jnp.matmul(rd(h), rd(head(rw, cfg)), precision=HIGHEST)


def logits(rw: dict, cfg: dict, tokens, rounding: str | None = None):
    """Teacher-forced logits (S, V) of one sequence; ``rounding`` selects
    the lower-precision control."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta", "tie_word_embeddings")
    items = tuple((k, cfg[k]) for k in keys)
    return _logits(rw, jnp.asarray(tokens, jnp.int32), cfg_items=items, rounding=rounding)
