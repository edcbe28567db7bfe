"""Seeded random weights, made on the device in one jitted call.

The benchmark owns the weights: the program under test and the plain
reference both start from the canonical tree this module returns for a
seed, so the reference takes nothing that the program made.

Projections are N(0, 1/sqrt(fan_in)), which keeps every layer's output at
unit scale, so the residual stream, and with it each next token, depends on
the context and not on the current token alone: a fault in the KV cache
changes what is served. The embedding and the head are N(0, 0.02); QKV
biases N(0, 0.5) and norm gains 1 + N(0, 0.1), so that a path that drops a
bias or a gain changes the result too.

Canonical tree (layers stacked on a leading axis L):
  embed (V, D); final_norm (D,); lm_head (D, V) unless tied;
  layers: ln1, ln2 (L, D); wq (L, D, H*hd); wk, wv (L, D, KV*hd);
          wo (L, H*hd, D); gate, up (L, D, F); down (L, F, D);
          bq (L, H*hd), bk, bv (L, KV*hd) with attention_bias;
          q_norm, k_norm (L, hd) with qk_norm.
"""

from __future__ import annotations

import functools

from benchlib import traffic


def shapes(cfg: dict) -> dict:
    """Leaf name -> (shape, kind), kind one of 'matrix', 'table', 'bias',
    'gain'."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    n_l, v, hd = cfg["num_hidden_layers"], cfg["vocab_size"], cfg["head_dim"]
    qd = cfg["num_attention_heads"] * hd
    kd = cfg["num_key_value_heads"] * hd
    out = {
        "embed": ((v, d), "table"),
        "final_norm": ((d,), "gain"),
        "ln1": ((n_l, d), "gain"),
        "ln2": ((n_l, d), "gain"),
        "wq": ((n_l, d, qd), "matrix"),
        "wk": ((n_l, d, kd), "matrix"),
        "wv": ((n_l, d, kd), "matrix"),
        "wo": ((n_l, qd, d), "matrix"),
        "gate": ((n_l, d, f), "matrix"),
        "up": ((n_l, d, f), "matrix"),
        "down": ((n_l, f, d), "matrix"),
    }
    if not cfg["tie_word_embeddings"]:
        out["lm_head"] = ((d, v), "table")
    if cfg["attention_bias"]:
        out["bq"] = ((n_l, qd), "bias")
        out["bk"] = ((n_l, kd), "bias")
        out["bv"] = ((n_l, kd), "bias")
    if cfg["qk_norm"]:
        out["q_norm"] = ((n_l, hd), "gain")
        out["k_norm"] = ((n_l, hd), "gain")
    return out


def key_seed(seed: int) -> int:
    """A 31-bit JAX key seed derived from any whole-number seed."""
    return int(traffic.rng(seed, traffic.WEIGHTS).integers(0, 2**31 - 1))


@functools.lru_cache(maxsize=None)
def _maker(layout: tuple, dtype_name: str):
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype_name)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(layout))
        out = {}
        for k, (name, shape, kind) in zip(keys, layout):
            z = jax.random.normal(k, shape, jnp.float32)
            std = {"table": 0.02, "bias": 0.5, "gain": 0.1}.get(kind)
            if kind == "matrix":
                std = shape[-2] ** -0.5
            w = 1.0 + std * z if kind == "gain" else std * z
            out[name] = w.astype(dtype)
        return out

    return make


def make(cfg: dict, seed: int):
    """The canonical weights of ``cfg`` for ``seed``, in the served dtype."""
    import jax

    layout = tuple(
        (name, shape, kind) for name, (shape, kind) in sorted(shapes(cfg).items())
    )
    out = _maker(layout, cfg["torch_dtype"])(jax.random.PRNGKey(key_seed(seed)))
    return jax.block_until_ready(out)
