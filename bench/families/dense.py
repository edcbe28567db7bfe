"""Family ``dense``: a decoder of identical attention + SwiGLU MLP layers
(Qwen2, Qwen3), run by ``repro.models.lm`` as one repeated block.

A family is the part of the benchmark that knows a layer structure. A
configuration names its family under ``"family"``, and the harness finds
``bench/families/<family>.py`` by that name (``registry.load_family``). Each
family module provides:

  shapes(cfg) -> {leaf: (shape, kind)}
      the canonical weight tree; ``weights.make`` draws every leaf from the
      seed, leaves in sorted order, one PRNG key each.
  KINDS: {kind: init(key, shape) -> float32 array}
      init rules for kinds beyond the shared four of ``weights.py``
      (``matrix``, ``table``, ``bias``, ``gain``), e.g. a decay that must
      stay negative; empty where the shared four suffice.
  model_config(cfg)
      the program's ``repro.models.base.ModelConfig``.
  program_params(w, cfg)
      canonical weights -> the program's parameter tree.
  matmul_params(cfg), token_flops(cfg, context)
      parameters each token multiplies, and model FLOPs of one token that
      attends over ``context`` positions: what ``serve_mfu`` counts.
  REFERENCE
      the name of the plain reference, ``bench/reference/<REFERENCE>.py``,
      which provides ``prepare(w, cfg)`` and
      ``logits(rw, cfg, tokens, rounding=None)``.

Canonical tree of this family (layers stacked on a leading axis L):
  embed (V, D); final_norm (D,); lm_head (D, V) unless tied;
  layers: ln1, ln2 (L, D); wq (L, D, H*hd); wk, wv (L, D, KV*hd);
          wo (L, H*hd, D); gate, up (L, D, F); down (L, F, D);
          bq (L, H*hd), bk, bv (L, KV*hd) with attention_bias;
          q_norm, k_norm (L, hd) with qk_norm.
"""

from __future__ import annotations

REFERENCE = "dense_lm"
KINDS: dict = {}


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


def model_config(cfg: dict):
    import jax.numpy as jnp

    from repro.models.base import ModelConfig

    dtype = jnp.dtype(cfg["torch_dtype"])
    return ModelConfig(
        name=cfg["name"],
        family="dense",
        n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"],
        vocab=cfg["vocab_size"],
        qkv_bias=cfg["attention_bias"],
        qk_norm=cfg["qk_norm"],
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        param_dtype=dtype,
        compute_dtype=dtype,
    )


def program_params(w: dict, cfg: dict) -> dict:
    """Canonical weights -> the program's parameter tree (models/lm.py)."""
    attn = {k: w[k] for k in ("wq", "wk", "wv", "wo")}
    for k in ("bq", "bk", "bv", "q_norm", "k_norm"):
        if k in w:
            attn[k] = w[k]
    layer = {
        "ln1": {"gamma": w["ln1"]},
        "ln2": {"gamma": w["ln2"]},
        "attn": attn,
        "mlp": {"w1": w["gate"], "w3": w["up"], "w2": w["down"]},
    }
    tree = {"embed": w["embed"], "blocks": {"p0": layer}, "final_norm": {"gamma": w["final_norm"]}}
    if not cfg["tie_word_embeddings"]:
        tree["lm_head"] = w["lm_head"]
    return tree


def matmul_params(cfg: dict) -> int:
    """Parameters that each token multiplies: every projection and the head
    (the embedding gather is not a matmul)."""
    d, f, n_l = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_hidden_layers"]
    hd = cfg["head_dim"]
    qd, kd = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    per_layer = d * qd + 2 * d * kd + qd * d + 3 * d * f
    return n_l * per_layer + d * cfg["vocab_size"]


def token_flops(cfg: dict, context: int) -> float:
    """Model FLOPs of one token that attends over ``context`` positions
    (itself included): 2 per matmul parameter, and 4 * head_dim per query
    head per attended position for the scores and the weighted sum."""
    attn = 4.0 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] * cfg["head_dim"] * context
    return 2.0 * matmul_params(cfg) + attn
