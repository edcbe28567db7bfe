"""Family ``hybrid_mamba2``: attention + Mamba-2 (SSD) layers in a repeating
pattern, every layer with a SwiGLU MLP (IBM Granite-4.0-H,
``granitemoehybrid`` without experts), run by ``repro.models.lm`` as a
hybrid period with the attention layer at its middle.

The interface is the one ``bench/families/dense.py`` describes. Layer
``i``'s kind is ``layer_types[i]`` ("mamba" or "attention"); the program
needs the pattern to repeat with period P and one attention layer at P // 2
(Granite-4.0-H: P = 10, attention at 5, 15, 25, 35).

Canonical tree (MLP and norms stacked over all L layers, attention leaves
over the A attention layers, Mamba-2 leaves over the M Mamba-2 layers, each
in layer order):
  embed (V, D); final_norm (D,);
  ln1, ln2 (L, D); gate, up (L, D, F); down (L, F, D);
  wq (A, D, H*hd); wk, wv (A, D, KV*hd); wo (A, H*hd, D);
  in_proj (M, D, di + di + 2N + Hs); conv_w (M, di + 2N, K); conv_b (M, di + 2N);
  dt_bias, a_log, d_skip (M, Hs); ssm_norm (M, di); out_proj (M, di, D)
with di = mamba_expand * D, Hs = di / mamba_d_head heads and N =
mamba_d_state. Init of the Mamba-2 leaves as Mamba-2 does it (the
configuration's ``assumed``): A_log = log U[1, 16], dt_bias the inverse
softplus of log-uniform values in [1e-3, 1e-1], D = 1, the depthwise conv
kernel U(-1/sqrt(K), 1/sqrt(K)).

The matrices that write into the residual stream (``wo``, ``down``,
``out_proj``) are of kind ``write``: a ``matrix`` divided by the published
``residual_multiplier`` 0.22, so that each layer adds at unit scale, as a
``matrix`` does in the dense family. With plain ``matrix`` writes the
embedding (times 12) outweighs what the layers add, and through the tied head
every position's best token is its own input token: the served text repeats
one token, and the reference, even at float8, agrees with it everywhere.
"""

from __future__ import annotations

import math

# The program's Mamba-2 mixer; a program without it cannot run this family,
# and a run fails here, before any weight is made.
import repro.models.mamba2  # noqa: E402,F401

REFERENCE = "granite_hybrid"


def _uniform(key, shape, lo, hi):
    import jax

    return jax.random.uniform(key, shape, minval=lo, maxval=hi)


def _a_log(key, shape):
    import jax.numpy as jnp

    return jnp.log(_uniform(key, shape, 1.0, 16.0))


def _dt_bias(key, shape):
    import jax.numpy as jnp

    dt = jnp.exp(_uniform(key, shape, math.log(1e-3), math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))  # softplus(dt_bias) == dt


def _conv_kernel(key, shape):
    bound = shape[-1] ** -0.5
    return _uniform(key, shape, -bound, bound)


# the published residual_multiplier, undone by the residual writes' init
_WRITE_GAIN = 1 / 0.22


def _write(key, shape):
    import jax

    return _WRITE_GAIN * shape[-2] ** -0.5 * jax.random.normal(key, shape)


def _ones(key, shape):
    import jax.numpy as jnp

    return jnp.ones(shape, jnp.float32)


KINDS = {"a_log": _a_log, "dt_bias": _dt_bias, "conv_kernel": _conv_kernel, "ones": _ones,
         "write": _write}


def _layout(cfg: dict):
    """(attention layer indices, Mamba-2 layer indices, period)."""
    types = cfg["layer_types"][: cfg["num_hidden_layers"]]
    attn = [i for i, t in enumerate(types) if t == "attention"]
    mamba = [i for i, t in enumerate(types) if t == "mamba"]
    if len(attn) + len(mamba) != len(types) or not attn:
        raise ValueError(f"layer_types must be 'mamba' / 'attention' with attention: {types}")
    period = len(types) // len(attn)
    want = ["attention" if i % period == period // 2 else "mamba" for i in range(len(types))]
    if types != want:
        raise ValueError(f"layer_types do not repeat with one attention layer at the middle "
                         f"of a period of {period}: {types}")
    return attn, mamba, period


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    di = cfg["mamba_expand"] * d
    hs = di // cfg["mamba_d_head"]
    if hs != cfg["mamba_n_heads"] or cfg["mamba_n_groups"] != 1:
        raise ValueError("the program's Mamba-2 mixer takes one B/C group and "
                         "d_inner / mamba_d_head heads")
    n = cfg["mamba_d_state"]
    return d, di, hs, n, di + 2 * n


def _head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def shapes(cfg: dict) -> dict:
    attn, mamba, _ = _layout(cfg)
    d, di, hs, n, conv = _dims(cfg)
    f, v, hd = cfg["shared_intermediate_size"], cfg["vocab_size"], _head_dim(cfg)
    n_l, n_a, n_m = cfg["num_hidden_layers"], len(attn), len(mamba)
    qd, kd = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return {
        "embed": ((v, d), "table"),
        "final_norm": ((d,), "gain"),
        "ln1": ((n_l, d), "gain"),
        "ln2": ((n_l, d), "gain"),
        "gate": ((n_l, d, f), "matrix"),
        "up": ((n_l, d, f), "matrix"),
        "down": ((n_l, f, d), "write"),
        "wq": ((n_a, d, qd), "matrix"),
        "wk": ((n_a, d, kd), "matrix"),
        "wv": ((n_a, d, kd), "matrix"),
        "wo": ((n_a, qd, d), "write"),
        "in_proj": ((n_m, d, di + conv + hs), "matrix"),
        "conv_w": ((n_m, conv, cfg["mamba_d_conv"]), "conv_kernel"),
        "conv_b": ((n_m, conv), "bias"),
        "dt_bias": ((n_m, hs), "dt_bias"),
        "a_log": ((n_m, hs), "a_log"),
        "d_skip": ((n_m, hs), "ones"),
        "ssm_norm": ((n_m, di), "gain"),
        "out_proj": ((n_m, di, d), "write"),
    }


def model_config(cfg: dict):
    import jax.numpy as jnp

    from repro.models.base import ModelConfig

    attn, _, period = _layout(cfg)
    d, di, hs, n, _ = _dims(cfg)
    if cfg["position_embedding_type"] != "nope" or cfg["num_local_experts"]:
        raise ValueError("the program runs NoPE attention and a shared MLP only")
    dtype = jnp.dtype(cfg["torch_dtype"])
    return ModelConfig(
        name=cfg["name"],
        family="hybrid",
        n_layers=cfg["num_hidden_layers"],
        d_model=d,
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=_head_dim(cfg),
        d_ff=cfg["shared_intermediate_size"],
        vocab=cfg["vocab_size"],
        qkv_bias=cfg["attention_bias"],
        tie_embeddings=cfg["tie_word_embeddings"],
        attn_every=period,
        ssm_mixer="mamba2",
        ssm_head_dim=cfg["mamba_d_head"],
        ssm_chunk=cfg["mamba_chunk_size"],
        d_state=n,
        d_conv=cfg["mamba_d_conv"],
        ssm_expand=cfg["mamba_expand"],
        rope=False,
        attn_scale=float(cfg["attention_multiplier"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        embed_mult=float(cfg["embedding_multiplier"]),
        residual_mult=float(cfg["residual_multiplier"]),
        logits_div=float(cfg["logits_scaling"]),
        param_dtype=dtype,
        compute_dtype=dtype,
    )


def program_params(w: dict, cfg: dict) -> dict:
    """Canonical weights -> the program's tree: one stacked block per
    position of the period, its leaves over the period's repeats."""
    import jax.numpy as jnp

    attn, mamba, period = _layout(cfg)
    reps = cfg["num_hidden_layers"] // period
    blocks = {}
    for j in range(period):
        layers = [r * period + j for r in range(reps)]
        take = lambda name, idx: w[name][jnp.asarray(idx)]
        layer = {
            "ln1": {"gamma": take("ln1", layers)},
            "ln2": {"gamma": take("ln2", layers)},
            "mlp": {"w1": take("gate", layers), "w3": take("up", layers),
                    "w2": take("down", layers)},
        }
        if j == period // 2:
            idx = [attn.index(i) for i in layers]
            layer["attn"] = {k: take(k, idx) for k in ("wq", "wk", "wv", "wo")}
        else:
            idx = [mamba.index(i) for i in layers]
            layer["mamba2"] = {
                "in_proj": take("in_proj", idx), "conv_w": take("conv_w", idx),
                "conv_b": take("conv_b", idx), "dt_bias": take("dt_bias", idx),
                "a_log": take("a_log", idx), "d_skip": take("d_skip", idx),
                "norm": take("ssm_norm", idx), "out_proj": take("out_proj", idx),
            }
        blocks[f"p{j}"] = layer
    return {"embed": w["embed"], "blocks": blocks, "final_norm": {"gamma": w["final_norm"]}}


def matmul_params(cfg: dict) -> int:
    """Parameters each token multiplies: every projection and the tied head."""
    attn, mamba, _ = _layout(cfg)
    d, di, hs, n, conv = _dims(cfg)
    hd = _head_dim(cfg)
    qd, kd = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    mlp = 3 * d * cfg["shared_intermediate_size"]
    per_attn = d * qd + 2 * d * kd + qd * d
    per_mamba = d * (di + conv + hs) + di * d
    return (len(attn) + len(mamba)) * mlp + len(attn) * per_attn + len(mamba) * per_mamba \
        + d * cfg["vocab_size"]


def token_flops(cfg: dict, context: int) -> float:
    """Model FLOPs of one token over ``context`` positions: 2 per matmul
    parameter; 4 * head_dim per query head per attended position in each
    attention layer; per Mamba-2 layer the depthwise conv (2 per tap and
    channel) and the SSD step (5 per state element: the decay, the input
    outer product, their sum, and the read-out's multiply-add)."""
    attn, mamba, _ = _layout(cfg)
    _, _, hs, n, conv = _dims(cfg)
    att = 4.0 * len(attn) * cfg["num_attention_heads"] * _head_dim(cfg) * context
    ssm = len(mamba) * (2.0 * cfg["mamba_d_conv"] * conv + 5.0 * hs * cfg["mamba_d_head"] * n)
    return 2.0 * matmul_params(cfg) + att + ssm
