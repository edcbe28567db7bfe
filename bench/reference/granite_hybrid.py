"""Plain float32 reference of the Granite-4.0-H decoder (``granitemoehybrid``
without experts), in ``jax.numpy``.

Written from the published architecture (HF ``GraniteMoeHybridForCausalLM``
with ``GraniteMoeHybridMambaLayer``, the Mamba-2 mixer), not from the
program under test:

  x = embed[tokens] * embedding_multiplier
  per layer:  x = x + residual_multiplier * mixer(RMSNorm(x))
              x = x + residual_multiplier * SwiGLU(RMSNorm(x))
  logits = RMSNorm(x) @ embed.T / logits_scaling

The attention mixer is causal grouped-query attention with no position
encoding and scores scaled by ``attention_multiplier``. The Mamba-2 mixer
projects to z, xBC and dt; runs a depthwise causal conv (with bias) over xBC
and SiLU; splits x, B, C (one group); dt = softplus(dt + dt_bias) and
A = -exp(A_log) per head; then, one token after the other in a
``lax.scan`` with no chunking,

  h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t,   y_t = h_t C_t + D x_t,

and ends with RMSNorm(y * silu(z)) * w over all d_inner channels and the
output projection.

Weights are the benchmark's canonical tree (bench/families/hybrid_mamba2.py).
The configuration states int8 storage for the protected matrices
(``precision.protected``), so the reference applies the same dequantisation
as ``dense_lm``. Everything else is float32 under
``jax.default_matmul_precision("highest")``.

``rounding`` rounds every matmul operand, the attention probabilities, the
residual stream and the SSM state to a lower precision: the control that a
precision cut must fail.
"""

from __future__ import annotations

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp


def _dense_lm():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "dense_lm.py")
    spec = importlib.util.spec_from_file_location("bench_reference_dense_lm_shared", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_shared = _dense_lm()
dequant_int8, rounder, _rms = _shared.dequant_int8, _shared.rounder, _shared._rms

_GROUPS = {
    "wq": "attn", "wk": "attn", "wv": "attn", "wo": "attn",
    "gate": "mlp", "up": "mlp", "down": "mlp",
    "in_proj": "ssm", "out_proj": "ssm", "embed": "embed",
}


def prepare(w: dict, cfg: dict) -> dict:
    """Canonical weights -> float32 reference weights (int8 dequantised
    where the configuration protects the matrix)."""
    groups = cfg["precision"]["protected"]
    return {
        k: dequant_int8(v) if _GROUPS.get(k) in groups else v.astype(jnp.float32)
        for k, v in w.items()
    }


def _attention(h, p, c, rd):
    s = h.shape[0]
    nh, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c["hidden_size"] // nh
    mm = lambda a, b: jnp.matmul(rd(a), rd(b))
    q = mm(h, p["wq"]).reshape(s, nh, hd)
    k = jnp.repeat(mm(h, p["wk"]).reshape(s, nkv, hd), nh // nkv, axis=1)
    v = jnp.repeat(mm(h, p["wv"]).reshape(s, nkv, hd), nh // nkv, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", rd(q), rd(k)) * c["attention_multiplier"]
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], sc, -jnp.inf)
    pr = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", rd(pr), rd(v))
    return mm(o.reshape(s, nh * hd), p["wo"])


def _mamba2(h, p, c, rd):
    di = c["mamba_expand"] * c["hidden_size"]
    n, hp = c["mamba_d_state"], c["mamba_d_head"]
    hs, k = di // hp, c["mamba_d_conv"]
    zxbcdt = jnp.matmul(rd(h), rd(p["in_proj"]))
    z, xbc, dt = zxbcdt[:, :di], zxbcdt[:, di : 2 * di + 2 * n], zxbcdt[:, 2 * di + 2 * n :]
    s = h.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc])
    conv = p["conv_b"] + sum(padded[i : i + s] * p["conv_w"][:, i] for i in range(k))
    xbc = jax.nn.silu(conv)
    x, bm, cm = xbc[:, :di].reshape(s, hs, hp), xbc[:, di : di + n], xbc[:, di + n :]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = -jnp.exp(p["a_log"])

    def token(state, t):
        x_t, dt_t, b_t, c_t = t
        state = rd(jnp.exp(dt_t * a)[:, None, None] * state
                   + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return state, jnp.einsum("hpn,n->hp", state, c_t)

    _, y = jax.lax.scan(token, jnp.zeros((hs, hp, n)), (x, dt, bm, cm))
    y = (y + x * p["d_skip"][:, None]).reshape(s, di)
    g = y * jax.nn.silu(z)
    g = _rms(g, p["ssm_norm"], c["rms_norm_eps"])
    return jnp.matmul(rd(g), rd(p["out_proj"]))


_MAMBA2 = ("in_proj", "conv_w", "conv_b", "dt_bias", "a_log", "d_skip", "ssm_norm", "out_proj")
_ATTN = ("wq", "wk", "wv", "wo")


def _runs(types):
    """(kind, first layer, end) of each run of consecutive layers of a kind."""
    runs, start = [], 0
    for i in range(1, len(types) + 1):
        if i == len(types) or types[i] != types[start]:
            runs.append((types[start], start, i))
            start = i
    return runs


def hidden_states(rw: dict, cfg: dict, tokens, rd):
    """Final-norm hidden states (S, D) for one token sequence (S,). Each run
    of consecutive layers of one kind is one ``lax.scan`` over its layers'
    weights, in layer order."""
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    types = cfg["layer_types"][: cfg["num_hidden_layers"]]
    x = rd(rw["embed"][tokens] * cfg["embedding_multiplier"])
    mm = lambda a, b: jnp.matmul(rd(a), rd(b))
    n_a = n_m = 0
    for kind, first, end in _runs(types):
        n = end - first
        if kind == "attention":
            mixer, keys, k0 = _attention, _ATTN, n_a
            n_a += n
        else:
            mixer, keys, k0 = _mamba2, _MAMBA2, n_m
            n_m += n

        def layer(x, w, mixer=mixer, keys=keys):
            y = mixer(_rms(x, w["ln1"], eps), {k: w[k] for k in keys}, cfg, rd)
            x = rd(x + res * y)
            h = _rms(x, w["ln2"], eps)
            y = mm(jax.nn.silu(mm(h, w["gate"])) * mm(h, w["up"]), w["down"])
            return rd(x + res * y), None

        xs = {k: rw[k][k0 : k0 + n] for k in keys}
        xs.update({k: rw[k][first:end] for k in ("ln1", "ln2", "gate", "up", "down")})
        x, _ = jax.lax.scan(layer, x, xs)
    return _rms(x, rw["final_norm"], eps)


_KEYS = ("num_hidden_layers", "layer_types", "hidden_size", "num_attention_heads",
         "num_key_value_heads", "attention_multiplier", "embedding_multiplier",
         "residual_multiplier", "logits_scaling", "rms_norm_eps", "mamba_expand",
         "mamba_d_state", "mamba_d_head", "mamba_d_conv")


@functools.partial(jax.jit, static_argnames=("cfg_items", "rounding"))
def _logits(rw, tokens, *, cfg_items, rounding):
    cfg = {k: (list(v) if isinstance(v, tuple) else v) for k, v in cfg_items}
    rd = rounder(rounding)
    with jax.default_matmul_precision("highest"):
        h = hidden_states(rw, cfg, tokens, rd)
        return jnp.matmul(rd(h), rd(rw["embed"].T)) / cfg["logits_scaling"]


def logits(rw: dict, cfg: dict, tokens, rounding: str | None = None):
    """Teacher-forced logits (S, V) of one sequence; ``rounding`` selects
    the lower-precision control."""
    items = tuple((k, tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k]) for k in _KEYS)
    return _logits(rw, jnp.asarray(tokens, jnp.int32), cfg_items=items, rounding=rounding)
