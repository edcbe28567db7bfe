"""Unified decoder LM: parameter construction + train/prefill/decode forwards
for all assigned families (dense, MoE, SSM/RWKV6, hybrid/Jamba, audio, VLM).

Layers are stacked over `cfg.n_groups` repeating period-groups and executed
with one `lax.scan`, so the lowered HLO is O(1) in depth. Parameter leaves are
`base.Spec`s carrying logical sharding axes ("layers", "embed", "heads",
"ffn", "experts", "vocab"), mapped to the mesh by repro.distributed.sharding.

The cross-entropy is computed in sequence chunks (lax.scan) against the
(vocab-sharded) unembedding so full (B, S, V) logits never materialise —
at 151k vocab and 1M-token batches that is the difference between 300 TB of
logits and a 100 MB working set.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import base, layers, mamba, mamba2, moe, rwkv6
from repro.models.base import ModelConfig, Spec

REMAT_POLICIES = {
    None: None,
    "full": jax.checkpoint_policies.nothing_saveable,
    "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
}


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------
def _norm_spec(cfg, d=None):
    d = d or cfg.d_model
    p = {"gamma": Spec((d,), ("embed",), "ones")}
    if cfg.norm_type == "layernorm":
        p["beta"] = Spec((d,), ("embed",), "zeros")
    return p


def _attn_spec(cfg):
    d, hd = cfg.d_model, cfg.hd
    # Head-granular TP constraint: "heads:<n>" only shards if n % model == 0.
    # Sharding the flattened H*hd dim when H doesn't divide splits heads
    # across devices; the q reshape then forces GSPMD into partial shardings
    # whose attention scores all-reduce at (B,H,S,S) scale (measured 10.7
    # GiB/op on llama4 train). Non-divisible head counts replicate instead.
    qh = f"heads:{cfg.n_heads}"
    kh = f"heads:{cfg.n_kv_heads}"
    p = {
        "wq": Spec((d, cfg.n_heads * hd), ("embed", qh)),
        "wk": Spec((d, cfg.n_kv_heads * hd), ("embed", kh)),
        "wv": Spec((d, cfg.n_kv_heads * hd), ("embed", kh)),
        "wo": Spec((cfg.n_heads * hd, d), (qh, "embed")),
    }
    if cfg.qkv_bias:
        p["bq"] = Spec((cfg.n_heads * hd,), (qh,), "zeros")
        p["bk"] = Spec((cfg.n_kv_heads * hd,), (kh,), "zeros")
        p["bv"] = Spec((cfg.n_kv_heads * hd,), (kh,), "zeros")
    if cfg.qk_norm:
        p["q_norm"] = Spec((hd,), (None,), "ones")
        p["k_norm"] = Spec((hd,), (None,), "ones")
    return p


def _mlp_spec(cfg):
    d, f = cfg.d_model, cfg.d_ff
    p = {
        "w1": Spec((d, f), ("embed", "ffn")),
        "w2": Spec((f, d), ("ffn", "embed")),
    }
    if cfg.gated_mlp:
        p["w3"] = Spec((d, f), ("embed", "ffn"))
    return p


def _moe_spec(cfg):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    p = {
        "router": Spec((d, e), ("embed", None)),
        "w1": Spec((e, d, f), ("experts", "embed", "ffn")),
        "w2": Spec((e, f, d), ("experts", "ffn", "embed")),
    }
    if cfg.gated_mlp:
        p["w3"] = Spec((e, d, f), ("experts", "embed", "ffn"))
    if cfg.shared_expert:
        p["shared_w1"] = Spec((d, f), ("embed", "ffn"))
        p["shared_w3"] = Spec((d, f), ("embed", "ffn"))
        p["shared_w2"] = Spec((f, d), ("ffn", "embed"))
    return p


def _mamba_spec(cfg):
    d, di, ds, k = cfg.d_model, cfg.d_inner, cfg.d_state, cfg.d_conv
    dtr = max(1, d // 16)
    return {
        "in_proj": Spec((d, 2 * di), ("embed", "ffn")),
        "conv_w": Spec((di, k), ("ffn", None), scale=0.5),
        "conv_b": Spec((di,), ("ffn",), "zeros"),
        "x_proj": Spec((di, dtr + 2 * ds), ("ffn", None)),
        "dt_proj": Spec((dtr, di), (None, "ffn")),
        "dt_bias": Spec((di,), ("ffn",), "zeros"),
        "a_log": Spec((di, ds), ("ffn", None), "decay"),
        "d_skip": Spec((di,), ("ffn",), "ones"),
        "out_proj": Spec((di, d), ("ffn", "embed")),
    }


def _mamba2_spec(cfg):
    d, di, k = cfg.d_model, cfg.d_inner, cfg.d_conv
    h, _, _, conv = mamba2.dims(cfg)
    return {
        "in_proj": Spec((d, di + conv + h), ("embed", "ffn")),
        "conv_w": Spec((conv, k), ("ffn", None), scale=0.5),
        "conv_b": Spec((conv,), ("ffn",), "zeros"),
        "dt_bias": Spec((h,), (None,), "zeros"),
        "a_log": Spec((h,), (None,), "zeros"),
        "d_skip": Spec((h,), (None,), "ones"),
        "norm": Spec((di,), ("ffn",), "ones"),
        "out_proj": Spec((di, d), ("ffn", "embed")),
    }


def _rwkv_tm_spec(cfg):
    d = cfg.d_model
    rh = f"heads:{d // cfg.rwkv_head_dim}"
    return {
        "mu_base": Spec((d,), ("embed",), "zeros"),
        "mix_a": Spec((d, rwkv6.N_MIX * rwkv6.LORA_MIX), ("embed", None)),
        "mix_b": Spec((rwkv6.N_MIX, rwkv6.LORA_MIX, d), (None, None, "embed")),
        "mu_five": Spec((rwkv6.N_MIX, d), (None, "embed"), "zeros"),
        "w_r": Spec((d, d), ("embed", rh)),
        "w_k": Spec((d, d), ("embed", rh)),
        "w_v": Spec((d, d), ("embed", rh)),
        "w_g": Spec((d, d), ("embed", rh)),
        "w_o": Spec((d, d), (rh, "embed")),
        "w_base": Spec((d,), (rh,), "decay"),
        "decay_a": Spec((d, rwkv6.LORA_DECAY), ("embed", None)),
        "decay_b": Spec((rwkv6.LORA_DECAY, d), (None, rh)),
        "u": Spec((d,), (rh,), "zeros"),
        "ln_x_g": Spec((d,), (rh,), "ones"),
        "ln_x_b": Spec((d,), (rh,), "zeros"),
    }


def _rwkv_cm_spec(cfg):
    d, f = cfg.d_model, cfg.d_ff
    rh = f"heads:{d // cfg.rwkv_head_dim}"
    return {
        "mu_k": Spec((d,), ("embed",), "zeros"),
        "mu_r": Spec((d,), ("embed",), "zeros"),
        "w_k": Spec((d, f), ("embed", "ffn")),
        "w_v": Spec((f, d), ("ffn", "embed")),
        "w_r": Spec((d, d), ("embed", rh)),
    }


def _layer_spec(cfg, pos):
    kind = cfg.layer_kind(pos)
    p = {"ln1": _norm_spec(cfg), "ln2": _norm_spec(cfg)}
    if kind["mixer"] in ("attn", "cross"):
        p["attn"] = _attn_spec(cfg)
        if kind["mixer"] == "cross":
            p["gate_attn"] = Spec((1,), (None,), "zeros")
            p["gate_ffn"] = Spec((1,), (None,), "zeros")
    elif kind["mixer"] == "mamba":
        p["mamba"] = _mamba_spec(cfg)
    elif kind["mixer"] == "mamba2":
        p["mamba2"] = _mamba2_spec(cfg)
    elif kind["mixer"] == "rwkv":
        p["tm"] = _rwkv_tm_spec(cfg)
    if kind["ffn"] == "moe":
        p["moe"] = _moe_spec(cfg)
    elif kind["ffn"] == "rwkv_cm":
        p["cm"] = _rwkv_cm_spec(cfg)
    else:
        p["mlp"] = _mlp_spec(cfg)
    return p


def _stack(spec, g):
    """Prepend the scan (groups) dimension to every leaf."""
    return base.spec_tree_map(
        lambda s: Spec((g,) + s.shape, ("layers",) + s.axes, s.init, s.scale), spec
    )


def init_specs(cfg: ModelConfig):
    blocks = {
        f"p{j}": _stack(_layer_spec(cfg, j), cfg.n_groups) for j in range(cfg.period)
    }
    if cfg.n_codebooks:
        embed = Spec((cfg.n_codebooks, cfg.vocab, cfg.d_model), (None, "vocab", "embed"))
        head = Spec((cfg.n_codebooks, cfg.d_model, cfg.vocab), (None, "embed", "vocab"))
    else:
        embed = Spec((cfg.vocab, cfg.d_model), ("vocab", "embed"))
        head = Spec((cfg.d_model, cfg.vocab), ("embed", "vocab"))
    tree = {"embed": embed, "blocks": blocks, "final_norm": _norm_spec(cfg)}
    if not cfg.tie_embeddings:
        tree["lm_head"] = head
    return tree


def init_params(cfg: ModelConfig, key):
    return base.materialize(init_specs(cfg), key, cfg.param_dtype)


def param_struct(cfg: ModelConfig):
    return base.struct(init_specs(cfg), cfg.param_dtype)


def logical_axes(cfg: ModelConfig):
    return base.axes_tree(init_specs(cfg))


def param_count(cfg: ModelConfig) -> tuple[int, int]:
    """Exact (total, active) parameter counts from the spec tree."""
    leaves = jax.tree_util.tree_leaves(
        init_specs(cfg), is_leaf=lambda x: isinstance(x, Spec)
    )
    total = active = 0
    for s in leaves:
        n = int(np.prod(s.shape))
        total += n
        if "experts" in s.axes and len(s.shape) >= 4:
            active += n * cfg.top_k // cfg.n_experts
        else:
            active += n
    return total, active


# ---------------------------------------------------------------------------
# Cache
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, max_len: int, img_tokens: int = 0):
    """Zero-initialised decode cache, one slot per period position per group."""
    g, dt = cfg.n_groups, cfg.compute_dtype
    cache: dict[str, Any] = {}
    for j in range(cfg.period):
        kind = cfg.layer_kind(j)
        c: dict[str, Any] = {}
        if kind["mixer"] == "attn":
            s = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
            kv_dt = jnp.int8 if cfg.kv_quant else dt
            c["k"] = jnp.zeros((g, batch, s, cfg.n_kv_heads, cfg.hd), kv_dt)
            c["v"] = jnp.zeros((g, batch, s, cfg.n_kv_heads, cfg.hd), kv_dt)
            if cfg.kv_quant:
                c["kv_scale"] = jnp.zeros((g, batch, s, cfg.n_kv_heads, 2), jnp.float32)
        elif kind["mixer"] == "cross":
            t = img_tokens or cfg.n_img_tokens
            c["k"] = jnp.zeros((g, batch, t, cfg.n_kv_heads, cfg.hd), dt)
            c["v"] = jnp.zeros((g, batch, t, cfg.n_kv_heads, cfg.hd), dt)
        elif kind["mixer"] == "mamba":
            c["conv"] = jnp.zeros((g, batch, cfg.d_conv - 1, cfg.d_inner), dt)
            c["ssm"] = jnp.zeros((g, batch, cfg.d_inner, cfg.d_state), dt)
        elif kind["mixer"] == "mamba2":  # float32 state (core/statestore seals it)
            h, p_, n, conv = mamba2.dims(cfg)
            c["conv"] = jnp.zeros((g, batch, cfg.d_conv - 1, conv), jnp.float32)
            c["ssm"] = jnp.zeros((g, batch, h, p_, n), jnp.float32)
        elif kind["mixer"] == "rwkv":
            n = cfg.rwkv_head_dim
            c["shift_tm"] = jnp.zeros((g, batch, cfg.d_model), dt)
            c["wkv"] = jnp.zeros((g, batch, cfg.d_model // n, n, n), dt)
            c["shift_cm"] = jnp.zeros((g, batch, cfg.d_model), dt)
        cache[f"p{j}"] = c
    return cache


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------
def _quant_kv(k, v):
    """(B,S,H,hd) -> int8 planes + per-(token,head) scales (B,S,H,2)."""
    ks = jnp.max(jnp.abs(k).astype(jnp.float32), axis=-1, keepdims=True) / 127.0
    vs = jnp.max(jnp.abs(v).astype(jnp.float32), axis=-1, keepdims=True) / 127.0
    ks = jnp.maximum(ks, 1e-9)
    vs = jnp.maximum(vs, 1e-9)
    kq = jnp.clip(jnp.round(k.astype(jnp.float32) / ks), -127, 127).astype(jnp.int8)
    vq = jnp.clip(jnp.round(v.astype(jnp.float32) / vs), -127, 127).astype(jnp.int8)
    return kq, vq, jnp.concatenate([ks, vs], axis=-1)


def _dequant_kv(kq, vq, scale, dtype):
    k = kq.astype(dtype) * scale[..., 0:1].astype(dtype)
    v = vq.astype(dtype) * scale[..., 1:2].astype(dtype)
    return k, v


def _attn_block(x, p, cfg, *, mode, cache, pos, img=None, cross=False):
    b, s, _ = x.shape
    h = layers.apply_norm(x, p["ln1"], cfg.norm_type, cfg.norm_eps)
    if cross:
        q, _, _ = layers.qkv_proj(h, p["attn"], cfg)
        new_cache = cache
        if mode == "decode":
            k, v = cache["k"], cache["v"]
        else:
            hi = img.astype(x.dtype)
            bi, si, _ = hi.shape
            k = jnp.einsum("bsd,dh->bsh", hi, p["attn"]["wk"]).reshape(
                bi, si, cfg.n_kv_heads, cfg.hd
            )
            v = jnp.einsum("bsd,dh->bsh", hi, p["attn"]["wv"]).reshape(
                bi, si, cfg.n_kv_heads, cfg.hd
            )
            if cfg.qk_norm:
                k = layers.rms_norm(k, p["attn"]["k_norm"])
            if mode == "prefill":
                new_cache = {"k": k, "v": v}
        if mode == "decode":
            out = layers.decode_attention(q, k, v, k.shape[1])
        elif s >= 2048:  # chunk the q axis: (S x n_img_tokens) scores are huge
            out = layers.flash_attention(
                q, k, v, causal=False, q_chunk=cfg.flash_chunk,
                kv_chunk=k.shape[1], unroll=cfg.unroll,
            )
        else:
            out = layers.full_attention(q, k, v, causal=False)
        out = layers.out_proj(out, p["attn"]) * jnp.tanh(p["gate_attn"])
        x = x + out
        h2 = layers.apply_norm(x, p["ln2"], cfg.norm_type, cfg.norm_eps)
        x = x + layers.mlp(h2, p["mlp"], cfg) * jnp.tanh(p["gate_ffn"])
        return x, new_cache, 0.0

    q, k, v = layers.qkv_proj(h, p["attn"], cfg)
    if mode == "decode":
        # pos is a scalar (whole batch at one position, the historical path)
        # or a (B,) vector (continuous batching: every lane decodes its own
        # position). The scalar path is kept byte-for-byte so existing
        # fixed-batch rollouts stay bit-identical.
        pos_v = jnp.asarray(pos)
        positions = jnp.full((b, 1), pos) if pos_v.ndim == 0 else pos_v[:, None]
    elif mode == "chunk":
        # Chunked prefill / speculative verify (DESIGN.md §16): s new tokens
        # per lane starting at per-lane cache position pos0 = pos.
        pos_v = jnp.asarray(pos)
        base = pos_v if pos_v.ndim else jnp.full((b,), pos, jnp.int32)
        positions = base[:, None] + jnp.arange(s)[None, :]
    else:
        positions = jnp.arange(s)[None, :]
    if cfg.rope:
        q = layers.apply_rope(q, positions, cfg.rope_theta)
        k = layers.apply_rope(k, positions, cfg.rope_theta)
    if cfg.attn_scale:  # the attention bodies divide scores by sqrt(head_dim)
        q = (q * (cfg.attn_scale * np.sqrt(cfg.hd))).astype(q.dtype)

    new_cache = cache
    w = cfg.sliding_window
    if mode == "decode":
        smax = cache["k"].shape[1]
        # SWA caches are ring buffers of size `window`: slot = pos % smax.
        slot = pos_v % smax if w else jnp.minimum(pos_v, smax - 1)
        if pos_v.ndim:
            # per-lane slot: vmap the row update over the batch axis
            upd = jax.vmap(
                lambda c, x, s_: jax.lax.dynamic_update_slice_in_dim(c, x, s_, 0)
            )
        else:
            upd = lambda c, x, s_: jax.lax.dynamic_update_slice_in_dim(c, x, s_, 1)
        if cfg.kv_quant:
            kq, vq, sc = _quant_kv(k, v)
            ck = upd(cache["k"], kq, slot)
            cv = upd(cache["v"], vq, slot)
            csc = upd(cache["kv_scale"], sc, slot)
            new_cache = {"k": ck, "v": cv, "kv_scale": csc}
            kd, vd = _dequant_kv(ck, cv, csc, cfg.compute_dtype)
        else:
            ck = upd(cache["k"], k.astype(cache["k"].dtype), slot)
            cv = upd(cache["v"], v.astype(cache["v"].dtype), slot)
            new_cache = {"k": ck, "v": cv}
            kd, vd = ck, cv
        cur = jnp.minimum(pos_v + 1, smax) if w else pos_v + 1
        out = layers.decode_attention(q, kd, vd, cur)
    elif mode == "chunk":
        # Same cache-write + attend-the-cache structure as decode, vmapped
        # over lanes with an s-row window; restricted to the paged-KV config
        # class (all-attn, no SWA ring, no quantized cache) the scheduler
        # already requires via configs.shapes.supports_paged_kv.
        assert not w and not cfg.kv_quant, (
            "chunk mode requires a paged-KV-compatible config"
        )
        upd = jax.vmap(
            lambda c, u, s_: jax.lax.dynamic_update_slice_in_dim(c, u, s_, 0)
        )
        ck = upd(cache["k"], k.astype(cache["k"].dtype), base)
        cv = upd(cache["v"], v.astype(cache["v"].dtype), base)
        new_cache = {"k": ck, "v": cv}
        out = layers.chunk_attention(q, ck, cv, base)
    else:
        if mode == "prefill":
            smax = cache["k"].shape[1]
            ks = k[:, -smax:, :, :]
            vs = v[:, -smax:, :, :]
            if w and s >= smax:
                # Keep ring positions consistent: seq position q lives at
                # slot q % smax for later decode steps.
                ks = jnp.roll(ks, s % smax, axis=1)
                vs = jnp.roll(vs, s % smax, axis=1)
            if cfg.kv_quant:
                kq, vq, sc = _quant_kv(ks, vs)
                ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], kq, 0, 1)
                cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], vq, 0, 1)
                csc = jax.lax.dynamic_update_slice_in_dim(cache["kv_scale"], sc, 0, 1)
                new_cache = {"k": ck, "v": cv, "kv_scale": csc}
            else:
                ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], ks.astype(cache["k"].dtype), 0, 1)
                cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], vs.astype(cache["v"].dtype), 0, 1)
                new_cache = {"k": ck, "v": cv}
        if w and s > w:
            out = layers.banded_attention(
                q, k, v, window=w, q_chunk=cfg.flash_chunk, unroll=cfg.unroll
            )
        elif s >= 4096:
            out = layers.flash_attention(
                q, k, v, causal=True, q_chunk=cfg.flash_chunk,
                kv_chunk=cfg.flash_chunk, unroll=cfg.unroll,
            )
        else:
            out = layers.full_attention(q, k, v, causal=True, window=w)
    x = x + _residual(layers.out_proj(out, p["attn"]), cfg)

    h2 = layers.apply_norm(x, p["ln2"], cfg.norm_type, cfg.norm_eps)
    if "moe" in p:
        y, aux = moe.moe_ffn(h2, p["moe"], cfg)
    else:
        y, aux = layers.mlp(h2, p["mlp"], cfg), 0.0
    return x + _residual(y, cfg), new_cache, aux


def _mamba_block(x, p, cfg, *, mode, cache):
    h = layers.apply_norm(x, p["ln1"], cfg.norm_type, cfg.norm_eps)
    state = None
    if mode == "decode":
        state = {"conv": cache["conv"], "ssm": cache["ssm"]}
    y, new_state = mamba.mamba_layer(h, p["mamba"], cfg, state)
    x = x + y
    new_cache = cache
    if mode in ("decode", "prefill"):
        new_cache = {"conv": new_state["conv"].astype(cache["conv"].dtype),
                     "ssm": new_state["ssm"].astype(cache["ssm"].dtype)}
    h2 = layers.apply_norm(x, p["ln2"], cfg.norm_type, cfg.norm_eps)
    if "moe" in p:
        y, aux = moe.moe_ffn(h2, p["moe"], cfg)
    else:
        y, aux = layers.mlp(h2, p["mlp"], cfg), 0.0
    return x + y, new_cache, aux


def _residual(y, cfg):
    """A branch's output as added to the residual stream."""
    return y if cfg.residual_mult == 1.0 else y * cfg.residual_mult


def _mamba2_block(x, p, cfg, *, mode, cache):
    h = layers.apply_norm(x, p["ln1"], cfg.norm_type, cfg.norm_eps)
    new_cache = cache
    if mode == "decode" and "ssm_lo" in cache:  # a protected lane slot
        y, new_cache = mamba2.step_protected(h, p["mamba2"], cfg, cache)
    elif mode == "decode":
        y, new_cache = mamba2.step(h, p["mamba2"], cfg, cache)
    elif mode in ("train", "prefill"):
        y, state = mamba2.forward(h, p["mamba2"], cfg)
        if mode == "prefill":
            new_cache = state
    else:
        raise NotImplementedError(f"mamba2 layers have no {mode!r} mode")
    x = x + _residual(y, cfg)
    h2 = layers.apply_norm(x, p["ln2"], cfg.norm_type, cfg.norm_eps)
    return x + _residual(layers.mlp(h2, p["mlp"], cfg), cfg), new_cache, 0.0


def _rwkv_block(x, p, cfg, *, mode, cache):
    h = layers.apply_norm(x, p["ln1"], cfg.norm_type, cfg.norm_eps)
    st = None
    if mode == "decode":
        st = {"shift": cache["shift_tm"], "wkv": cache["wkv"]}
    y, tm_state = rwkv6.time_mix(h, p["tm"], cfg, st)
    x = x + y
    h2 = layers.apply_norm(x, p["ln2"], cfg.norm_type, cfg.norm_eps)
    st2 = {"shift": cache["shift_cm"]} if mode == "decode" else None
    y2, cm_state = rwkv6.channel_mix(h2, p["cm"], cfg, st2)
    x = x + y2
    new_cache = cache
    if mode in ("decode", "prefill"):
        new_cache = {
            "shift_tm": tm_state["shift"].astype(x.dtype),
            "wkv": tm_state["wkv"].astype(x.dtype),
            "shift_cm": cm_state["shift"].astype(x.dtype),
        }
    return x, new_cache, 0.0


def _mamba2_run(x, ps, cfg, *, mode):
    """Consecutive Mamba-2 layers (train / prefill) as one scan over their
    stacked weights, so that the layer is compiled once. Returns (x, the
    layers' new caches)."""
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *ps)

    def layer(h, p):
        h, nc, _ = _mamba2_block(h, p, cfg, mode=mode, cache={})
        return h, nc

    x, ncs = jax.lax.scan(layer, x, stacked)
    return x, [jax.tree.map(lambda a: a[i], ncs) for i in range(len(ps))]


def _group_body(x, pgroup, cfg, *, mode, cache_group, pos, img):
    """One scan step: run the `period` layers of a group."""
    aux_total = 0.0
    new_cache = {}
    j = 0
    while j < cfg.period:
        kind = cfg.layer_kind(j)
        run = 1
        if kind["mixer"] == "mamba2" and mode in ("train", "prefill"):
            while j + run < cfg.period and cfg.layer_kind(j + run)["mixer"] == "mamba2":
                run += 1
        if run > 1:
            x, ncs = _mamba2_run(x, [pgroup[f"p{i}"] for i in range(j, j + run)], cfg, mode=mode)
            new_cache.update({f"p{j + i}": nc for i, nc in enumerate(ncs)})
            j += run
            continue
        p = pgroup[f"p{j}"]
        c = cache_group.get(f"p{j}", {}) if cache_group is not None else {}
        if kind["mixer"] in ("attn", "cross"):
            x, nc, aux = _attn_block(
                x, p, cfg, mode=mode, cache=c, pos=pos, img=img,
                cross=kind["mixer"] == "cross",
            )
        elif kind["mixer"] == "mamba":
            x, nc, aux = _mamba_block(x, p, cfg, mode=mode, cache=c)
        elif kind["mixer"] == "mamba2":
            x, nc, aux = _mamba2_block(x, p, cfg, mode=mode, cache=c)
        else:
            x, nc, aux = _rwkv_block(x, p, cfg, mode=mode, cache=c)
        new_cache[f"p{j}"] = nc
        aux_total = aux_total + aux
        j += 1
    return x, new_cache, aux_total


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------
def _embed(params, tokens, cfg):
    if cfg.n_codebooks:
        # tokens: (B, K, S); sum the K codebook embeddings (MusicGen).
        parts = [
            jnp.take(params["embed"][k], tokens[:, k], axis=0)
            for k in range(cfg.n_codebooks)
        ]
        x = sum(parts)
        s = tokens.shape[-1]
        x = x + _sinusoid(s, cfg.d_model, x.dtype)
    else:
        x = jnp.take(params["embed"], tokens, axis=0)
    x = x.astype(cfg.compute_dtype)
    return x if cfg.embed_mult == 1.0 else x * cfg.embed_mult


def _sinusoid(s, d, dtype, offset=0):
    # offset may be a traced scalar (decode step): keep arange static.
    pos = (jnp.arange(s, dtype=jnp.float32) + offset)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, dim / d)
    pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
    return pe.astype(dtype)[None]


def _unembed_matrix(params, cfg):
    if cfg.tie_embeddings:
        e = params["embed"]
        return e.swapaxes(-1, -2) if cfg.n_codebooks else e.T
    return params["lm_head"]


def _scale_logits(logits, cfg):
    return logits if cfg.logits_div == 1.0 else logits / cfg.logits_div


def forward(params, tokens, cfg: ModelConfig, *, img=None, cache=None,
            pos=0, mode="train", remat=None):
    """Shared backbone. Returns (hidden (B,S,D), new_cache, aux_loss)."""
    x = _embed(params, tokens, cfg)
    if cfg.n_codebooks and mode == "decode":
        # decode-time positional: replace the offset-0 sinusoid added in _embed
        x = x - _sinusoid(1, cfg.d_model, x.dtype, offset=0) + _sinusoid(
            1, cfg.d_model, x.dtype, offset=pos
        )

    body = functools.partial(_group_body, cfg=cfg, mode=mode, pos=pos, img=img)
    zero = jnp.zeros((), jnp.float32)

    if cache is None:  # train: no cache threading
        empty = {f"p{j}": {} for j in range(cfg.period)}

        def step(carry, pg):
            h, aux = carry
            h, _, a = body(h, pg, cache_group=empty)
            return (h, aux + a), None

        if remat is not None:
            step = jax.checkpoint(step, policy=REMAT_POLICIES[remat])
        (x, aux), _ = jax.lax.scan(step, (x, zero), params["blocks"], unroll=cfg.unroll)
        new_cache = None
    else:

        def step(carry, xs):
            h, aux = carry
            pg, cg = xs
            h, nc, a = body(h, pg, cache_group=cg)
            return (h, aux + a), nc

        if remat is not None:
            step = jax.checkpoint(step, policy=REMAT_POLICIES[remat])
        (x, aux), new_cache = jax.lax.scan(
            step, (x, zero), (params["blocks"], cache), unroll=cfg.unroll
        )

    x = layers.apply_norm(x, params["final_norm"], cfg.norm_type, cfg.norm_eps)
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Losses / entry points
# ---------------------------------------------------------------------------
def chunked_xent(hidden, unembed, labels, chunk=512, unroll=1):
    """Cross-entropy without materialising (B, S, V) logits.

    hidden: (B, S, D); unembed: (D, V); labels: (B, S) int32 (-1 = masked).
    Scans over S chunks; each step computes (B, chunk, V) logits in f32.
    """
    b, s, d = hidden.shape
    chunk = min(chunk, s)
    while s % chunk:
        chunk //= 2
    nc = s // chunk
    hs = hidden.reshape(b, nc, chunk, d).swapaxes(0, 1)  # (nc, B, c, D)
    ls = labels.reshape(b, nc, chunk).swapaxes(0, 1)

    def step(acc, xs):
        h, lab = xs
        logits = jnp.einsum("bcd,dv->bcv", h.astype(jnp.float32), unembed.astype(jnp.float32))
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(lab, 0)[..., None], axis=-1
        )[..., 0]
        mask = (lab >= 0).astype(jnp.float32)
        loss_sum, n = acc
        return (loss_sum + jnp.sum((lse - gold) * mask), n + mask.sum()), None

    (loss_sum, n), _ = jax.lax.scan(step, (0.0, 0.0), (hs, ls), unroll=unroll)
    return loss_sum / jnp.maximum(n, 1.0)


def train_loss(params, batch, cfg: ModelConfig, remat="full"):
    """batch: {tokens, labels[, img]}. Returns (loss, metrics)."""
    tokens = batch["tokens"]
    hidden, _, aux = forward(
        params, tokens, cfg, img=batch.get("img"), mode="train", remat=remat
    )
    un = _unembed_matrix(params, cfg)
    if cfg.n_codebooks:
        losses = [
            chunked_xent(hidden, un[k], batch["labels"][:, k], unroll=cfg.unroll)
            for k in range(cfg.n_codebooks)
        ]
        ce = sum(losses) / cfg.n_codebooks
    else:
        ce = chunked_xent(hidden, un, batch["labels"], unroll=cfg.unroll)
    loss = ce + 0.01 * aux
    return loss, {"ce": ce, "aux": aux}


def sequence_logits(params, tokens, cfg: ModelConfig, *, img=None):
    """Teacher-forced per-position logits for a fixed token sequence.

    The paired clean-vs-faulty eval path (core/campaign.py, DESIGN.md §15):
    feeding the *same* ``tokens`` (B, S) through clean and fault-injected
    params gives position-aligned (B, S, V) f32 logits whose KL / NLL deltas
    are well-defined — unlike comparing logits along each model's own greedy
    rollout, which diverges after the first mismatched token. Runs the full
    causal train-mode forward (no cache), so ECC-protected ``EccWeight``
    leaves decode through the scrub-on-read matmul path exactly as serving
    does. Not implemented for multi-codebook (audio) heads.
    """
    assert not cfg.n_codebooks, "sequence_logits: single-codebook LMs only"
    hidden, _, _ = forward(params, tokens, cfg, img=img, mode="train")
    un = _unembed_matrix(params, cfg)
    return _scale_logits(jnp.einsum(
        "bsd,dv->bsv", hidden.astype(jnp.float32), un.astype(jnp.float32)
    ), cfg)


def prefill(params, tokens, cfg: ModelConfig, cache, *, img=None):
    """Process a prompt, fill the cache. Returns (last-token logits, cache)."""
    hidden, new_cache, _ = forward(
        params, tokens, cfg, img=img, cache=cache, mode="prefill", remat="full"
    )
    last = hidden[:, -1]
    un = _unembed_matrix(params, cfg)
    if cfg.n_codebooks:
        logits = jnp.einsum("bd,kdv->bkv", last.astype(jnp.float32), un.astype(jnp.float32))
    else:
        logits = jnp.einsum("bd,dv->bv", last.astype(jnp.float32), un.astype(jnp.float32))
    return _scale_logits(logits, cfg), new_cache


def greedy_decode_loop(params, tok0, cfg: ModelConfig, cache, start_pos, n_steps: int,
                       *, img=None):
    """Greedy-decode ``n_steps`` tokens after ``tok0`` with one ``lax.scan``.

    The per-token Python loop dispatches one jitted computation per token;
    under a scan the whole rollout lowers to a single device program (O(1)
    dispatch, DESIGN.md §2). ``start_pos`` may be a traced scalar so prompt
    length never forces a retrace. Token-identical to stepping
    ``decode_step`` in Python (tested).

    Returns (tokens (B, n_steps) int32, final cache).
    """

    def step(carry, i):
        tok, c = carry
        logits, c = decode_step(params, tok, cfg, c, start_pos + i, img=img)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        return (nxt, c), nxt

    (_, cache), toks = jax.lax.scan(
        step, (tok0, cache), jnp.arange(n_steps, dtype=jnp.int32)
    )
    return jnp.swapaxes(toks[..., 0], 0, 1), cache


def decode_step(params, tokens, cfg: ModelConfig, cache, pos, *, img=None):
    """One decode step. tokens: (B, 1) or (B, K, 1). pos: scalar int32 —
    0-based position of the token being processed — or a (B,) int32 vector
    giving every batch lane its own position (continuous batching)."""
    hidden, new_cache, _ = forward(
        params, tokens, cfg, img=img, cache=cache, pos=pos, mode="decode"
    )
    last = hidden[:, -1]
    un = _unembed_matrix(params, cfg)
    if cfg.n_codebooks:
        logits = jnp.einsum("bd,kdv->bkv", last.astype(jnp.float32), un.astype(jnp.float32))
    else:
        logits = jnp.einsum("bd,dv->bv", last.astype(jnp.float32), un.astype(jnp.float32))
    return _scale_logits(logits, cfg), new_cache


def chunk_step(params, tokens, cfg: ModelConfig, cache, pos0):
    """Chunked prefill (DESIGN.md §16): process ``tokens`` (B, S) whose
    cache positions start at per-lane ``pos0`` ((B,) or scalar int32),
    writing their K/V into the cache. Returns (last-token logits (B, V),
    new cache) — token-identical to feeding the S tokens through
    ``decode_step`` one at a time (the per-position contractions are the
    same; tested)."""
    assert not cfg.n_codebooks, "chunk_step: single-codebook LMs only"
    hidden, new_cache, _ = forward(
        params, tokens, cfg, cache=cache, pos=pos0, mode="chunk"
    )
    last = hidden[:, -1]
    un = _unembed_matrix(params, cfg)
    logits = jnp.einsum(
        "bd,dv->bv", last.astype(jnp.float32), un.astype(jnp.float32)
    )
    return _scale_logits(logits, cfg), new_cache


def chunk_logits(params, tokens, cfg: ModelConfig, cache, pos0):
    """Like :func:`chunk_step` but returning the full (B, S, V) logits —
    the speculative-decode verify block scores every drafted token against
    the target model in one dispatch (DESIGN.md §16)."""
    assert not cfg.n_codebooks, "chunk_logits: single-codebook LMs only"
    hidden, new_cache, _ = forward(
        params, tokens, cfg, cache=cache, pos=pos0, mode="chunk"
    )
    un = _unembed_matrix(params, cfg)
    logits = jnp.einsum(
        "bsd,dv->bsv", hidden.astype(jnp.float32), un.astype(jnp.float32)
    )
    return _scale_logits(logits, cfg), new_cache
