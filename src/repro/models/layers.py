"""Shared transformer layers: norms, RoPE, GQA/cross attention, MLPs.

Attention comes in four structurally different lowerings (not just masks),
because the roofline of each shape class differs:

  * `full_attention`    — direct einsum, used when S is small (train_4k).
  * `flash_attention`   — doubly-chunked online-softmax scan (prefill_32k):
                          O(S^2) FLOPs but O(S * chunk) memory.
  * `banded_attention`  — sliding-window prefill: per q-chunk a gathered KV
                          band, O(S * window) FLOPs (mixtral long-context).
  * `decode_attention`  — one token vs. a (possibly sequence-sharded) KV
                          cache; softmax reductions over the sharded S axis
                          lower to tiny all-reduces (flash-decoding).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops

NEG_INF = -1e30


def _linear(x, w):
    """Dense or ECC-protected linear: dispatch on the parameter type.

    `EccWeight` leaves route through the SECDED read path (the paper's
    technique as a first-class feature); plain arrays use an einsum.
    """
    if isinstance(w, kops.EccWeight):
        return kops.ecc_matmul(x, w, fuse=w.fuse).astype(x.dtype)
    return jnp.einsum("...d,df->...f", x, w)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def rms_norm(x, gamma, eps=1e-6):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * inv).astype(dt) * gamma


def layer_norm(x, gamma, beta, eps=1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps)).astype(dt) * gamma + beta


def apply_norm(x, p, norm_type, eps=1e-6):
    """``eps`` is the RMSNorm epsilon; LayerNorm keeps its own 1e-5."""
    if norm_type == "layernorm":
        return layer_norm(x, p["gamma"], p["beta"])
    return rms_norm(x, p["gamma"], eps)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_freqs(hd, theta):
    return theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)  # (hd/2,)


def apply_rope(x, positions, theta):
    """x: (B, S, H, Dh); positions: (B, S) or (S,)."""
    hd = x.shape[-1]
    inv = rope_freqs(hd, theta)
    ang = positions[..., None].astype(jnp.float32) * inv  # (B, S, hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[..., None, :]
    sin = sin[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention bodies
# ---------------------------------------------------------------------------
def _group_q(q, hkv):
    """(B, S, H, Dh) -> (B, S, Hkv, R, Dh): grouped-query layout.

    Used on the DECODE path only, where the KV cache is sequence-sharded: a
    broadcast+reshape of sharded KV would force a full cache all-gather.
    On train/prefill paths KV is replicated over the model axis, so the
    opposite layout wins: repeat KV locally (free broadcast) and keep the
    full q-head dim, which shards 16-way even when n_kv_heads < mesh model
    size (kv=8/4 archs).
    """
    b, s, h, dh = q.shape
    return q.reshape(b, s, hkv, h // hkv, dh)


def _repeat_kv(k, n_rep):
    """Local repeat of replicated KV heads (no collective when k is
    replicated over the model axis — train/prefill only)."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d
    )


def full_attention(q, k, v, *, causal=True, window=0):
    """q: (B, Sq, H, Dh), k/v: (B, Skv, Hkv, Dh). Direct einsum path."""
    b, sq, h, dh = q.shape
    skv = k.shape[1]
    k = _repeat_kv(k, h // k.shape[2])
    v = _repeat_kv(v, h // v.shape[2])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / jnp.sqrt(
        dh
    ).astype(jnp.float32)
    qpos = jnp.arange(sq)[:, None] + (skv - sq)
    kpos = jnp.arange(skv)[None, :]
    mask = kpos <= qpos if causal else jnp.ones((sq, skv), bool)
    if window:
        mask = mask & (kpos > qpos - window)
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def flash_attention(q, k, v, *, causal=True, q_chunk=1024, kv_chunk=1024, unroll=1):
    """Doubly-chunked online-softmax attention (pure JAX flash-style).

    Memory: O(B * H * q_chunk * kv_chunk) per step instead of O(S^2).
    """
    b, sq, h, dh = q.shape
    skv = k.shape[1]
    k = _repeat_kv(k, h // k.shape[2])
    v = _repeat_kv(v, h // v.shape[2])
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    assert sq % q_chunk == 0 and skv % kv_chunk == 0
    nq, nk = sq // q_chunk, skv // kv_chunk
    scale = 1.0 / jnp.sqrt(dh).astype(jnp.float32)

    qs = q.reshape(b, nq, q_chunk, h, dh).transpose(1, 0, 2, 3, 4)
    ks = k.reshape(b, nk, kv_chunk, h, dh).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(b, nk, kv_chunk, h, dh).transpose(1, 0, 2, 3, 4)

    def q_step(_, qi_qc):
        qi, qc = qi_qc  # qc: (B, q_chunk, H, Dh)
        m0 = jnp.full((b, h, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, q_chunk), jnp.float32)
        acc0 = jnp.zeros((b, q_chunk, h, dh), jnp.float32)

        def kv_step(carry, ki_kc):
            m, l, acc = carry
            ki, kc, vc = ki_kc
            s = jnp.einsum("bqhd,bkhd->bhqk", qc, kc).astype(jnp.float32) * scale
            if causal:
                qpos = qi * q_chunk + jnp.arange(q_chunk)[:, None] + (skv - sq)
                kpos = ki * kv_chunk + jnp.arange(kv_chunk)[None, :]
                s = jnp.where(kpos <= qpos, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr.transpose(0, 2, 1)[..., None] + jnp.einsum(
                "bhqk,bkhd->bqhd", p.astype(qc.dtype), vc
            ).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, acc0), (jnp.arange(nk), ks, vs), unroll=unroll
        )
        out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_step, None, (jnp.arange(nq), qs), unroll=unroll)
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, dh)


def banded_attention(q, k, v, *, window, q_chunk=1024, unroll=1):
    """Sliding-window causal attention with an explicit gathered KV band.

    For each q chunk [t, t+C) only KV [t-window, t+C) can be attended; we
    dynamic-slice that band so FLOPs are O(S * (window + C)), not O(S^2).
    """
    b, sq, h, dh = q.shape
    k = _repeat_kv(k, h // k.shape[2])
    v = _repeat_kv(v, h // v.shape[2])
    q_chunk = min(q_chunk, sq)
    assert sq % q_chunk == 0
    band = window + q_chunk
    # Left-pad KV by `window` so every band slice is in range.
    kp = jnp.pad(k, ((0, 0), (window, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (window, 0), (0, 0), (0, 0)))
    nq = sq // q_chunk
    scale = 1.0 / jnp.sqrt(dh).astype(jnp.float32)
    qs = q.reshape(b, nq, q_chunk, h, dh).transpose(1, 0, 2, 3, 4)

    def q_step(_, qi_qc):
        qi, qc = qi_qc
        start = qi * q_chunk  # band begins at (start - window) in unpadded coords
        kc = jax.lax.dynamic_slice_in_dim(kp, start, band, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(vp, start, band, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qc, kc).astype(jnp.float32) * scale
        qpos = jnp.arange(q_chunk)[:, None] + window  # position within band
        kpos = jnp.arange(band)[None, :]
        valid = (kpos <= qpos) & (kpos > qpos - window) & (kpos + start >= window)
        s = jnp.where(valid, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(qc.dtype)
        return None, jnp.einsum("bhqk,bkhd->bqhd", p, vc)

    _, outs = jax.lax.scan(q_step, None, (jnp.arange(nq), qs), unroll=unroll)
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, dh)


def decode_attention(q, k_cache, v_cache, cur_len, *, window=0):
    """One-token attention against a KV cache.

    q: (B, 1, H, Dh); caches: (B, S_max, Hkv, Dh); cur_len: () or (B,) int32 —
    number of valid cache entries (including the token being decoded); a (B,)
    vector gives every lane its own depth (continuous batching mixes requests
    at different positions in one batch).
    Softmax reductions over the cache S axis work transparently when S is
    sequence-sharded (flash-decoding lowers to tiny all-reduces).
    """
    b, _, h, dh = q.shape
    smax = k_cache.shape[1]
    hkv = k_cache.shape[2]
    qg = _group_q(q, hkv)  # (B, 1, Hkv, R, Dh)
    s = jnp.einsum("bqhrd,bkhd->bhrqk", qg, k_cache).astype(jnp.float32) / jnp.sqrt(
        dh
    ).astype(jnp.float32)
    kpos = jnp.arange(smax)[None, None, None, None, :]
    cur_len = jnp.asarray(cur_len)
    if cur_len.ndim:
        cur_len = cur_len.reshape(b, 1, 1, 1, 1)
    valid = kpos < cur_len
    if window:
        valid = valid & (kpos >= cur_len - window)
    s = jnp.where(valid, s, NEG_INF)
    # Softmax + weighted-sum reductions run over the (sequence-sharded) cache
    # axis: GSPMD lowers them to tiny max/sum/partial-out all-reduces — this
    # IS flash-decoding, derived by the partitioner.
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhrqk,bkhd->bqhrd", p, v_cache)
    return out.reshape(b, 1, h, dh)


def chunk_attention(q, k_cache, v_cache, pos0, *, window=0):
    """Multi-token attention against a KV cache (chunked prefill and the
    speculative verify block, DESIGN.md §16).

    q: (B, Sq, H, Dh) — Sq new tokens whose K/V were already written into the
    cache; caches: (B, S_max, Hkv, Dh); pos0: () or (B,) int32 — the cache
    position of the chunk's *first* token per lane. Token i of the chunk
    attends kpos <= pos0 + i, so for Sq == 1 this is exactly
    ``decode_attention(q, k, v, cur_len=pos0 + 1)``: the same grouped-query
    einsum contracting the same axes per position, which is what keeps the
    chunked path bit-identical to the step-by-step decode path.
    """
    b, sq, h, dh = q.shape
    smax = k_cache.shape[1]
    hkv = k_cache.shape[2]
    qg = _group_q(q, hkv)  # (B, Sq, Hkv, R, Dh)
    s = jnp.einsum("bqhrd,bkhd->bhrqk", qg, k_cache).astype(jnp.float32) / jnp.sqrt(
        dh
    ).astype(jnp.float32)
    kpos = jnp.arange(smax)[None, None, None, None, :]
    qpos = jnp.asarray(pos0).reshape(-1, 1, 1, 1, 1) + jnp.arange(sq).reshape(
        1, 1, 1, sq, 1
    )
    valid = kpos <= qpos
    if window:
        valid = valid & (kpos > qpos - window)
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhrqk,bkhd->bqhrd", p, v_cache)
    return out.reshape(b, sq, h, dh)


# ---------------------------------------------------------------------------
# Projections / MLP
# ---------------------------------------------------------------------------
def qkv_proj(x, p, cfg):
    """x: (B, S, D) -> q (B,S,H,Dh), k/v (B,S,Hkv,Dh)."""
    b, s, _ = x.shape
    q = _linear(x, p["wq"])
    k = _linear(x, p["wk"])
    v = _linear(x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(b, s, cfg.n_heads, cfg.hd)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.hd)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    return q, k, v


def out_proj(attn_out, p):
    b, s = attn_out.shape[:2]
    return _linear(attn_out.reshape(b, s, -1), p["wo"])


_ACTS = {
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),  # nemotron/minitron MLP
}


def mlp(x, p, cfg):
    if cfg.gated_mlp:
        gate = jax.nn.silu(_linear(x, p["w1"]))
        up = _linear(x, p["w3"])
        return _linear(gate * up, p["w2"])
    h = _ACTS[cfg.mlp_act](_linear(x, p["w1"]))
    return _linear(h, p["w2"])
