"""Mamba-2 (SSD) mixer of the attention + Mamba-2 hybrids (Granite-4.0-H).

Per layer, after HF ``GraniteMoeHybridMambaLayer`` / ``Mamba2Mixer`` with
one B/C group:

    z, xBC, dt = in_proj(x)                       (d_inner, d_inner + 2N, H)
    xBC = silu(causal depthwise conv_K(xBC) + conv_b);  x, B, C = split(xBC)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)  (one scalar decay per head)
    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t   (per head: (P, N))
    y_t = h_t C_t + D x_t
    out = out_proj(RMSNorm(y * silu(z)) * norm)   (over all d_inner channels)

Prefill runs the chunked SSD form (``ssd_chunked``: quadratic within a chunk,
a scan over chunk states); decode the one-step form. The state is float32.
Decode takes the state either as floats (``step``) or as the SECDED planes
of core/statestore.py (``step_protected``): the fused kernel
``kernels/ecc_ssd.ecc_ssd_step_2d`` decodes, corrects, updates and re-encodes
the SSM state, and the conv tail is decoded and re-encoded beside it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import statestore
from repro.kernels import ecc_ssd
from repro.models import layers, mamba


def dims(cfg):
    """(heads, head size, state size, conv channels)."""
    h = cfg.d_inner // cfg.ssm_head_dim
    return h, cfg.ssm_head_dim, cfg.d_state, cfg.d_inner + 2 * cfg.d_state


def _project(x, p, cfg):
    """in_proj -> z (.., di), xBC (.., conv), dt (.., H) in float32."""
    h, _, _, conv = dims(cfg)
    di = cfg.d_inner
    zxbcdt = layers._linear(x, p["in_proj"]).astype(jnp.float32)
    return zxbcdt[..., :di], zxbcdt[..., di : di + conv], zxbcdt[..., di + conv :]


def _split_xbc(xbc, cfg):
    di, n = cfg.d_inner, cfg.d_state
    return xbc[..., :di], xbc[..., di : di + n], xbc[..., di + n :]


def _out(y, z, p, cfg, dtype):
    """Gated RMSNorm over d_inner, then out_proj."""
    g = y * jax.nn.silu(z)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + cfg.norm_eps)
    g = (g * p["norm"].astype(jnp.float32)).astype(dtype)
    return layers._linear(g, p["out_proj"])


def _decay(p, dt_raw):
    dt = jax.nn.softplus(dt_raw + p["dt_bias"].astype(jnp.float32))
    return dt, -jnp.exp(p["a_log"].astype(jnp.float32))


def ssd_chunked(x, dt, a, bm, cm, chunk):
    """Chunked SSD from a zero state.

    x: (B, S, H, P); dt: (B, S, H); a: (H,); bm, cm: (B, S, N), all float32.
    Returns (y (B, S, H, P) without the D skip, final state (B, H, P, N)).
    Padding positions take dt = 0: decay 1 and no input, so the state
    passes them unchanged.
    """
    b, s, h, p = x.shape
    n = bm.shape[-1]
    ln = min(chunk, s)
    nc = -(-s // ln)
    pad = nc * ln - s
    if pad:
        padf = lambda t: jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        x, dt, bm, cm = padf(x), padf(dt), padf(bm), padf(cm)
    x = x.reshape(b, nc, ln, h, p)
    dt = dt.reshape(b, nc, ln, h)
    bm = bm.reshape(b, nc, ln, n)
    cm = cm.reshape(b, nc, ln, n)
    acs = jnp.cumsum(dt * a, axis=2)  # (B, c, L, H), non-increasing in L
    causal = jnp.tril(jnp.ones((ln, ln), bool))
    seg = acs[:, :, :, None, :] - acs[:, :, None, :, :]  # (B, c, i, j, H)
    decay = jnp.exp(jnp.where(causal[None, None, :, :, None], seg, -jnp.inf))
    cb = jnp.einsum("bcin,bcjn->bcij", cm, bm)
    y = jnp.einsum("bcij,bcijh,bcjh,bcjhp->bcihp", cb, decay, dt, x)
    # each chunk's own end state, then the scan over chunk states
    to_end = jnp.exp(acs[:, :, -1:, :] - acs) * dt  # (B, c, L, H)
    own = jnp.einsum("bcjn,bcjh,bcjhp->bchpn", bm, to_end, x)
    chunk_decay = jnp.exp(acs[:, :, -1, :])  # (B, c, H)

    def cross(hc, t):
        dec, st = t
        return dec[:, :, None, None] * hc + st, hc

    h_fin, h_in = jax.lax.scan(
        cross,
        jnp.zeros((b, h, p, n), jnp.float32),
        (chunk_decay.swapaxes(0, 1), own.swapaxes(0, 1)),
    )
    h_in = h_in.swapaxes(0, 1)  # (B, c, H, P, N): state entering chunk c
    y = y + jnp.einsum("bcin,bchpn,bcih->bcihp", cm, h_in, jnp.exp(acs))
    return y.reshape(b, nc * ln, h, p)[:, :s], h_fin


def forward(x, p, cfg):
    """Train / prefill from a zero state. x: (B, S, D).

    Returns (out (B, S, D), state {"conv": (B, K-1, C), "ssm": (B, H, P, N)})."""
    b, s, _ = x.shape
    h, hp, _, _ = dims(cfg)
    z, xbc, dt_raw = _project(x, p, cfg)
    k = cfg.d_conv
    tail = jnp.pad(xbc, ((0, 0), (max(0, k - 1 - s), 0), (0, 0)))[:, -(k - 1) :]
    xbc = jax.nn.silu(
        mamba._conv_causal(
            xbc, p["conv_w"].astype(jnp.float32), p["conv_b"].astype(jnp.float32)
        )
    )
    xs, bm, cm = _split_xbc(xbc, cfg)
    dt, a = _decay(p, dt_raw)
    xs = xs.reshape(b, s, h, hp)
    y, h_fin = ssd_chunked(xs, dt, a, bm, cm, cfg.ssm_chunk)
    y = y + xs * p["d_skip"].astype(jnp.float32)[:, None]
    out = _out(y.reshape(b, s, -1), z, p, cfg, x.dtype)
    return out, {"conv": tail, "ssm": h_fin}


def _conv_step(tail, xbc, p):
    """One conv step. tail (B, K-1, C), xbc (B, C) -> (activated (B, C),
    new tail)."""
    win = jnp.concatenate([tail, xbc[:, None]], axis=1)  # (B, K, C)
    w = p["conv_w"].astype(jnp.float32)
    out = p["conv_b"].astype(jnp.float32)
    for i in range(w.shape[1]):
        out = out + win[:, i] * w[:, i]
    return jax.nn.silu(out), win[:, 1:]


def _step(x, p, cfg, tail, update):
    """One decode step around ``update(dA (B, H), u = dt x (B, H, P), B, C)
    -> (h' . C (B, H, P), new SSM state)``. Returns (out (B, 1, D), new conv
    tail, new SSM state)."""
    b = x.shape[0]
    h, hp, _, _ = dims(cfg)
    z, xbc, dt_raw = _project(x[:, 0], p, cfg)
    xbc, tail = _conv_step(tail, xbc, p)
    xs, bm, cm = _split_xbc(xbc, cfg)
    dt, a = _decay(p, dt_raw)
    xs = xs.reshape(b, h, hp)
    ys, state = update(jnp.exp(dt * a), dt[..., None] * xs, bm, cm)
    y = ys + xs * p["d_skip"].astype(jnp.float32)[:, None]
    return _out(y.reshape(b, 1, -1), z[:, None], p, cfg, x.dtype), tail, state


def step(x, p, cfg, state):
    """One decode step from a float state. x: (B, 1, D)."""

    def update(da, u, bm, cm):
        hs = da[..., None, None] * state["ssm"] + u[..., None] * bm[:, None, None, :]
        return jnp.sum(hs * cm[:, None, None, :], axis=-1), hs

    out, tail, hs = _step(x, p, cfg, state["conv"], update)
    return out, {"conv": tail, "ssm": hs}


def step_protected(x, p, cfg, slots):
    """One decode step whose state lives in SECDED planes (core/statestore).

    ``slots``: one layer's planes for every lane, the lanes' ``live`` mask
    and the running ``cnt`` (B, 3) counters. Idle lanes keep their planes
    and count nothing. Returns (out (B, 1, D), updated slots)."""
    live = slots["live"]
    tail, conv_cnt = statestore.open_conv(slots, live)

    def update(da, u, bm, cm):
        ys, *planes = ecc_ssd.ecc_ssd_step(
            slots["ssm_lo"], slots["ssm_hi"], slots["ssm_par"], da, u, bm, cm, live
        )
        return ys, planes

    out, tail, (lo, hi, par, ssm_cnt) = _step(x, p, cfg, tail, update)
    new = statestore.seal_conv(dict(slots, ssm_lo=lo, ssm_hi=hi, ssm_par=par), tail, live)
    new["cnt"] = slots["cnt"] + conv_cnt + ssm_cnt
    return out, new
