"""Pallas kernel: one Mamba-2 decode step on a SECDED-protected state.

The state store (core/statestore.py) holds each lane's SSM state only as
SECDED(72,64) planes: for head h, codeword (i, n) packs row i of the head's
(P, N) float32 state in ``lo`` and row i + P/2 in ``hi``. One grid step
takes ``heads`` heads of one lane as (heads * P/2, N) tiles and

  * recomputes the syndrome of every codeword, corrects single-bit words
    and counts clean / corrected / detected words of live lanes (as
    ``paged_gather.gather_scrub_2d`` counts a page);
  * applies the one-step update h <- dA * h + u (x) B per row (dA the
    head's decay exp(dt A), u = dt x the row's input) and produces
    y = h . C;
  * re-encodes the new state and writes the planes back in place
    (``input_output_aliases``).

Rows of idle lanes (``live`` 0) are written back as they were stored and
count nothing. A detected word is decoded as stored, used, counted and
re-encoded: the count is what reports it (core/statestore.py).

Layout (2D, rows = lane-major (lane, head, i), N on the lanes): planes
(R, N) with R = lanes * H * P/2; dA, u, live per row (R, 1); B and C per
lane (lanes, 1, N). Counters: one (8, 128) int32 tile per grid step,
lanes 0..2 of its row 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import codes

_CNT = (8, 128)


def _kernel(lo_ref, hi_ref, par_ref, da_ref, ulo_ref, uhi_ref, live_ref, b_ref, c_ref,
            olo_ref, ohi_ref, opar_ref, ylo_ref, yhi_ref, cnt_ref, *, codec):
    lo, hi, stored = lo_ref[...], hi_ref[...], par_ref[...]
    live = live_ref[...] > 0  # (R, 1)
    synd = codec.encode_jnp(lo, hi) ^ stored.astype(jnp.uint32)
    flip_lo, flip_hi, _, status = codec.classify_jnp(synd)
    h_lo = jax.lax.bitcast_convert_type(lo ^ flip_lo, jnp.float32)
    h_hi = jax.lax.bitcast_convert_type(hi ^ flip_hi, jnp.float32)
    da, bv, cv = da_ref[...], b_ref[0], c_ref[0]  # (R, 1), (1, N), (1, N)
    h_lo = da * h_lo + ulo_ref[...] * bv
    h_hi = da * h_hi + uhi_ref[...] * bv
    ylo_ref[...] = jnp.sum(h_lo * cv, axis=1, keepdims=True)
    yhi_ref[...] = jnp.sum(h_hi * cv, axis=1, keepdims=True)
    nlo = jax.lax.bitcast_convert_type(h_lo, jnp.uint32)
    nhi = jax.lax.bitcast_convert_type(h_hi, jnp.uint32)
    olo_ref[...] = jnp.where(live, nlo, lo)
    ohi_ref[...] = jnp.where(live, nhi, hi)
    opar_ref[...] = jnp.where(live, codec.encode_jnp(nlo, nhi).astype(stored.dtype), stored)

    lane = jax.lax.broadcasted_iota(jnp.int32, _CNT, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, _CNT, 0)
    count = lambda s: jnp.sum(jnp.where(live & (status == s), 1, 0))
    vals = jnp.zeros(_CNT, jnp.int32)
    for s in range(3):
        vals = jnp.where((row == 0) & (lane == s), count(s), vals)
    cnt_ref[...] = vals


@functools.partial(jax.jit, static_argnames=("rows", "interpret"))
def ecc_ssd_step_2d(lo, hi, par, da, u_lo, u_hi, live, bm, cm, *, rows, interpret=False):
    """One protected SSD step over (R, N) planes, ``rows`` rows a grid step.

    lo/hi (R, N) uint32, par (R, N) uint8; da, u_lo, u_hi (R, 1) float32;
    live (R, 1) int32; bm, cm (lanes, 1, N) float32, each lane's rows
    contiguous and a multiple of ``rows``. Returns (lo', hi', par' (aliased
    to the inputs), y_lo, y_hi (R, 1) float32, counters (steps*8, 128))."""
    r, n = lo.shape
    lanes = bm.shape[0]
    steps = r // rows
    per_lane = steps // lanes
    plane = pl.BlockSpec((rows, n), lambda i: (i, 0))
    col = pl.BlockSpec((rows, 1), lambda i: (i, 0))
    vec = pl.BlockSpec((1, 1, n), lambda i: (i // per_lane, 0, 0))
    c = codes.get("secded72")
    return pl.pallas_call(
        functools.partial(_kernel, codec=c),
        grid=(steps,),
        in_specs=[plane, plane, plane, col, col, col, col, vec, vec],
        out_specs=[plane, plane, plane, col, col, pl.BlockSpec(_CNT, lambda i: (i, 0))],
        out_shape=(
            jax.ShapeDtypeStruct(lo.shape, jnp.uint32),
            jax.ShapeDtypeStruct(lo.shape, jnp.uint32),
            jax.ShapeDtypeStruct(lo.shape, par.dtype),
            jax.ShapeDtypeStruct((r, 1), jnp.float32),
            jax.ShapeDtypeStruct((r, 1), jnp.float32),
            jax.ShapeDtypeStruct((steps * _CNT[0], _CNT[1]), jnp.int32),
        ),
        input_output_aliases={0: 0, 1: 1, 2: 2},
        interpret=interpret,
    )(lo, hi, par, da, u_lo, u_hi, live, bm, cm)


def _heads_per_step(h: int, half: int, target_rows: int = 512) -> int:
    hb = max(1, min(h, target_rows // half))
    while h % hb:
        hb -= 1
    return hb


def ecc_ssd_step(lo, hi, par, da, u, bm, cm, live, *, interpret: bool | None = None):
    """One Mamba-2 decode step of every lane on its protected state.

    lo/hi/par: (lanes, H, P/2, N) planes; da: (lanes, H) decays exp(dt A);
    u: (lanes, H, P) = dt * x; bm, cm: (lanes, N); live: (lanes,) int.
    Returns (y (lanes, H, P) = h' . C without the D skip, lo', hi', par',
    counts (lanes, 3) of clean / corrected / detected words)."""
    from repro.kernels import backend as _backend
    from repro.kernels import ops as kops

    interpret = _backend.resolve_interpret(interpret)
    kops._count_launch()
    lanes, h, half, n = lo.shape
    hb = _heads_per_step(h, half)
    flat = lambda t: t.reshape(lanes * h * half, n)
    rowcol = lambda t: t.reshape(lanes * h * half, 1).astype(jnp.float32)
    da_rows = jnp.broadcast_to(da[:, :, None], (lanes, h, half))
    live_rows = jnp.broadcast_to(live.astype(jnp.int32)[:, None, None], (lanes, h, half))
    olo, ohi, opar, ylo, yhi, cnt = ecc_ssd_step_2d(
        flat(lo), flat(hi), flat(par), rowcol(da_rows),
        rowcol(u[:, :, :half]), rowcol(u[:, :, half:]),
        live_rows.reshape(-1, 1), bm[:, None, :].astype(jnp.float32),
        cm[:, None, :].astype(jnp.float32), rows=hb * half, interpret=interpret,
    )
    y = jnp.concatenate(
        [ylo.reshape(lanes, h, half), yhi.reshape(lanes, h, half)], axis=2
    )
    counts = cnt.reshape(lanes, h // hb, _CNT[0], _CNT[1])[:, :, 0, :3].sum(axis=1)
    shape = lo.shape
    return y, olo.reshape(shape), ohi.reshape(shape), opar.reshape(shape), counts
