"""Pallas TPU kernel: fused SECDED-decode + dequant + matmul (the ECC read path).

This is the TPU-native adaptation of the FPGA BRAM hard-core ECC port
(DESIGN.md §2): encoded int8 weights stream HBM->VMEM as (lo, hi, parity)
planes, are syndrome-checked and corrected with VPU bitwise ops *inside* the
tile loop, unpacked to int8, dequantised, and fed straight to the MXU — one
HBM pass, zero extra weight traffic for ECC beyond the 12.5% parity plane.

Weight packing (see ops.pack_ecc_weights): codeword i of column n holds the 8
int8 weights W[j*K/8 + i, n], j=0..7 (j<4 in `lo`, j>=4 in `hi`). A K block of
bk8 codewords is unpacked block-contiguously: byte j of the block's codeword i'
becomes tile row j*bk8 + i', so the tile is eight sublane-aligned (bk8, bn)
pieces stacked along rows and needs no sublane shuffle. The matching activation
permutation, x_perm[:, kb*bk + j*bk8 + i'] = x[:, j*K/8 + kb*bk8 + i'] for K
block kb, is a free reshape-transpose applied once per call in ops.py
(``permute_k``, with the bk8 of ``matmul_tiling``); the dot product is
permutation-invariant, so only the order of the f32 sums differs from the
plain matmul.

Decode: syndrome bit r is the parity of the codeword's bits under Hsiao row r
with the stored check bit folded in, taken by one population count; data bit
d flips iff its column equals the syndrome, evaluated bit-sliced as the AND
over the 8 rows of (syndrome bit r XNOR bit r of every column). A clean word,
a check-bit error and an even-weight (double-error) syndrome match no data
column, so they flip nothing.

Grid: (M/bm, N/bn, K/bk), k innermost, f32 accumulator in VMEM scratch.
VMEM per step (bm=128, bk=512, bn=256): x 256K + planes 148K + w 512K
+ acc 128K ~= 1.1 MiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hsiao

_U32 = jnp.uint32


def _decode_planes(lo, hi, stored_parity):
    """Syndrome + single-bit correction (no status plane — fused fast path)."""
    p = stored_parity.astype(_U32)
    flip_lo = jnp.full_like(lo, 0xFFFFFFFF)
    flip_hi = jnp.full_like(hi, 0xFFFFFFFF)
    for r in range(hsiao.N_PARITY):
        mlo = _U32(int(hsiao.MASK_LO[r]))
        mhi = _U32(int(hsiao.MASK_HI[r]))
        t = (lo & mlo) ^ (hi & mhi) ^ (p & _U32(1 << r))
        s = _U32(0) - (lax.population_count(t) & _U32(1))  # all ones iff set
        flip_lo = flip_lo & (s ^ ~mlo)
        flip_hi = flip_hi & (s ^ ~mhi)
    return lo ^ flip_lo, hi ^ flip_hi


def _unpack_int8(lo, hi, out_dtype):
    """(bk8, bn) u32 planes -> (8*bk8, bn) weights, byte j of codeword i on
    row j*bk8 + i."""
    pieces = []
    for word in (lo, hi):
        w = lax.bitcast_convert_type(word, jnp.int32)
        for j in range(4):
            b = w if j == 3 else w << (24 - 8 * j)
            pieces.append((b >> 24).astype(out_dtype))  # arithmetic: sign-extends
    return jnp.concatenate(pieces, axis=0)


def _matmul_kernel(x_ref, lo_ref, hi_ref, par_ref, out_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lo, hi = _decode_planes(lo_ref[...], hi_ref[...], par_ref[...])
    w = _unpack_int8(lo, hi, jnp.float32)
    acc_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32), w, preferred_element_type=jnp.float32
    )

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        out_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def ecc_matmul_2d(x, lo, hi, parity, *, block=(128, 512, 256), interpret=False):
    """x: (M, K) [K-permuted], planes: (K/8, N). Returns (M, N) float32."""
    m, kdim = x.shape
    k8, n = lo.shape
    assert kdim == 8 * k8, (x.shape, lo.shape)
    bm, bk, bn = block
    bk8 = bk // 8
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(kdim, bk))
    plane_spec = pl.BlockSpec((bk8, bn), lambda i, j, k: (k, j))
    return pl.pallas_call(
        _matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            plane_spec,
            plane_spec,
            plane_spec,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, lo, hi, parity)
