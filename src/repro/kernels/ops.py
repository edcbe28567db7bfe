"""Jit'd public wrappers around the Pallas kernels.

Handle arbitrary plane shapes (flatten/pad/reshape to lane-aligned 2D),
choose interpret mode automatically off-TPU, and expose the weight-packing
helpers used by the serving stack. `fuse=False` paths implement the *naive*
ECC read (separate decode pass materialising corrected weights to HBM) used
as the §Perf baseline against the fused kernel.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels import backend as _backend
from repro.kernels import ecc_matmul as _mm
from repro.kernels import fault_inject as _fi
from repro.kernels import inject_scrub as _isc
from repro.kernels import ref as _ref
from repro.kernels import secded as _secded

LANES = 512  # default 2D width for flattened planes (multiple of 128)

# Pallas launch accounting (benchmarks/kernel_micro voltage_sweep). Each
# wrapper below executes exactly one pallas_call per eager invocation; calls
# traced inside an outer jit are counted once per trace, so only eager-path
# comparisons (the engine voltage loop) are meaningful.
_launches = {"n": 0}


def reset_launch_count() -> None:
    _launches["n"] = 0


def launch_count() -> int:
    return _launches["n"]


def _count_launch(n: int = 1) -> None:
    _launches["n"] += n


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def use_interpret() -> bool:
    """True when the interpret lane is in force (the platform's lane, see
    kernels/backend.py)."""
    return _backend.use_interpret()


def _to_2d(*planes, lanes=LANES, block_rows=256):
    """Flatten + zero-pad planes to a common (rows, lanes) 2D layout.

    Rows are padded to a multiple of the kernel block so no grid step ever
    touches out-of-bounds memory. Returns (planes_2d, n, block) with the
    adapted (block_rows, lanes) block.
    """
    n = planes[0].size
    rows = max(1, -(-n // lanes))
    bm = min(block_rows, rows)
    rows = _round_up(rows, bm)
    pad = rows * lanes - n
    out = []
    for p in planes:
        flat = p.reshape(-1)
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), p.dtype)])
        out.append(flat.reshape(rows, lanes))
    return out, n, (bm, lanes)


def encode(lo: jnp.ndarray, hi: jnp.ndarray, *, codec: str = "secded72",
           interpret: bool | None = None):
    """ECC check plane for word planes of any shape (codec's check dtype)."""
    interpret = _backend.resolve_interpret(interpret)
    _count_launch()
    (lo2, hi2), n, block = _to_2d(lo, hi)
    par = _secded.encode_2d(lo2, hi2, block=block, codec=codec, interpret=interpret)
    return par.reshape(-1)[:n].reshape(lo.shape)


def decode(lo, hi, parity, *, codec: str = "secded72", interpret: bool | None = None):
    """ECC decode for planes of any shape -> (lo', hi', status int32)."""
    interpret = _backend.resolve_interpret(interpret)
    _count_launch()
    (lo2, hi2, par2), n, block = _to_2d(lo, hi, parity)
    olo, ohi, st = _secded.decode_2d(
        lo2, hi2, par2, block=block, codec=codec, interpret=interpret
    )
    unpad = lambda a: a.reshape(-1)[:n].reshape(lo.shape)
    return unpad(olo), unpad(ohi), unpad(st)


def inject(lo, hi, parity, mlo, mhi, mparity, *, interpret: bool | None = None):
    """Apply XOR flip masks to planes of any shape."""
    interpret = _backend.resolve_interpret(interpret)
    _count_launch()
    (a, b, c, d, e, f), n, block = _to_2d(lo, hi, parity, mlo, mhi, mparity)
    olo, ohi, opar = _fi.inject_2d(a, b, c, d, e, f, block=block, interpret=interpret)
    unpad = lambda x: x.reshape(-1)[:n].reshape(lo.shape)
    return unpad(olo), unpad(ohi), unpad(opar)


def inject_scrub(
    lo, hi, parity, mlo, mhi, mparity, *, codec: str = "secded72",
    reencode: bool = False, interpret: bool | None = None,
):
    """Fused inject + scrub: one pass over the planes instead of two (three
    with the no-ECC re-encode).

    Returns (faulty_lo, faulty_hi, faulty_parity, counters) where counters is
    an (N_COUNTERS,) int32 device vector ordered like telemetry.COUNTER_FIELDS.
    Zero-padding added by the 2D layout decodes clean with zero flips, so the
    pad count is subtracted from the clean counter before returning.
    """
    interpret = _backend.resolve_interpret(interpret)
    _count_launch()
    (a, b, c, d, e, f), n, block = _to_2d(lo, hi, parity, mlo, mhi, mparity)
    olo, ohi, opar, cnt = _isc.inject_scrub_2d(
        a, b, c, d, e, f, block=block, codec=codec, reencode=reencode,
        interpret=interpret,
    )
    counters = cnt.reshape(-1)[: _isc.N_COUNTERS].at[0].add(n - a.size)
    unpad = lambda x: x.reshape(-1)[:n].reshape(lo.shape)
    return unpad(olo), unpad(ohi), unpad(opar), counters


def inject_scrub_domains(
    lo, hi, parity, mlo, mhi, mparity, domain_ids, n_domains: int, *,
    codec: str = "secded72", reencode: bool = False, interpret: bool | None = None,
):
    """Fused inject + scrub with one counter row per memory domain.

    ``domain_ids``: int32 array shaped like ``lo`` mapping every word to its
    domain index in [0, n_domains). Layout pad words are routed to a spill
    row inside the kernel, so no pad correction is needed. Returns
    (faulty_lo, faulty_hi, faulty_parity, counters (n_domains, N_COUNTERS)).
    """
    interpret = _backend.resolve_interpret(interpret)
    _count_launch()
    (a, b, c, d, e, f), n, block = _to_2d(lo, hi, parity, mlo, mhi, mparity)
    # Pad the domain plane with the spill index (not 0: pad words must not
    # count as domain 0's clean words).
    flat_dom = domain_ids.reshape(-1).astype(jnp.int32)
    pad = a.size - n
    if pad:
        flat_dom = jnp.concatenate(
            [flat_dom, jnp.full((pad,), n_domains, jnp.int32)]
        )
    dom2 = flat_dom.reshape(a.shape)
    olo, ohi, opar, cnt = _isc.inject_scrub_domains_2d(
        a, b, c, d, e, f, dom2, n_domains=n_domains, block=block,
        codec=codec, reencode=reencode, interpret=interpret,
    )
    counters = cnt[:n_domains, : _isc.N_COUNTERS]
    unpad = lambda x: x.reshape(-1)[:n].reshape(lo.shape)
    return unpad(olo), unpad(ohi), unpad(opar), counters


# ---------------------------------------------------------------------------
# ECC-protected weights + fused matmul
# ---------------------------------------------------------------------------
@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class EccWeight:
    """SECDED-encoded int8 weight matrix (K, N) as word planes (K/8, N').

    N' is N, or N padded with zero columns (valid codewords of zero weights)
    to the fused kernel's padded width when N spans more than one N block
    and is not a multiple of it (``planes_width``): the padding is paid
    once here, not by a copy of the planes in every call."""

    lo: Any  # (K/8, N) uint32
    hi: Any  # (K/8, N) uint32
    parity: Any  # (K/8, N) uint8
    scale: Any  # per-tensor () or per-column (N,) float32
    k: int
    n: int
    fuse: bool = True  # fused Pallas read path vs naive decode-then-matmul

    def tree_flatten(self):
        return (self.lo, self.hi, self.parity, self.scale), (self.k, self.n, self.fuse)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


@jax.jit
def _pack_planes(qw):
    """int8 (K, N) -> SECDED planes on device; bit-identical to the host
    oracle ``ref.pack_ecc_weights_np`` (codeword i of column n packs
    W[j*K/8 + i, n], j = 0..7)."""
    from repro.core import ecc

    k, n = qw.shape
    wr = (qw.reshape(8, k // 8, n).astype(jnp.int32) & 0xFF).astype(jnp.uint32)
    lo = wr[0] | (wr[1] << 8) | (wr[2] << 16) | (wr[3] << 24)
    hi = wr[4] | (wr[5] << 8) | (wr[6] << 16) | (wr[7] << 24)
    return lo, hi, ecc.encode(lo, hi)


def planes_width(n: int, block_n: int = 256) -> int:
    """Stored plane width of an N-column weight: N padded to the fused
    kernel's N block where N is wider than one block and not a multiple of
    it (narrower matrices keep N: their per-call pad is one small block)."""
    return _round_up(n, block_n) if n > block_n and n % block_n else n


def pack_ecc_weights(w: jnp.ndarray, axis_scale: int | None = 1, fuse: bool = True) -> EccWeight:
    """Quantize a float (K, N) weight to int8 and SECDED-encode it."""
    from repro.core import quantize as q

    k, n = w.shape
    assert k % 8 == 0, f"K={k} must be a multiple of 8 (64-bit codewords)"
    qw, scale = q.quantize(w, axis=axis_scale)
    lo, hi, parity = _pack_planes(qw)
    pad = planes_width(n) - n
    if pad:
        lo, hi, parity = (jnp.pad(a, ((0, 0), (0, pad))) for a in (lo, hi, parity))
    return EccWeight(
        lo, hi, parity,
        scale.reshape(-1) if axis_scale is not None else scale, k, n, fuse,
    )


def permute_k(x: jnp.ndarray, k: int, bk8: int) -> jnp.ndarray:
    """Activation permutation matching the kernel's block-contiguous unpack
    (a free transpose): within each K block of ``bk8`` codewords,
    x_perm[kb*8*bk8 + j*bk8 + i] = x[j*k/8 + kb*bk8 + i]."""
    lead = x.shape[:-1]
    return (
        x.reshape(*lead, 8, k // (8 * bk8), bk8).swapaxes(-3, -2).reshape(*lead, k)
    )


def matmul_tiling(m: int, k: int, n: int, block=(128, 512, 256)):
    """Kernel block and padded (M, N) of the fused matmul for an
    (m, k) x (k, n) product: ``((bm, bk, bn), mp, np)``."""
    # bk8 must divide K/8 exactly: byte j of codeword i is weight row
    # j*K/8 + i, so the K dimension cannot be padded after packing.
    k8 = k // 8
    bk8 = block[1] // 8
    while k8 % bk8:
        bk8 //= 2
    # Pad M and N to block multiples (interpret-mode OOB reads are undefined).
    bm = min(block[0], _round_up(m, 8))
    bn = min(block[2], _round_up(n, 128))
    return (bm, bk8 * 8, bn), _round_up(m, bm), _round_up(n, bn)


def ecc_matmul(
    x: jnp.ndarray,
    w: EccWeight,
    *,
    fuse: bool = True,
    block=(128, 512, 256),
    interpret: bool | None = None,
):
    """x @ decode(w) with ECC correction on the read path.

    fuse=True : single-pass Pallas kernel (decode in VMEM, no extra HBM traffic)
    fuse=False: naive baseline — full decode pass materialises corrected int8
                weights to HBM, then a plain matmul re-reads them.
    """
    interpret = _backend.resolve_interpret(interpret)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, w.k)
    if fuse:
        m, n = x2.shape[0], w.n
        blk, mp, np_ = matmul_tiling(m, w.k, n, block)
        xp = jnp.pad(permute_k(x2, w.k, blk[1] // 8), ((0, mp - m), (0, 0)))
        planes = (w.lo, w.hi, w.parity)
        pad_n = np_ - w.lo.shape[-1]
        if pad_n:
            planes = tuple(jnp.pad(a, ((0, 0), (0, pad_n))) for a in planes)
        _count_launch()
        out = _mm.ecc_matmul_2d(xp, *planes, block=blk, interpret=interpret)[:m, :n]
    else:
        lo, hi, _ = decode(w.lo, w.hi, w.parity, interpret=interpret)
        w_i8 = _ref.unpack_ecc_weights(lo, hi)[:, : w.n]  # materialised (K, N) int8
        out = jnp.dot(x2.astype(jnp.float32), w_i8.astype(jnp.float32))
    out = out * w.scale
    return out.reshape(*lead, w.n)


def scrub(w: EccWeight, *, interpret: bool | None = None):
    """Telemetry pass (memory scrubber): decode all planes, return status."""
    _, _, status = decode(w.lo, w.hi, w.parity, interpret=interpret)
    return status
