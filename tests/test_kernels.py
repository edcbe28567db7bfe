"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import backend, ecc_matmul, ops, ref


@pytest.mark.parametrize(
    "shape", [(64,), (1000,), (256, 512), (7, 13), (3, 5, 7)]
)
def test_encode_decode_inject_match_oracle(shape, rng):
    lo = jnp.asarray(rng.integers(0, 2**32, shape, dtype=np.uint32))
    hi = jnp.asarray(rng.integers(0, 2**32, shape, dtype=np.uint32))
    par_k = ops.encode(lo, hi)
    par_r = ref.encode_ref(lo, hi)
    assert np.array_equal(np.asarray(par_k), np.asarray(par_r))

    mask = rng.integers(0, 2**32, shape, dtype=np.uint32)
    for _ in range(4):  # sparsify
        mask &= rng.integers(0, 2**32, shape, dtype=np.uint32)
    z32 = jnp.zeros(shape, jnp.uint32)
    zp = jnp.zeros(shape, jnp.uint8)
    flo, fhi, fpar = ops.inject(lo, hi, par_k, jnp.asarray(mask), z32, zp)
    r = ref.inject_ref(lo, hi, par_k, jnp.asarray(mask), z32, zp)
    assert all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip((flo, fhi, fpar), r))

    out_k = ops.decode(flo, fhi, fpar)
    out_r = ref.decode_ref(flo, fhi, fpar)
    for a, b in zip(out_k, out_r):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# After the first three: M 8 at K 256 and 384 (matmul_tiling's bk8 of 32 and
# 16; K 64 takes 8), then at every K of the benchmark's projections
# (qwen3-0.6b, qwen2-7b, granite-4.0-h-micro): bk8 64 over 2 to 37 K blocks,
# so the block-contiguous activation permutation meets each.
@pytest.mark.parametrize(
    "mkn",
    [(8, 64, 128), (33, 512, 256), (128, 1024, 130)]
    + [(8, k, 128) for k in (256, 384, 1024, 2048, 3072, 3584, 8192, 18944)],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ecc_matmul_fused_naive_oracle(mkn, dtype, rng):
    m, k, n = mkn
    x = jnp.asarray(rng.standard_normal((m, k)), dtype)
    w = jnp.asarray(rng.standard_normal((k, n)) * 0.05, jnp.float32)
    ew = ops.pack_ecc_weights(w)
    out_f = np.asarray(ops.ecc_matmul(x, ew, fuse=True))
    out_n = np.asarray(ops.ecc_matmul(x, ew, fuse=False))
    out_r = np.asarray(ref.ecc_matmul_ref(x, ew.lo, ew.hi, ew.parity, ew.scale))
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(out_f, out_r, rtol=tol, atol=tol)
    np.testing.assert_allclose(out_n, out_r, rtol=tol, atol=tol)


def test_fused_kernel_corrects_all_single_bit_faults(rng):
    m, k, n = 16, 512, 256
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, n)) * 0.05, jnp.float32)
    ew = ops.pack_ecc_weights(w)
    clean = np.asarray(ops.ecc_matmul(x, ew, fuse=True))
    sel = rng.random(ew.lo.shape) < 0.2
    bit = rng.integers(0, 64, ew.lo.shape)
    mlo = np.where(sel & (bit < 32), np.uint32(1) << bit.astype(np.uint32), 0).astype(np.uint32)
    mhi = np.where(sel & (bit >= 32), np.uint32(1) << (bit - 32).astype(np.uint32), 0).astype(np.uint32)
    faulty = dataclasses.replace(ew, lo=ew.lo ^ jnp.asarray(mlo), hi=ew.hi ^ jnp.asarray(mhi))
    out = np.asarray(ops.ecc_matmul(x, faulty, fuse=True))
    np.testing.assert_array_equal(out, clean)
    status = np.asarray(ops.scrub(faulty))
    assert (status == 1).sum() == sel.sum()


def _bit_masks(bits):
    """Codeword bit indices (0-31 lo, 32-63 hi, 64-71 check) -> XOR masks."""
    one = np.uint32(1)
    mlo = np.where(bits < 32, one << np.clip(bits, 0, 31).astype(np.uint32), 0)
    mhi = np.where(
        (bits >= 32) & (bits < 64), one << np.clip(bits - 32, 0, 31).astype(np.uint32), 0
    )
    mpar = np.where(bits >= 64, one << np.clip(bits - 64, 0, 7).astype(np.uint32), 0)
    return mlo.astype(np.uint32), mhi.astype(np.uint32), mpar.astype(np.uint8)


def _random_codewords(rng, n):
    lo = rng.integers(0, 2**32, n, dtype=np.uint32)
    hi = rng.integers(0, 2**32, n, dtype=np.uint32)
    return lo, hi, np.asarray(ref.encode_ref(jnp.asarray(lo), jnp.asarray(hi)))


_tile_decode = jax.jit(ecc_matmul._decode_planes)


def _assert_tile_decode(lo, hi, par, flips, want_lo, want_hi):
    faulty = [a ^ m for a, m in zip((lo, hi, par), flips)]
    got = _tile_decode(*(jnp.asarray(a) for a in faulty))
    oracle = ref.decode_ref(*(jnp.asarray(a) for a in faulty))
    for g, o, w in zip(got, oracle, (want_lo, want_hi)):
        assert np.array_equal(np.asarray(g), np.asarray(o))
        assert np.array_equal(np.asarray(g), w)


@pytest.mark.parametrize("bit", range(72))
def test_tile_decode_corrects_every_single_bit_flip(bit):
    """The fused kernel's tile decode, run as plain jnp, restores the word
    for a flip of any one of the 72 codeword bits, as the oracle does."""
    lo, hi, par = _random_codewords(np.random.default_rng(bit), 512)
    _assert_tile_decode(lo, hi, par, _bit_masks(np.full(512, bit)), lo, hi)


@pytest.mark.parametrize("n_flips", [0, 2])
def test_tile_decode_leaves_clean_and_double_flipped_words(n_flips):
    """Clean words, and every one of the 2,556 double flips (one random
    codeword each), decode to the data as read, as the oracle does."""
    pairs = np.array([(a, b) for a in range(72) for b in range(a + 1, 72)])
    lo, hi, par = _random_codewords(np.random.default_rng(72 + n_flips), len(pairs))
    flips = [np.zeros_like(a) for a in (lo, hi, par)]
    for col in pairs.T[:n_flips]:
        flips = [f ^ m for f, m in zip(flips, _bit_masks(col))]
    _assert_tile_decode(lo, hi, par, flips, lo ^ flips[0], hi ^ flips[1])


@pytest.mark.parametrize("kn", [(64, 128), (1024, 130)])
def test_pack_ecc_weights_matches_numpy_oracle(kn, rng):
    w = jnp.asarray(rng.standard_normal(kn) * 0.05, jnp.float32)
    ew = ops.pack_ecc_weights(w)
    from repro.core import quantize

    qw, _ = quantize.quantize(w, axis=1)
    for got, want in zip(
        (ew.lo, ew.hi, ew.parity), ref.pack_ecc_weights_np(np.asarray(qw))
    ):
        assert got.dtype == want.dtype
        assert np.array_equal(np.asarray(got), want)


def test_int8_word_packing_roundtrip(rng):
    from repro.core import quantize

    q = jnp.asarray(rng.integers(-127, 128, 333, dtype=np.int8))
    lo, hi = quantize.pack_int8_to_words(q)
    q2 = quantize.unpack_words_to_int8(lo, hi, q.size)
    assert np.array_equal(np.asarray(q2), np.asarray(q))


@pytest.mark.parametrize("dtype", [np.float32, np.int8, np.uint32, np.float64])
def test_bit_exact_array_words_roundtrip(dtype, rng):
    from repro.core import quantize

    arr = rng.standard_normal(97).astype(dtype) if dtype != np.uint32 else rng.integers(
        0, 2**32, 97, dtype=np.uint32
    )
    lo, hi, nbytes = quantize.array_to_words_np(arr)
    back = np.asarray(quantize.words_to_array(jnp.asarray(lo), jnp.asarray(hi), nbytes, arr.shape, arr.dtype))
    assert np.array_equal(back.view(np.uint8), arr.view(np.uint8))


# ---------------------------------------------------------------------------
# backend selection (DESIGN.md §18): compiled lane vs interpret lane


def _backend_case_arrays(rng):
    shape = (64, 512)
    lo = jnp.asarray(rng.integers(0, 2**32, shape, dtype=np.uint32))
    hi = jnp.asarray(rng.integers(0, 2**32, shape, dtype=np.uint32))
    par = ops.encode(lo, hi, interpret=True)
    mask = rng.integers(0, 2**32, shape, dtype=np.uint32)
    for _ in range(4):  # sparsify
        mask &= rng.integers(0, 2**32, shape, dtype=np.uint32)
    mlo = jnp.asarray(mask)
    z32 = jnp.zeros(shape, jnp.uint32)
    zp = jnp.zeros(shape, jnp.uint8)
    return lo, hi, par, mlo, z32, zp


_BACKEND_CASES = {
    "encode": lambda a, i: ops.encode(a[0], a[1], interpret=i),
    "decode": lambda a, i: ops.decode(a[0], a[1], a[2], interpret=i),
    "inject": lambda a, i: ops.inject(*a, interpret=i),
    "inject_scrub": lambda a, i: ops.inject_scrub(*a, interpret=i),
}


@pytest.mark.skipif(
    not backend.compiled_available(),
    reason="no compiled Pallas lowering on this host (interpret-only)",
)
@pytest.mark.parametrize("name", sorted(_BACKEND_CASES))
def test_compiled_matches_interpret_bit_for_bit(name, rng):
    """On hosts with a real Pallas lowering, the compiled lane is
    bit-identical to the interpret lane for every kernel entry point."""
    arrays = _backend_case_arrays(rng)
    fn = _BACKEND_CASES[name]
    got = jax.tree.leaves(fn(arrays, False))
    want = jax.tree.leaves(fn(arrays, True))
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w)), name


def test_forced_compiled_falls_back_cleanly_on_cpu(rng):
    """A compiled request never degrades to the interpreter: on a host with
    no Pallas lowering, forcing backend=compiled raises (globally and per
    call) while an explicit interpret=True reference call still runs; where
    the lowering exists the forced lane is bit-identical to that reference."""
    arrays = _backend_case_arrays(rng)
    want = jax.tree.leaves(ops.inject_scrub(*arrays, interpret=True))
    backend.set_backend("compiled")
    try:
        if backend.compiled_available():
            got = jax.tree.leaves(ops.inject_scrub(*arrays, interpret=None))
            for g, w in zip(got, want):
                assert np.array_equal(np.asarray(g), np.asarray(w))
        else:
            with pytest.raises(backend.BackendUnavailable):
                ops.inject_scrub(*arrays, interpret=None)
            with pytest.raises(backend.BackendUnavailable):
                backend.resolve()
    finally:
        backend.set_backend(None)
    if not backend.compiled_available():
        with pytest.raises(backend.BackendUnavailable):
            ops.inject_scrub(*arrays, interpret=False)


def test_backend_modes_and_tag():
    assert backend.requested() in backend.VALID
    lane = backend.platform_lane()
    assert backend.tag() == lane
    with pytest.raises(ValueError):
        backend.set_backend("mosaic")
    other = "interpret" if lane == "compiled" else "compiled"
    backend.set_backend(lane)
    try:
        assert backend.use_interpret() is (lane == "interpret")
        assert backend.resolve() == lane
        # an explicit per-call interpret=True is the reference lane and is
        # honored everywhere; interpret=False needs a real lowering
        assert backend.resolve_interpret(True) is True
        if lane == "interpret":
            with pytest.raises(backend.BackendUnavailable):
                backend.resolve_interpret(False)
        else:
            assert backend.resolve_interpret(False) is False
        # the platform has exactly one lane: requesting the other raises
        backend.set_backend(other)
        with pytest.raises(backend.BackendUnavailable):
            backend.resolve()
    finally:
        backend.set_backend(None)
