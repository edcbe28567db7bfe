"""Main-path Pallas kernels compiled for a described TPU v5e chip.

Interpret mode accepts layouts Mosaic refuses (unaligned slices, too much
VMEM, unsupported gathers), so every kernel the serving path launches is
lowered and compiled here by the TPU compiler at the published qwen3-0.6b
widths, against one chip of a ``v5e:2x2`` topology that is described, not
attached. A compile that passes is not a run: results and times come only
from ``chip_smoke.py`` on the chip.

The topology is described inside a module-scoped fixture (never at import):
only the worker that runs this file loads the TPU compiler library.
"""

import functools
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs.qwen3_0_6b import config as qwen3_config
from repro.core.kvpages import KVGeometry, _commit_tokens, _gather_pages, _scatter_pages
from repro.kernels import ecc_matmul, ecc_ssd, inject_scrub, ops, paged_gather, secded

CFG = qwen3_config()
D, HD = CFG.d_model, CFG.hd
# (K, N) of every protected projection: wq, wk/wv, wo, w1/w3, w2
PROJECTIONS = [
    (D, CFG.n_heads * HD),
    (D, CFG.n_kv_heads * HD),
    (CFG.n_heads * HD, D),
    (D, CFG.d_ff),
    (CFG.d_ff, D),
]
# (K, N) of the wider cells' projections: qwen2-7b's MLP up and down, and
# granite-4.0-h-micro's Mamba-2 in_proj (N 8,512, padded to the N block) and
# MLP down, so the tile body is lowered at K 18944 and at a padded N
WIDE_PROJECTIONS = [(3584, 18944), (18944, 3584), (2048, 8512), (8192, 2048)]
ROWS, LANES = 2048, ops.LANES  # one 1M-word arena block in the ops 2D layout


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    # A compile for a described chip can be written to the persistent cache
    # but never read back without one: keep the cache out of these tests.
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, chip, *shapes, **static):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in shapes]
    hlo = jax.jit(lambda *a: fn(*a, interpret=False, **static)).lower(*args).compile()
    assert "tpu_custom_call" in hlo.as_text()
    return hlo


@pytest.mark.parametrize("m", [8, 512], ids=["decode", "prefill"])
@pytest.mark.parametrize(
    "kn", PROJECTIONS + WIDE_PROJECTIONS, ids=lambda kn: f"{kn[0]}x{kn[1]}"
)
def test_ecc_matmul_compiles(chip, kn, m):
    k, n = kn
    block, mp, np_ = ops.matmul_tiling(m, k, n)
    _compile(
        ecc_matmul.ecc_matmul_2d, chip,
        ((mp, k), CFG.compute_dtype),
        ((k // 8, np_), jnp.uint32), ((k // 8, np_), jnp.uint32),
        ((k // 8, np_), jnp.uint8),
        block=block,
    )


def test_inject_scrub_domains_compiles(chip):
    p32, p8 = ((ROWS, LANES), jnp.uint32), ((ROWS, LANES), jnp.uint8)
    _compile(
        inject_scrub.inject_scrub_domains_2d, chip,
        p32, p32, p8, p32, p32, p8, ((ROWS, LANES), jnp.int32),
        n_domains=3, block=(256, LANES),
    )


@pytest.mark.parametrize("pages", [4, 32])
def test_gather_scrub_compiles(chip, pages):
    words = KVGeometry.from_config(CFG).words_per_page
    bp = min(16, pages)  # gather_scrub_pages' page block
    p32, p8 = ((pages, words), jnp.uint32), ((pages, words), jnp.uint8)
    _compile(paged_gather.gather_scrub_2d, chip, p32, p32, p8, page_block=bp)


@pytest.mark.parametrize("helper", ["gather", "scatter", "commit"])
def test_page_window_addressing_has_no_word_index(chip, helper):
    """The KV store's window addressing at the qwen3-0.6b widths: the
    interval scrub's pages over a 128-entry table, and the commit of 128
    token rows. One int32 start offset per window, so the compiled program
    holds no int32 array of one element per word moved (a per-word index)."""
    geom = KVGeometry.from_config(CFG)
    rows, words = 128, geom.words_per_page
    arena = ((115 * words,), jnp.uint32)
    ids = ((rows,), jnp.int32)
    if helper == "gather":
        fn, shapes = functools.partial(_gather_pages, words_per_page=words), [arena, ids]
    elif helper == "scatter":
        fn = functools.partial(_scatter_pages, words_per_page=words)
        shapes = [arena, ids, ((rows, words), jnp.uint32)]
    else:
        words = geom.token_words
        fn = functools.partial(
            _commit_tokens, token_words=words, words_per_page=geom.words_per_page
        )
        shapes = [arena, arena, ((115 * geom.words_per_page,), jnp.uint8),
                  ((rows, geom.token_f32), jnp.float32), ids, ids]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert f"u32[{rows},{words}]" in text
    for dims in re.findall(r"s32\[([\d,]+)\]", text):
        n = 1
        for d in dims.split(","):
            n *= int(d)
        assert n < rows * words, f"s32[{dims}]"


def test_secded_encode_decode_compile(chip):
    p32, p8 = ((ROWS, LANES), jnp.uint32), ((ROWS, LANES), jnp.uint8)
    _compile(secded.encode_2d, chip, p32, p32, block=(256, LANES))
    _compile(secded.decode_2d, chip, p32, p32, p8, block=(256, LANES))


@pytest.mark.parametrize("codec", ["ileave88", "parity65"])
def test_other_gather_free_codecs_compile(chip, codec):
    from repro import codes

    check = jnp.dtype(codes.get(codec).check_dtype)
    p32, pc = ((ROWS, LANES), jnp.uint32), ((ROWS, LANES), check)
    _compile(
        inject_scrub.inject_scrub_2d, chip, p32, p32, pc, p32, p32, pc,
        codec=codec, block=(256, LANES),
    )


def test_dected79_lut_decode_has_no_lowering(chip):
    """The limit ReliabilityConfig.validate guards on the compiled lane:
    the dense-LUT DEC-TED classify (a 1-D jnp.take) does not lower."""
    p32, pc = ((ROWS, LANES), jnp.uint32), ((ROWS, LANES), jnp.uint32)
    with pytest.raises(NotImplementedError, match="gather"):
        _compile(secded.decode_2d, chip, p32, p32, pc, codec="dected79", block=(256, LANES))


def test_ecc_ssd_step_compiles(chip):
    """The protected Mamba-2 step at granite-4.0-h-micro's widths: 8 lanes
    of 64 heads, each head a (32, 128) tile of state codewords."""
    lanes, heads, half, n = 8, 64, 32, 128
    rows = ecc_ssd._heads_per_step(heads, half) * half
    r = lanes * heads * half
    p32, p8, col = ((r, n), jnp.uint32), ((r, n), jnp.uint8), ((r, 1), jnp.float32)
    vec = ((lanes, 1, n), jnp.float32)
    hlo = _compile(
        ecc_ssd.ecc_ssd_step_2d, chip, p32, p32, p8, col, col, col, ((r, 1), jnp.int32),
        vec, vec, rows=rows,
    )
    assert re.search(r"%ecc_ssd_step_2d[.\d]* = ", hlo.as_text())
