"""Mamba-2 (SSD) hybrids on the protected serve() path (DESIGN.md §19).

Pins down:
  * the chunked SSD prefill equals the sequential recurrence it stands for;
  * prefill then one-step decode, from float state and from the SECDED state
    store, reproduces the full forward's logits;
  * the fused ``ecc_ssd_step_2d`` kernel matches a NumPy oracle, corrects
    and counts single-bit words, counts double-bit words as detected, and
    leaves idle lanes' planes as they were;
  * the engine refuses what the state store does not cover, and the
    configs that keep unprotected state stay refused;
  * every decode block size runs in one compiled program;
  * wide weight planes are padded once at build time.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import codes
from repro.configs import get_smoke_config, shapes
from repro.core import statestore
from repro.kernels import ecc_ssd
from repro.kernels import ops as kops
from repro.models import lm, mamba2
from repro.models.base import ModelConfig
from repro.serving.engine import (
    ReliabilityConfig,
    ReliabilityConfigError,
    ServingEngine,
    protect_params_inline,
)

CFG = ModelConfig(
    name="granite-toy", family="hybrid", n_layers=4, d_model=64, n_heads=4,
    n_kv_heads=2, head_dim=16, d_ff=128, vocab=97, attn_every=4,
    ssm_mixer="mamba2", ssm_head_dim=8, d_state=16, d_conv=4, ssm_expand=2,
    ssm_chunk=4, rope=False, attn_scale=1 / 16, norm_eps=1e-5, embed_mult=12.0,
    residual_mult=0.22, logits_div=8.0, tie_embeddings=True,
)


@pytest.fixture(scope="module")
def toy():
    params = lm.init_params(CFG, jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: a, params)
    # a_log 0 everywhere would give every head the same decay: spread them
    for j in range(CFG.period):
        blk = params["blocks"][f"p{j}"]
        if "mamba2" in blk:
            h = blk["mamba2"]["a_log"].shape[-1]
            blk["mamba2"]["a_log"] = jnp.log(jnp.linspace(1.0, 16.0, h))[None]
            blk["mamba2"]["dt_bias"] = jnp.full((1, h), -3.0)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 11), 0, CFG.vocab)
    return params, toks, lm.sequence_logits(params, toks, CFG)


def test_layer_pattern_and_state_positions():
    kinds = [CFG.layer_kind(j)["mixer"] for j in range(CFG.period)]
    assert kinds == ["mamba2", "mamba2", "attn", "mamba2"]
    assert statestore.positions(CFG) == (0, 1, 3)
    assert all(CFG.layer_kind(j)["ffn"] == "mlp" for j in range(CFG.period))


def test_chunked_ssd_matches_the_sequential_recurrence():
    b, s, h, p, n = 2, 11, 3, 4, 5
    k = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(k[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, s, h)))
    a = -jnp.exp(jax.random.normal(k[2], (h,)))
    bm, cm = jax.random.normal(k[3], (b, s, n)), jax.random.normal(k[4], (b, s, n))
    y, h_fin = mamba2.ssd_chunked(x, dt, a, bm, cm, chunk=4)
    state, ys = jnp.zeros((b, h, p, n)), []
    for t in range(s):
        state = (jnp.exp(dt[:, t] * a)[..., None, None] * state
                 + (dt[:, t, :, None] * x[:, t])[..., None] * bm[:, t, None, None, :])
        ys.append(jnp.einsum("bhpn,bn->bhp", state, cm[:, t]))
    np.testing.assert_allclose(y, jnp.stack(ys, 1), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(h_fin, state, rtol=1e-5, atol=1e-5)


def test_prefill_then_decode_matches_the_full_forward(toy):
    params, toks, full = toy
    cache = lm.init_cache(CFG, 2, 32)
    logits, cache = lm.prefill(params, toks[:, :6], CFG, cache)
    np.testing.assert_allclose(logits, full[:, 5], atol=1e-5)
    for t in range(6, 11):
        logits, cache = lm.decode_step(params, toks[:, t : t + 1], CFG, cache, t)
        np.testing.assert_allclose(logits, full[:, t], atol=1e-5)


def test_protected_decode_matches_the_full_forward(toy):
    """The same rollout with the state only in SECDED planes between steps:
    the prefill's float state is committed to lane slots 1 and 0 (swapped,
    so the slot is the lane's and not the row's), then decoded in place."""
    params, toks, full = toy
    cachem = lm.init_cache(CFG, 2, 32)
    _, cachem = lm.prefill(params, toks[:, :6], CFG, cachem)
    lanes = statestore.seal(lm.init_cache(CFG, 2, 32), CFG)
    lanes = statestore.commit(lanes, cachem, jnp.array([1, 0]), cfg=CFG)
    lanes["p2"] = jax.tree.map(lambda c: c[:, ::-1], cachem["p2"])
    assert set(lanes["p0"]) == set(statestore.PLANES)
    lanes = statestore.arm(lanes, jnp.array([1, 1]), CFG)
    for t in range(6, 11):
        logits, lanes = lm.decode_step(params, toks[::-1, t : t + 1], CFG, lanes, t)
        np.testing.assert_allclose(logits[::-1], full[:, t], atol=1e-5)
    lanes, counts = statestore.harvest(lanes, CFG)
    assert set(lanes["p0"]) == set(statestore.PLANES)
    # every live word read once a step, all clean
    assert counts.tolist() == [[5 * statestore.words_per_lane(CFG), 0, 0]] * 2


def _flip(planes, mask_lo, mask_hi):
    lo, hi, par = planes
    return lo ^ mask_lo, hi ^ mask_hi, par


def test_ecc_ssd_kernel_matches_numpy_and_counts_flips():
    lanes, h, half, n = 3, 4, 8, 128
    rng = np.random.default_rng(3)
    state = rng.normal(size=(lanes, h, 2 * half, n)).astype(np.float32)
    code = codes.get("secded72")
    words = state.view(np.uint32)
    lo, hi = words[:, :, :half], words[:, :, half:]
    par = code.encode_np(lo, hi)
    # lane 0: one flipped bit in every word of head 1 (corrected);
    # lane 1: two flipped bits in one word (detected); lane 2: idle
    mlo = np.zeros_like(lo)
    mhi = np.zeros_like(hi)
    mlo[0, 1] = np.uint32(1) << rng.integers(0, 32, size=(half, n)).astype(np.uint32)
    mlo[1, 2, 3, 5] = 0b11
    da = rng.uniform(0.5, 1.0, size=(lanes, h)).astype(np.float32)
    u = rng.normal(size=(lanes, h, 2 * half)).astype(np.float32)
    bm = rng.normal(size=(lanes, n)).astype(np.float32)
    cm = rng.normal(size=(lanes, n)).astype(np.float32)
    live = np.array([1, 1, 0], np.int32)
    y, nlo, nhi, npar, cnt = ecc_ssd.ecc_ssd_step(
        jnp.asarray(lo ^ mlo), jnp.asarray(hi ^ mhi), jnp.asarray(par),
        jnp.asarray(da), jnp.asarray(u), jnp.asarray(bm), jnp.asarray(cm),
        jnp.asarray(live), interpret=True,
    )
    # NumPy oracle on the corrected state (the double-bit word decodes as stored)
    dlo, dhi, status = code.decode_np(lo ^ mlo, hi ^ mhi, par)
    h_old = np.concatenate([dlo, dhi], axis=2).view(np.float32)
    h_new = da[..., None, None] * h_old + u[..., None] * bm[:, None, None, :]
    want_y = (h_new * cm[:, None, None, :]).sum(-1)
    np.testing.assert_allclose(np.asarray(y)[:2], want_y[:2], rtol=1e-5, atol=1e-4)
    got = np.concatenate([np.asarray(nlo), np.asarray(nhi)], axis=2).view(np.float32)
    np.testing.assert_allclose(got[:2], h_new[:2], rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(npar)[:2], code.encode_np(np.asarray(nlo), np.asarray(nhi))[:2])
    # idle lane: planes exactly as stored, nothing counted
    np.testing.assert_array_equal(np.asarray(nlo)[2], lo[2])
    np.testing.assert_array_equal(np.asarray(npar)[2], par[2])
    per_lane = h * half * n
    assert np.asarray(cnt).tolist() == [
        [per_lane - half * n, half * n, 0],
        [per_lane - 1, 0, 1],
        [0, 0, 0],
    ]
    assert int((status[1] == 2).sum()) == 1


def test_supports_paged_kv_admits_mamba2_hybrids_only():
    assert shapes.supports_paged_kv(CFG) and shapes.has_state_layers(CFG)
    jamba = get_smoke_config("jamba-1.5-large-398b")  # Mamba-1 state: unprotected
    assert not shapes.supports_paged_kv(jamba)
    assert not shapes.supports_paged_kv(get_smoke_config("rwkv6-3b"))
    assert not shapes.supports_paged_kv(get_smoke_config("mixtral-8x22b"))  # SWA
    assert not shapes.supports_paged_kv(dataclasses.replace(CFG, kv_quant=True))
    assert not shapes.has_state_layers(get_smoke_config("qwen3-0.6b"))


@pytest.fixture(scope="module")
def engine(toy):
    rel = ReliabilityConfig(platform="vc707", ecc=True, voltage=1.0, mode="inline")
    return ServingEngine(CFG, toy[0], rel, max_len=32)


@pytest.mark.parametrize("kw", [{"share_prefix": True}, {"speculative": 2}])
def test_engine_refuses_what_the_state_store_does_not_cover(engine, toy, kw):
    reqs = [(np.arange(1, 9, dtype=np.int32), 4)]
    if "speculative" in kw:
        kw = dict(kw, draft_params=toy[0], draft_cfg=CFG)
    with pytest.raises(ReliabilityConfigError, match="state store"):
        engine.serve(reqs, n_lanes=2, **kw)


def test_every_decode_block_size_runs_one_program(engine):
    """Outputs of 3, 6 and 9 tokens over 2 lanes make blocks of 1, 2, 4 and
    8 steps; a model with state layers runs them all in one program."""
    reqs = [(np.arange(1, 6, dtype=np.int32) + i, n) for i, n in enumerate((3, 6, 9))]
    rep = engine.serve(reqs, n_lanes=2, scrub_interval=8)
    assert [len(rep.outputs[i]) for i in range(3)] == [3, 6, 9]
    (helpers,) = engine._paged_helper_cache.values()
    assert helpers.multistep.__wrapped__._cache_size() == 1


def test_mamba2_projections_are_protected_in_the_ssm_domain(engine):
    blk = engine.params["blocks"]["p0"]["mamba2"]
    assert isinstance(blk["in_proj"], kops.EccWeight)
    assert isinstance(blk["out_proj"], kops.EccWeight)
    assert not isinstance(blk["norm"], kops.EccWeight)
    assert shapes.domain_of("['blocks']['p0']['mamba2']['in_proj']") == "ssm"
    assert shapes.domain_of("['blocks']['p0']['mamba']['in_proj']") == "mlp"


@pytest.mark.parametrize("n", [64, 256, 384])
def test_wide_planes_are_padded_once_at_build_time(n):
    w = jax.random.normal(jax.random.PRNGKey(n), (64, n))
    ew = kops.pack_ecc_weights(w)
    assert ew.n == n and ew.lo.shape == (8, 512 if n == 384 else n)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 64))
    plain = kops.ecc_matmul(x, ew, fuse=False)
    np.testing.assert_allclose(kops.ecc_matmul(x, ew), plain, rtol=1e-5, atol=1e-5)
    assert plain.shape == (3, n)


def test_protect_filter_asks_the_domain_registry():
    params = {
        "embed": jnp.ones((128, 64)),
        "lm_head": jnp.ones((64, 128)),
        "blocks": {"p0": {"attn": {"wq": jnp.ones((1, 64, 64)), "q_norm": jnp.ones((8, 64))},
                          "ln1": {"gamma": jnp.ones((8, 64))}}},
    }
    out, fields = protect_params_inline(params, CFG, include_embed=True)
    assert sorted(fields) == ["['blocks']['p0']['attn']['wq']", "['embed']"]
    assert not isinstance(out["lm_head"], kops.EccWeight)
