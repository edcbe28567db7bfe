"""The engine's plane-arena voltage step and scan decode vs the per-leaf and
per-token oracles kept here: identical counters, planes and tokens."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.core.faultsim import FaultField
from repro.core.nn_accel import EccMLP
from repro.core.planestore import PlaneStore, leaf_seed
from repro.core.telemetry import FaultStats
from repro.core.voltage import PLATFORMS
from repro.kernels import ops as kops
from repro.models import lm
from repro.serving import scheduler as sched
from repro.serving.engine import (
    FaultModelConfig,
    ReliabilityConfig,
    ServingEngine,
    _decode_gather_table,
    protect_params_inline,
)


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("qwen3-0.6b")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    prompts = np.random.default_rng(0).integers(0, cfg.vocab, (2, 8)).astype(np.int32)
    return cfg, params, prompts


@pytest.fixture(scope="module")
def loop_generate(setup):
    """Per-token oracle of ``ServingEngine.generate``: one jitted
    ``lm.decode_step`` dispatch per token, at the same padded decode batch."""
    cfg = setup[0]
    prefill = jax.jit(lambda p, t, c: lm.prefill(p, t, cfg, c))
    decode = jax.jit(lambda p, t, c, pos: lm.decode_step(p, t, cfg, c, pos))

    def run(params, prompts, n_tokens, max_len=48):
        b, s0 = prompts.shape
        cache = lm.init_cache(cfg, b, max_len)
        logits, cache = prefill(params, jnp.asarray(prompts), cache)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        pad = sched.decode_rows(b) - b
        tok = jnp.pad(tok, ((0, pad), (0, 0)))
        cache = jax.tree.map(
            lambda c: jnp.pad(c, [(0, 0), (0, pad)] + [(0, 0)] * (c.ndim - 2)),
            cache,
        )
        outs = [tok]
        for i in range(n_tokens - 1):
            logits, cache = decode(params, tok, cache, s0 + i)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
            outs.append(tok)
        return np.concatenate([np.asarray(o) for o in outs], axis=1)[:b]

    return run


class PerLeafFaults:
    """Per-leaf oracle of the engine's inline voltage step: one host
    FaultField per EccWeight leaf (kept across voltage steps), one inject
    and one scrub launch per leaf."""

    def __init__(self, cfg, params, rel: ReliabilityConfig):
        self.rel = rel
        self.platform = PLATFORMS[rel.platform]
        protected, _ = protect_params_inline(
            params, cfg, seed=rel.seed, include_embed=rel.embed_protected
        )
        self.flat, self.treedef = jax.tree_util.tree_flatten_with_path(
            protected, is_leaf=lambda x: isinstance(x, kops.EccWeight)
        )
        self.fields: dict[str, FaultField] = {}

    def set_voltage(self, v: float):
        """Returns (faulty params, FaultStats) at rail voltage ``v``."""
        out = []
        agg = FaultStats()
        for path, leaf in self.flat:
            if not isinstance(leaf, kops.EccWeight):
                out.append(leaf)
                continue
            key = jax.tree_util.keystr(path)
            field = self.fields.get(key)
            if field is None:
                field = FaultField(
                    self.platform, leaf.lo.size, seed=leaf_seed(self.rel.seed, key)
                )
                self.fields[key] = field
            masks = field.masks(v)
            mlo = jnp.asarray(masks.lo.reshape(leaf.lo.shape))
            mhi = jnp.asarray(masks.hi.reshape(leaf.hi.shape))
            mpar = jnp.asarray(masks.parity.reshape(leaf.parity.shape))
            flo, fhi, fpar = kops.inject(leaf.lo, leaf.hi, leaf.parity, mlo, mhi, mpar)
            faulty = dataclasses.replace(leaf, lo=flo, hi=fhi, parity=fpar)
            if not self.rel.ecc:
                # no-ECC baseline: re-encode the faulty data so the decode is
                # a pass-through and the raw faulty bits reach the matmul
                faulty = dataclasses.replace(
                    faulty, parity=kops.encode(faulty.lo, faulty.hi)
                )
            status = np.asarray(kops.scrub(faulty))
            agg.accumulate(FaultStats.from_decode(status, masks.flip_counts()))
            out.append(_decode_gather_table(faulty) if "embed" in key else faulty)
        return jax.tree_util.tree_unflatten(self.treedef, out), agg


def _ecc_leaves(params):
    return [
        l
        for l in jax.tree_util.tree_leaves(
            params, is_leaf=lambda x: isinstance(x, kops.EccWeight)
        )
        if isinstance(l, kops.EccWeight)
    ]


@pytest.mark.parametrize("ecc", [True, False])
def test_engine_batched_identical_to_perleaf(setup, loop_generate, ecc):
    cfg, params, prompts = setup
    rel = ReliabilityConfig(platform="vc707", ecc=ecc, voltage=0.55, mode="inline")
    eng_b = ServingEngine(cfg, params, rel=rel, max_len=48)
    ref_params, ref_stats = PerLeafFaults(cfg, params, rel).set_voltage(0.55)
    assert np.array_equal(eng_b.stats.counters(), ref_stats.counters())
    assert eng_b.stats.words == ref_stats.words
    leaves_b, leaves_p = _ecc_leaves(eng_b.params), _ecc_leaves(ref_params)
    assert len(leaves_b) == len(leaves_p) > 0
    for lb, lp in zip(leaves_b, leaves_p):
        assert np.array_equal(np.asarray(lb.lo), np.asarray(lp.lo))
        assert np.array_equal(np.asarray(lb.hi), np.asarray(lp.hi))
        assert np.array_equal(np.asarray(lb.parity), np.asarray(lp.parity))
    np.testing.assert_array_equal(
        eng_b.generate(prompts, 6), loop_generate(ref_params, prompts, 6)
    )


def test_engine_batched_identity_across_voltage_walk(setup):
    """The paths stay identical when the rail moves (field reuse, not rebuild)."""
    cfg, params, _ = setup
    rel = ReliabilityConfig(platform="vc707", ecc=True, voltage=0.57, mode="inline")
    eng_b = ServingEngine(cfg, params, rel=rel, max_len=48)
    ref = PerLeafFaults(cfg, params, rel)
    ref.set_voltage(0.57)
    for v in (0.56, 0.54, 0.56):  # down, crash-adjacent, back up
        eng_b.set_voltage(v)
        _, ref_stats = ref.set_voltage(v)
        assert np.array_equal(
            eng_b._last_scrub.counters(), ref_stats.counters()
        ), v


def test_scan_generate_matches_python_loop(setup, loop_generate):
    cfg, params, prompts = setup
    eng = ServingEngine(cfg, params, rel=None, max_len=48)
    ref = loop_generate(params, prompts, 8)
    np.testing.assert_array_equal(eng.generate(prompts, 8), ref)
    # degenerate rollouts
    np.testing.assert_array_equal(eng.generate(prompts, 1), ref[:, :1])


def test_device_mask_source_serves(setup):
    cfg, params, prompts = setup
    rel = ReliabilityConfig(
        platform="vc707", ecc=True, voltage=0.55, mode="inline",
        fault_model=FaultModelConfig(mask_source="device"),
    )
    eng = ServingEngine(cfg, params, rel=rel, max_len=48)
    assert eng.stats.words == eng._store.n_words > 0
    assert eng.stats.faulty_bits > 0  # 0.55 V is well below the guardband
    out = eng.generate(prompts, 4)
    assert out.shape == (2, 4)


def test_eccmlp_batched_identical_to_perleaf():
    mlp = EccMLP((64, 32, 10), platform="vc707", seed=3)
    mlp.store()
    for v, ecc in ((0.56, True), (0.55, False), (0.54, True)):
        mlp.set_voltage(v, ecc=ecc, batched=False)
        ref_stats = mlp.stats.counters()
        ref_planes = [
            (np.asarray(l.faulty.lo), np.asarray(l.faulty.hi), np.asarray(l.faulty.parity))
            for l in mlp.layers
        ]
        mlp.set_voltage(v, ecc=ecc, batched=True)
        assert np.array_equal(mlp.stats.counters(), ref_stats), (v, ecc)
        for l, (rlo, rhi, rpar) in zip(mlp.layers, ref_planes):
            assert np.array_equal(np.asarray(l.faulty.lo), rlo)
            assert np.array_equal(np.asarray(l.faulty.hi), rhi)
            assert np.array_equal(np.asarray(l.faulty.parity), rpar)


def test_planestore_empty():
    store = PlaneStore([], [], PLATFORMS["vc707"], seed=0)
    leaves, stats = store.set_voltage(0.54)
    assert leaves == [] and stats.words == 0
