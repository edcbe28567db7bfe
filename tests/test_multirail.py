"""Multi-rail undervolting: memory domains, per-domain ECC counters,
MultiRailController convergence, and the vmapped sweep harness."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import shapes
from repro.core import (
    MultiRailController,
    PLATFORMS,
    UndervoltController,
    ecc,
    sweep,
)
from repro.core.faultsim import DeviceFaultField, _popcount32
from repro.core.planestore import PlaneStore
from repro.core.telemetry import DomainFaultStats, FaultStats
from repro.core.voltage import (
    bram_power,
    derive_domain_profiles,
    multi_rail_bram_power,
    multi_rail_power_saving,
)
from repro.kernels import ops, ref
from _hypothesis_compat import given, settings, st


# -- domain registry ----------------------------------------------------------
def test_domain_classifier():
    assert shapes.domain_of("['embed']") == "embedding"
    assert shapes.domain_of("['blocks']['p0']['attn']['wq']") == "attention"
    assert shapes.domain_of("['blocks']['p0']['mlp']['w1']") == "mlp"
    assert shapes.domain_of("['kv_cache']['k']") == "kv"
    assert shapes.domain_of("['whatever']") == "mlp"  # default bucket
    for d in ("embedding", "attention", "mlp", "kv"):
        assert d in shapes.MEMORY_DOMAINS


# -- per-domain counter kernel ------------------------------------------------
def test_domain_counters_match_reference(rng):
    n = 3000
    lo = jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint32))
    hi = jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint32))
    par = ops.encode(lo, hi)
    mlo = rng.integers(0, 2**32, n, dtype=np.uint32)
    for _ in range(4):
        mlo &= rng.integers(0, 2**32, n, dtype=np.uint32)
    mhi = np.zeros(n, np.uint32)
    mpar = np.zeros(n, np.uint8)
    dom = rng.integers(0, 3, n).astype(np.int32)

    flo, _, _, cnt = ops.inject_scrub_domains(
        lo, hi, par, jnp.asarray(mlo), jnp.asarray(mhi), jnp.asarray(mpar),
        jnp.asarray(dom), 3,
    )
    cnt = np.asarray(cnt)
    # each domain row equals the separate-pass oracle on that domain's words
    for d in range(3):
        idx = dom == d
        *_, rcnt = ref.inject_scrub_ref(
            np.asarray(lo)[idx], np.asarray(hi)[idx], np.asarray(par)[idx],
            mlo[idx], mhi[idx], mpar[idx],
        )
        assert np.array_equal(cnt[d], rcnt)
    # rows sum to the single-rail fused kernel's counters; planes identical
    slo, _, _, c1 = ops.inject_scrub(
        lo, hi, par, jnp.asarray(mlo), jnp.asarray(mhi), jnp.asarray(mpar)
    )
    assert np.array_equal(cnt.sum(0), np.asarray(c1))
    assert np.array_equal(np.asarray(flo), np.asarray(slo))


# -- plane store rails --------------------------------------------------------
def _toy_store(mask_source, seed=3, profiles=None):
    rng = np.random.default_rng(7)
    leaves = [
        ops.pack_ecc_weights(jnp.asarray(rng.standard_normal((64, 96)), jnp.float32))
        for _ in range(4)
    ]
    keys = ["a_attn", "b_mlp", "c_attn", "d_embed"]
    return PlaneStore(
        leaves, keys, PLATFORMS["vc707"], seed=seed, mask_source=mask_source,
        domain_key=shapes.domain_of, profiles=profiles,
    ), leaves


@pytest.mark.parametrize("mask_source", ["host", "device"])
def test_set_rails_uniform_is_bit_identical_to_set_voltage(mask_source):
    store, leaves = _toy_store(mask_source)
    flat, _ = _toy_store(mask_source)  # fresh store: set_voltage consumes masks
    lv1, st1 = flat.set_voltage(0.55)
    lv2, st2 = store.set_rails({d: 0.55 for d in store.domains})
    for a, b in zip(lv1, lv2):
        assert np.array_equal(np.asarray(a.lo), np.asarray(b.lo))
        assert np.array_equal(np.asarray(a.hi), np.asarray(b.hi))
        assert np.array_equal(np.asarray(a.parity), np.asarray(b.parity))
    assert st1.counters().tolist() == st2.total().counters().tolist()
    assert sum(store.words_by_domain().values()) == store.n_words


@pytest.mark.parametrize("mask_source", ["host", "device"])
def test_set_rails_faults_stay_in_their_domain(mask_source):
    store, _ = _toy_store(mask_source)
    _, st = store.set_rails({"attention": 1.0, "mlp": 0.54, "embedding": 1.0})
    assert st["attention"].faulty_bits == 0
    assert st["embedding"].faulty_bits == 0
    assert st["mlp"].faulty_bits > 0
    assert st["mlp"].words == store.words_by_domain()["mlp"]


@pytest.mark.parametrize("mask_source", ["host", "device"])
def test_set_rails_uniform_matches_set_voltage_with_domain_profiles(mask_source):
    """The bit-identity invariant must also hold when domains carry their
    own fault curves (the scalar path has to consult per-word rates then)."""
    profs = derive_domain_profiles(
        PLATFORMS["vc707"], shapes.MEMORY_DOMAINS, spread=0.5, seed=1
    )
    s1, _ = _toy_store(mask_source, profiles=profs)
    s2, _ = _toy_store(mask_source, profiles=profs)
    lv1, st1 = s1.set_voltage(0.55)
    lv2, st2 = s2.set_rails({d: 0.55 for d in s2.domains})
    for a, b in zip(lv1, lv2):
        assert np.array_equal(np.asarray(a.lo), np.asarray(b.lo))
    assert st1.counters().tolist() == st2.total().counters().tolist()


def test_derived_domain_profiles_vary_rates_not_envelope():
    base = PLATFORMS["vc707"]
    profs = derive_domain_profiles(base, shapes.MEMORY_DOMAINS, spread=0.5, seed=0)
    again = derive_domain_profiles(base, shapes.MEMORY_DOMAINS, spread=0.5, seed=0)
    assert {d: p.rate_crash for d, p in profs.items()} == {
        d: p.rate_crash for d, p in again.items()
    }  # deterministic in (seed, domain)
    rates = [p.rate_crash for p in profs.values()]
    assert len(set(rates)) == len(rates)  # domains actually differ
    for p in profs.values():
        assert (p.v_min, p.v_crash) == (base.v_min, base.v_crash)


# -- controller ---------------------------------------------------------------
def _stats(detected=0, silent=0):
    return FaultStats(words=100, detected=detected, silent=silent)


def test_multirail_trips_are_independent():
    ctrl = MultiRailController(
        PLATFORMS["vc707"], ("attention", "mlp"), step_v=0.01, start_v=0.60
    )
    # attention sees a DED, mlp stays clean
    volts = ctrl.update({"attention": _stats(detected=1), "mlp": _stats()})
    assert ctrl.rails["attention"].locked
    assert not ctrl.rails["mlp"].locked
    v_att = volts["attention"]
    for _ in range(3):
        volts = ctrl.update({"attention": _stats(), "mlp": _stats()})
    assert volts["attention"] == v_att  # locked rail holds
    assert volts["mlp"] < v_att - 0.02  # free rail keeps descending
    assert not ctrl.locked
    ctrl.update({"attention": _stats(), "mlp": _stats(detected=2)})
    assert ctrl.locked


def test_multirail_paranoid_trips_on_silent():
    relaxed = MultiRailController(PLATFORMS["vc707"], ("mlp",), start_v=0.60)
    paranoid = MultiRailController(
        PLATFORMS["vc707"], ("mlp",), paranoid=True, start_v=0.60
    )
    stats = {"mlp": _stats(silent=1)}
    relaxed.update(stats)
    paranoid.update(stats)
    assert not relaxed.rails["mlp"].locked
    assert paranoid.rails["mlp"].locked
    assert paranoid.rails["mlp"].history[-1].action == "trip+backoff"


def test_multirail_missing_domain_telemetry_holds_rail():
    ctrl = MultiRailController(PLATFORMS["vc707"], ("a", "b"), start_v=0.60)
    v0 = ctrl.voltages["b"]
    ctrl.update({"a": _stats()})  # no telemetry for b this interval
    assert ctrl.voltages["b"] == v0
    assert ctrl.voltages["a"] < v0


@settings(max_examples=25, deadline=None)
@given(
    st.integers(min_value=0, max_value=2**32 - 1),
    st.floats(min_value=0.56, max_value=1.0),
    st.sampled_from([1, 2, 3]),
)
def test_rail_walk_monotone_until_trip_then_locked(seed, start_v, backoff):
    """Property: every rail's voltage is non-increasing until its trip, never
    leaves [v_crash, v_nom], and is constant once locked (backoff included)."""
    prof = PLATFORMS["vc707"]
    rng = np.random.default_rng(seed)
    ctrl = MultiRailController(
        prof, ("a", "b", "c"), step_v=0.01, backoff_steps=backoff, start_v=start_v
    )
    seen = {d: [ctrl.voltages[d]] for d in ctrl.domains}
    for _ in range(40):
        stats = {
            d: _stats(detected=int(rng.random() < 0.15)) for d in ctrl.domains
        }
        volts = ctrl.update(stats)
        for d in ctrl.domains:
            seen[d].append(volts[d])
        if ctrl.locked:
            break
    for d in ctrl.domains:
        vs = seen[d]
        c = ctrl.rails[d]
        assert all(prof.v_crash <= v <= prof.v_nom for v in vs)
        tripped = [i for i, r in enumerate(c.history) if r.action == "trip+backoff"]
        upto = tripped[0] + 1 if tripped else len(vs) - 1
        # non-increasing descent strictly before the trip step
        assert all(vs[i + 1] <= vs[i] + 1e-12 for i in range(max(upto - 1, 0)))
        if tripped:
            # backoff is bounded and the rail never moves again
            assert vs[upto] <= vs[upto - 1] + backoff * c.step_v + 1e-12
            assert all(v == vs[upto] for v in vs[upto:])
            assert c.locked


# -- telemetry contract -------------------------------------------------------
def test_faultstats_accumulate_contract():
    a = FaultStats(words=1, corrected=2)
    b = FaultStats(words=3, corrected=5)
    assert a.accumulate(b) is None  # explicitly in-place, no alias to return
    assert (a.words, a.corrected) == (4, 7)
    assert (b.words, b.corrected) == (3, 5)  # other side untouched
    pure = FaultStats.summed([a, b])
    assert pure.words == 7 and a.words == 4  # inputs untouched
    d = DomainFaultStats({"x": FaultStats(words=2, detected=1)})
    d.accumulate(DomainFaultStats({"x": FaultStats(words=1), "y": FaultStats(silent=3)}))
    assert d["x"].words == 3 and d["y"].silent == 3
    tot = d.total()
    tot.accumulate(FaultStats(words=100))
    assert d["x"].words == 3  # total() is a fresh instance, not a view


# -- vmapped sweep ------------------------------------------------------------
def test_vmapped_sweep_matches_per_voltage_loop():
    """The vmapped grid equals the per-voltage device loop bit-for-bit, in
    fewer compiled dispatches, and tracks the host-oracle curve."""
    n = 1 << 16
    voltages = [0.56, 0.55, 0.54]
    grid = [(p, v) for p in PLATFORMS.values() for v in voltages]
    sweep.reset_dispatch_count()
    pts = sweep.sweep_platform_grid(grid, n, seed=11)
    vmapped_dispatches = sweep.dispatch_count()

    loop_dispatches = 0
    for (prof, v), pt in zip(grid, pts):
        dev = DeviceFaultField(prof, n, seed=11)
        mlo, mhi, mpar = dev.masks(v)
        loop_dispatches += 1
        _, _, status = ecc.decode(mlo, mhi, mpar)
        flips = (
            _popcount32(np.asarray(mlo))
            + _popcount32(np.asarray(mhi))
            + _popcount32(np.asarray(mpar).astype(np.uint32))
        )
        st = FaultStats.from_decode(np.asarray(status), flips)
        assert pt.stats.counters().tolist() == st.counters().tolist(), (prof.name, v)
    assert vmapped_dispatches < loop_dispatches

    # statistical agreement with the host-oracle loop (different PRNG stream)
    from benchmarks.fig1_fault_rate import _stats_at
    from repro.core.faultsim import FaultField

    host = FaultField(PLATFORMS["vc707"], n, seed=11)
    for v in voltages:
        h = _stats_at(host, v)
        d = next(
            p.stats for (pr, pv), p in zip(grid, pts)
            if pr.name == "vc707" and pv == v
        )
        assert h.faulty_bits > 50
        assert 0.5 < d.faulty_bits / h.faulty_bits < 2.0, (v, h, d)


def test_schedule_sweep_matches_planestore_device_path():
    """sweep_rail_schedules on the store's geometry reproduces the store's
    own device-path telemetry exactly (same stream, same thresholds)."""
    store, _ = _toy_store("device", seed=5)
    volts = {"attention": 0.55, "mlp": 0.56, "embedding": 0.54}
    _, st_store = store.set_rails(volts)
    res = sweep.sweep_rail_schedules(
        [volts], store.domains, store._dom_ids_np, PLATFORMS["vc707"], seed=5
    )[0]
    for d in store.domains:
        assert (
            st_store[d].counters().tolist() == res[d].counters().tolist()
        ), d


# -- serving engine end-to-end ------------------------------------------------
@pytest.fixture(scope="module")
def lm_setup():
    from repro.configs import get_smoke_config
    from repro.models import lm

    cfg = get_smoke_config("qwen3-0.6b")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    prompts = np.random.default_rng(0).integers(0, cfg.vocab, (2, 6)).astype(np.int32)
    return cfg, params, prompts


def test_engine_multirail_beats_single_rail_and_is_clean_at_nominal(lm_setup):
    """Acceptance: per-domain autotune locks at least one domain below the
    global single-rail lock, saves at least as much power, and the multi-rail
    machinery is bit-invisible at nominal voltage."""
    from repro.serving.engine import (
        ProtectionConfig,
        RailsConfig,
        ReliabilityConfig,
        ServingEngine,
    )

    cfg, params, prompts = lm_setup
    single = ServingEngine(
        cfg, params,
        rel=ReliabilityConfig(
            platform="vc707", ecc=True, voltage=1.0, mode="inline",
            rails=RailsConfig(start_v=0.62),
            protection=ProtectionConfig(embed=True),
        ),
        max_len=32,
    )
    v_single, _ = single.autotune_voltage()
    saving_single = single.power_report()["saving_vs_nominal"]

    multi = ServingEngine(
        cfg, params,
        rel=ReliabilityConfig(
            platform="vc707", ecc=True, voltage=1.0, mode="inline",
            rails=RailsConfig(multi_rail=True, start_v=0.62),
        ),
        max_len=32,
    )
    volts, history = multi.autotune_voltage()
    assert multi.controller.locked
    assert set(volts) == set(multi._store.domains)
    prof = PLATFORMS["vc707"]
    assert all(prof.v_crash <= v <= prof.v_min for v in volts.values())
    # every rail's own telemetry drove its lock: per-domain histories exist
    assert all(len(history[d]) > 0 for d in volts)

    # (a) at least one domain locks below the global single-rail lock
    assert any(v < v_single - 1e-9 for v in volts.values()), (volts, v_single)
    # (b) total power saving dominates the single-rail baseline
    report = multi.power_report()
    assert report["saving_vs_nominal"] >= saving_single - 1e-12
    assert report["bram_w"] <= bram_power(v_single, ecc=True) + 1e-12
    # (c) nominal schedule is bit-identical to the single-rail engine
    multi.set_rails({d: 1.0 for d in multi._store.domains})
    single.set_voltage(1.0)
    out_m = multi.generate(prompts, 8)
    out_s = single.generate(prompts, 8)
    np.testing.assert_array_equal(out_m, out_s)


def test_engine_multirail_generates_under_locked_schedule(lm_setup):
    from repro.serving.engine import (
        FaultModelConfig,
        RailsConfig,
        ReliabilityConfig,
        ServingEngine,
    )

    cfg, params, prompts = lm_setup
    eng = ServingEngine(
        cfg, params,
        rel=ReliabilityConfig(
            platform="vc707", mode="inline",
            fault_model=FaultModelConfig(mask_source="device"),
            rails=RailsConfig(multi_rail=True, start_v=0.62),
        ),
        max_len=32,
    )
    volts, _ = eng.autotune_voltage()
    out = eng.generate(prompts, 6)
    assert out.shape == (2, 6)
    # the locked schedule was DED-free on its final scrub
    assert all(eng._last_scrub[d].detected == 0 for d in eng._store.domains)
    # cumulative per-domain telemetry accounts every scrubbed word
    assert eng.rail_stats.total().words == eng.stats.words


# -- trainer integration ------------------------------------------------------
def test_trainer_rail_policy_is_read_only_and_walks_rails():
    import tempfile

    from repro.data.pipeline import DataConfig, TokenPipeline
    from repro.optim.adamw import AdamWConfig
    from repro.train.train_step import TrainConfig
    from repro.train.trainer import RailPolicy, Trainer
    from conftest import tiny_cfg

    cfg = tiny_cfg(vocab=64)
    dc = DataConfig(vocab=64, global_batch=8, seq_len=32)
    tc = TrainConfig(
        optimizer=AdamWConfig(lr=1e-3, warmup_steps=5, total_steps=100), remat=None
    )
    with tempfile.TemporaryDirectory() as d:
        plain = Trainer(cfg, tc, TokenPipeline(dc), d, ckpt_every=100)
        h0 = plain.run(4)
    with tempfile.TemporaryDirectory() as d:
        railed = Trainer(
            cfg, tc, TokenPipeline(dc), d, ckpt_every=100,
            rails=RailPolicy(scrub_every=2, start_v=0.60),
        )
        h1 = railed.run(4)
    events = [r for r in railed.history if r.get("event") == "rails"]
    assert len(events) == 2  # steps 2 and 4
    assert events[0]["voltages"]["mlp"] == pytest.approx(0.60)
    assert events[1]["voltages"]["mlp"] < 0.60  # the walk is live
    assert set(events[0]["voltages"]) >= {"attention", "mlp", "embedding"}
    # scrubbing is a read path: training is bitwise unaffected
    np.testing.assert_array_equal(
        [r["loss"] for r in h0 if "loss" in r],
        [r["loss"] for r in h1 if "loss" in r],
    )


# -- power accounting ---------------------------------------------------------
def test_multi_rail_power_dominates_single_rail():
    words = {"attention": 1000, "mlp": 3000, "embedding": 500}
    single = {d: 0.56 for d in words}
    hetero = {"attention": 0.55, "mlp": 0.56, "embedding": 0.54}
    p_single = multi_rail_bram_power(single, words)
    assert p_single == pytest.approx(bram_power(0.56, ecc=True), rel=1e-12)
    assert multi_rail_bram_power(hetero, words) < p_single
    assert multi_rail_power_saving(hetero, words) > multi_rail_power_saving(
        single, words
    )
