"""Accuracy campaign: divergence scorers, harness, and canary rail retreat.

DESIGN.md §15. Three layers:
  * scorer fixtures — hand-computed greedy-match / KL / perplexity values,
    exact-zero invariance on clean-vs-clean;
  * the tiny-config campaign — nominal rows bit-identical, divergence
    monotone as the rail descends, ileave88 holding zero strictly deeper
    than parity65 (the checked-in BENCH_accuracy.json's acceptance shape);
  * the accuracy canary — a rail retreat driven purely by canary divergence
    in a configuration where the DED counters never fire (ecc=False
    re-encodes parity over faulty data, so detection is structurally blind).
"""

import math
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from repro.core import campaign
from repro.core.controller import (
    EscalationPolicy,
    MultiRailController,
    UndervoltController,
)
from repro.core.sweep import campaign_voltage_grid
from repro.core.telemetry import FaultStats
from repro.core.voltage import PLATFORMS
from repro.serving.engine import (
    CanaryConfig,
    RailsConfig,
    ReliabilityConfig,
    ServingEngine,
)

VC707 = PLATFORMS["vc707"]


# ---------------------------------------------------------------------------
# Scorers: hand-computed fixtures + exact-zero invariance
# ---------------------------------------------------------------------------
def test_greedy_match_len_fixture():
    ref = np.array([[1, 2, 3, 4], [5, 6, 7, 8], [9, 9, 9, 9]])
    test = np.array([[1, 2, 9, 4], [5, 6, 7, 8], [0, 9, 9, 9]])
    assert campaign.greedy_match_len(ref, test).tolist() == [2, 4, 0]
    # a later re-match does not extend the prefix: row 0 scores 2, not 3
    assert campaign.token_divergence(ref, test) == pytest.approx(
        1.0 - (2 + 4 + 0) / 3 / 4
    )


def test_token_divergence_exact_zero_on_identity():
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 256, size=(4, 24))
    assert campaign.token_divergence(toks, toks.copy()) == 0.0  # exact


def test_logit_kl_fixture():
    # ref uniform over 2 classes, test = softmax([ln 3, 0]) = (3/4, 1/4):
    # KL = 0.5 ln(0.5/0.75) + 0.5 ln(0.5/0.25) = 0.5 ln(4/3)
    ref = np.zeros((1, 1, 2))
    test = np.array([[[math.log(3.0), 0.0]]])
    assert campaign.logit_kl(ref, test) == pytest.approx(
        0.5 * math.log(4.0 / 3.0), rel=1e-12
    )
    assert campaign.logit_kl(ref, ref.copy()) == 0.0  # exact
    # shift-invariance of the softmax: adding a constant changes nothing
    assert campaign.logit_kl(ref, ref + 7.0) == pytest.approx(0.0, abs=1e-12)


def test_perplexity_fixture():
    # uniform logits over V classes: NLL = ln V, perplexity = V
    v = 16
    logits = np.zeros((2, 3, v))
    tokens = np.arange(6).reshape(2, 3)
    assert campaign.token_nll(logits, tokens) == pytest.approx(math.log(v))
    assert campaign.perplexity(logits, tokens) == pytest.approx(float(v))


def test_label_divergence_fixture():
    assert campaign.label_divergence(
        np.array([1, 2, 3, 4]), np.array([1, 2, 0, 4])
    ) == 0.25
    assert campaign.label_divergence(np.array([1, 2]), np.array([1, 2])) == 0.0


def test_score_clean_vs_clean_is_exactly_zero():
    rng = np.random.default_rng(1)
    toks = rng.integers(0, 32, size=(2, 8))
    logits = rng.normal(size=(2, 8, 32))
    rep = campaign.score(toks, toks.copy(), logits, logits.copy(), toks)
    assert rep.divergence == 0.0
    assert rep.kl == 0.0
    assert rep.ppl_delta == 0.0
    assert rep.match_frac == 1.0
    assert rep.scorer_version == campaign.SCORER_VERSION


def test_eval_prompts_deterministic():
    a = campaign.eval_prompts(256, 4, 8, seed=3)
    b = campaign.eval_prompts(256, 4, 8, seed=3)
    assert a.shape == (4, 8) and a.dtype == np.int32
    assert (a == b).all()
    assert a.min() >= 0 and a.max() < 256
    assert not (a == campaign.eval_prompts(256, 4, 8, seed=4)).all()


def test_campaign_voltage_grid_vc707():
    grid = campaign_voltage_grid(VC707)
    assert grid == (1.0, 0.61, 0.59, 0.57, 0.55, 0.54)
    assert grid == tuple(sorted(grid, reverse=True))
    assert min(grid) == VC707.v_crash  # never below the crash rail


def test_campaign_model_names():
    tiny = campaign.campaign_model("tiny")
    assert tiny.name == "tiny"
    smoke = campaign.campaign_model("qwen2-7b-smoke")
    assert tiny.vocab == smoke.vocab and tiny.n_layers == smoke.n_layers


# ---------------------------------------------------------------------------
# Controller: divergence SLO as a trip signal
# ---------------------------------------------------------------------------
def test_acc_trip_retreats_with_zero_ded():
    """The canary acceptance property at the controller level: a rail backs
    off on divergence alone — every counter the DED canary watches is 0.
    (start_v warm starts clamp to the fault-free guardband edge v_min.)"""
    c = UndervoltController(VC707, start_v=VC707.v_min, divergence_slo=0.05)
    clean = FaultStats(words=1000)
    assert c.update(clean, divergence=0.0) == pytest.approx(VC707.v_min - 0.01)
    v = c.update(clean, divergence=0.4)  # counters still silent
    assert c.locked and v == pytest.approx(VC707.v_min)
    assert [h.action for h in c.history] == ["lower", "acc+backoff"]
    assert all(h.detected == 0 for h in c.history)
    assert c.history[-1].divergence == pytest.approx(0.4)


def test_divergence_ignored_without_slo():
    c = UndervoltController(VC707, start_v=0.58)
    c.update(FaultStats(words=1000), divergence=0.9)
    assert not c.locked
    assert c.history[-1].action == "lower"
    assert c.history[-1].divergence == pytest.approx(0.9)  # recorded anyway


def test_acc_trip_escalates_codec_before_retreating():
    """With ladder steps left, an SLO violation steps the code up (voltage
    holds); once exhausted, the next violation retreats — the policy trades
    check-bit overhead against the divergence SLO."""
    c = UndervoltController(
        VC707, start_v=0.57, divergence_slo=0.1,
        escalation=EscalationPolicy(ladder=("secded72", "dected79")),
    )
    v0 = c.voltage
    c.update(FaultStats(words=1000), divergence=0.5)
    assert c.history[-1].action == "escalate"
    assert c.codec == "dected79" and c.pop_codec_change() == "dected79"
    assert not c.locked and c.voltage == pytest.approx(v0)
    c.update(FaultStats(words=1000), divergence=0.5)  # ladder exhausted
    assert c.history[-1].action == "acc+backoff" and c.locked


def test_multirail_broadcasts_scalar_divergence():
    """Canary divergence is whole-model: a scalar retreats every rail; a
    {domain: score} dict trips only the attributed rails."""
    stats = {"attn": FaultStats(words=100), "mlp": FaultStats(words=100)}
    m = MultiRailController(VC707, ("attn", "mlp"), divergence_slo=0.1)
    m.update(stats, divergence=0.5)
    assert all(
        c.locked and c.history[-1].action == "acc+backoff"
        for c in m.rails.values()
    )
    m2 = MultiRailController(VC707, ("attn", "mlp"), divergence_slo=0.1)
    m2.update(stats, divergence={"mlp": 0.5})
    assert m2.rails["mlp"].locked and not m2.rails["attn"].locked


# ---------------------------------------------------------------------------
# The tiny-config campaign (module-scoped: one compile set for the file)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def campaign_rows():
    spec = campaign.CampaignSpec(
        codecs=("parity65", "ileave88"),
        voltages=(1.0, 0.57, 0.55, 0.54),
        n_prompts=2,
        n_tokens=12,
        proxy_words=0,
    )
    return campaign.run_campaign(spec)


def test_campaign_nominal_rows_exactly_clean(campaign_rows):
    nominal = [r for r in campaign_rows if r["nominal"]]
    assert nominal, "grid must include the nominal anchor"
    for r in nominal:
        assert r["divergence"] == 0.0 and r["kl"] == 0.0
        assert r["ppl_delta"] == 0.0 and r["faulty_words"] == 0


def test_campaign_divergence_monotone_under_fault_rate(campaign_rows):
    """Monotonicity under increasing injected fault rate: descending the
    rail strictly grows the injected damage (faulty_words, deterministic in
    the seed), and the zero-divergence region is a contiguous prefix from
    nominal — once a codec's output diverges it never recovers to exactly
    zero at a deeper point. (The raw prefix-length score itself saturates
    noisily once rollouts fully diverge, so point-wise ordering below the
    first divergence is not a property; the zero/nonzero boundary is.)"""
    for codec in ("parity65", "ileave88"):
        by_v = sorted(
            (r["voltage"], r["divergence"], r["faulty_words"])
            for r in campaign_rows
            if r["codec"] == codec
        )
        faults = [f for _, _, f in by_v]  # ascending voltage: deep -> nominal
        assert faults == sorted(faults, reverse=True), (codec, by_v)
        assert faults[0] > 0  # the deep end injects real damage
        first_zero = next(
            i for i, (_, d, _) in enumerate(by_v) if d == 0.0
        )
        assert all(d == 0.0 for _, d, _ in by_v[first_zero:]), (codec, by_v)
    deep_parity = [
        r for r in campaign_rows
        if r["codec"] == "parity65" and r["voltage"] == 0.54
    ][0]
    assert deep_parity["divergence"] > 0.0


def test_campaign_ileave88_holds_deeper_than_parity65(campaign_rows):
    """The paper-shaped codec ordering BENCH_accuracy.json is gated on:
    at 0.55 V the 4-way interleaved code still matches the clean rollout
    bit-for-bit while the detect-only code has already diverged."""
    at = {
        (r["codec"], r["voltage"]): r["divergence"] for r in campaign_rows
    }
    assert at[("ileave88", 0.55)] == 0.0
    assert at[("parity65", 0.55)] > 0.0

    def floor(codec):
        zero = [
            r["voltage"] for r in campaign_rows
            if r["codec"] == codec and r["divergence"] == 0.0
        ]
        return min(zero)

    assert floor("ileave88") < floor("parity65")


def test_campaign_rows_carry_contract_columns(campaign_rows):
    r = campaign_rows[0]
    for col in (
        "model", "arch", "platform", "codec", "voltage", "nominal",
        "divergence", "match_len", "kl", "ppl_delta", "scorer_version",
        "detected", "faulty_words", "bram_saving_vs_nominal", "seed",
    ):
        assert col in r, col
    assert r["scorer_version"] == campaign.SCORER_VERSION


# ---------------------------------------------------------------------------
# Accuracy canary in the serving engine
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_setup():
    import jax

    from repro.models import lm

    cfg = campaign.campaign_model("tiny")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _engine(cfg, params, **rel_kw):
    rel = ReliabilityConfig(platform="vc707", mode="inline", **rel_kw)
    return ServingEngine(cfg, params, rel=rel, max_len=32)


def test_canary_divergence_disabled_and_clean(tiny_setup):
    cfg, params = tiny_setup
    eng = _engine(cfg, params)
    assert eng.canary_divergence() is None  # off by default
    eng2 = _engine(cfg, params, canary=CanaryConfig(prompts=2, tokens=8))
    assert eng2.canary_divergence() == 0.0  # nominal == clean, exactly


def test_canary_retreat_where_ded_counters_are_blind(tiny_setup):
    """THE acceptance scenario: with ecc=False the inject path re-encodes
    parity over the faulty planes, so scrub syndromes are structurally
    clean — detected stays 0 at any depth and the DED canary can never
    trip. The accuracy canary still sees the corrupted outputs and
    retreats the rail."""
    cfg, params = tiny_setup
    # control: DED-only walk from the guardband edge never retreats — it
    # descends straight to the crash floor
    ctl = _engine(cfg, params, ecc=False, rails=RailsConfig(start_v=VC707.v_min))
    v_ctl, hist_ctl = ctl.autotune_voltage(max_rounds=12)
    assert all(h.detected == 0 for h in hist_ctl)
    assert not any("backoff" in h.action for h in hist_ctl)
    # locked at the crash floor (within one fp-accumulated 0.01 step)
    assert hist_ctl[-1].action == "floor"
    assert v_ctl < VC707.v_crash + 0.015

    # canary: same blind counters, but the divergence SLO trips the rail
    eng = _engine(
        cfg, params, ecc=False, rails=RailsConfig(start_v=VC707.v_min),
        canary=CanaryConfig(prompts=2, tokens=8, divergence_slo=0.05),
    )
    v, hist = eng.autotune_voltage(max_rounds=12)
    assert all(h.detected == 0 for h in hist)  # DED never fired
    assert any(h.action == "acc+backoff" for h in hist)
    assert eng.controller.locked
    assert v > v_ctl + 1e-9  # retreated strictly above the control's floor
    assert hist[-1].divergence > 0.05


def test_canary_multirail_retreats_all_rails(tiny_setup):
    """Multi-rail engines broadcast the whole-model canary score: every
    arena rail backs off on the SLO violation, counters silent."""
    cfg, params = tiny_setup
    eng = _engine(
        cfg, params, ecc=False,
        rails=RailsConfig(multi_rail=True, start_v=VC707.v_min),
        canary=CanaryConfig(prompts=2, tokens=8, divergence_slo=0.05),
    )
    eng.autotune_voltage(max_rounds=12)
    tripped = [
        d for d, c in eng.controller.rails.items()
        if any(h.action == "acc+backoff" for h in c.history)
    ]
    assert set(tripped) == set(eng._store.domains)
    assert all(
        h.detected == 0 for c in eng.controller.rails.values()
        for h in c.history
    )
