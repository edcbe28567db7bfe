"""Off-critical-path async scrub must be invisible (DESIGN.md §18).

The scheduler's overlapped scrub dispatches the fused inject+scrub launch
asynchronously and harvests its counters just before the *next* interval's
tick. The deferred harvest is purely a host-side reordering: the controller
still sees interval N's telemetry before interval N+1's injection, so every
observable — tokens, per-request stats, aggregate cache stats, the kv rail
trajectory — must be byte-identical to the serialized path. These tests pin
that contract, including under preemption-recompute and live rail walks.
"""

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import lm
from repro.serving.engine import (
    ProtectionConfig,
    RailsConfig,
    ReliabilityConfig,
    ServingEngine,
)


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("qwen3-0.6b")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    prompts = (
        np.random.default_rng(0).integers(0, cfg.vocab, (4, 8)).astype(np.int32)
    )
    return cfg, params, prompts


@pytest.fixture(scope="module")
def engine(setup):
    cfg, params, _ = setup
    return ServingEngine(cfg, params, rel=None, max_len=48)


def _assert_reports_identical(a, b):
    assert sorted(a.outputs) == sorted(b.outputs)
    for rid, toks in a.outputs.items():
        np.testing.assert_array_equal(toks, b.outputs[rid])
    assert a.kv_voltages == b.kv_voltages
    assert a.kv_stats == b.kv_stats
    assert a.request_stats == b.request_stats
    assert a.preemptions == b.preemptions


def test_overlap_identical_under_undervolt(setup, engine):
    """Undervolted cache (real corrections on the read path): overlapped
    scrub produces byte-identical tokens, counters, and voltages."""
    cfg, params, prompts = setup
    reqs = [(prompts[i][: 4 + i], 6 + 3 * i) for i in range(4)]
    kw = dict(n_lanes=2, scrub_interval=1, kv_voltage=0.58)
    ser = engine.serve(reqs, scrub_overlap=False, **kw)
    ovl = engine.serve(reqs, scrub_overlap=True, **kw)
    assert ser.kv_stats.words > 0  # the scrub path actually ran
    _assert_reports_identical(ser, ovl)


def test_overlap_identical_under_preemption_recompute(setup, engine):
    """A tight arena forces preemption + prefill-recompute between a scrub
    dispatch and its deferred harvest; attribution is captured at dispatch
    time, so the reports still match bit for bit."""
    cfg, params, prompts = setup
    reqs = [(prompts[i][: 4 + 2 * i], 5 + 3 * i) for i in range(4)]
    kw = dict(
        n_lanes=2, page_tokens=4, n_pages=8, scrub_interval=2,
        kv_voltage=0.58,
    )
    ser = engine.serve(reqs, scrub_overlap=False, **kw)
    ovl = engine.serve(reqs, scrub_overlap=True, **kw)
    assert ser.preemptions >= 1  # page pressure actually bit
    _assert_reports_identical(ser, ovl)


def test_overlap_identical_rail_walk(setup):
    """walk_kv: the canary-driven kv rail walks on live telemetry. The
    overlapped path must produce the exact same rail trajectory (each move
    lands before the next interval's injection, as serialized)."""
    cfg, params, prompts = setup

    def run(overlap):
        eng = ServingEngine(
            cfg, params,
            rel=ReliabilityConfig(
                platform="vc707", ecc=True, voltage=1.0, mode="inline",
                rails=RailsConfig(multi_rail=True, start_v=0.60),
            ),
            max_len=48,
        )
        reqs = [(prompts[i % 4], 12) for i in range(5)]
        rep = eng.serve(
            reqs, n_lanes=3, scrub_interval=1, walk_kv=True,
            kv_voltage=0.60, scrub_overlap=overlap,
        )
        kv = eng.controller.rails["kv"]
        return rep, (kv.voltage, kv.locked)

    ser, ser_rail = run(False)
    ovl, ovl_rail = run(True)
    assert len(set(ser.kv_voltages)) > 1  # the rail actually moved
    assert ser_rail == ovl_rail
    _assert_reports_identical(ser, ovl)


def test_overlap_auto_demotes_under_escalation(setup):
    """With a codec-escalation controller bound, the commit path can be
    rebound mid-stream, so scrub_overlap=None must demote to serialized —
    and still serve the stream correctly."""
    cfg, params, prompts = setup
    eng = ServingEngine(
        cfg, params,
        rel=ReliabilityConfig(
            platform="vc707", ecc=True, voltage=1.0, mode="inline",
            rails=RailsConfig(multi_rail=True, start_v=0.60),
            protection=ProtectionConfig(escalation=("secded72", "dected79")),
        ),
        max_len=48,
    )
    reqs = [(prompts[i % 4], 10) for i in range(4)]
    rep = eng.serve(
        reqs, n_lanes=2, scrub_interval=1, walk_kv=True, kv_voltage=0.60,
    )
    assert sorted(rep.outputs) == list(range(len(reqs)))
    for i, (_, n) in enumerate(reqs):
        assert len(rep.outputs[i]) == n
