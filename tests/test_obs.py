"""Reliability flight recorder (docs/OBSERVABILITY.md): event schema,
metrics registry, deterministic step-clock traces, export round-trips, and
the two acceptance properties — byte-identical traces across identical runs
and bit-identical serving with the recorder off vs absent."""

import json

import numpy as np
import pytest

import jax

from conftest import tiny_cfg
from repro.core.telemetry import DomainFaultStats, FaultStats, ShardFaultStats
from repro.models import lm
from repro.obs import (
    EVENT_KINDS,
    EventSchemaError,
    MetricsRegistry,
    TraceRecorder,
    read_jsonl,
    summary_markdown,
    to_chrome_trace,
    to_jsonl,
    validate_events,
)
from repro.obs import profile as obs_profile
from repro.serving import (
    FaultModelConfig,
    RailsConfig,
    ReliabilityConfig,
    ServingEngine,
)


# ---------------------------------------------------------------------------
# events + recorder core
# ---------------------------------------------------------------------------
def test_emit_validates_and_orders():
    rec = TraceRecorder()
    rec.emit("serve_begin", n_requests=2, n_lanes=2, scrub_interval=4)
    rec.advance(3)
    ev = rec.emit("gauge", name="queue_depth", value=1)
    assert ev["seq"] == 1 and ev["step"] == 3
    assert validate_events(rec.events) == 2


def test_emit_rejects_unknown_kind_and_missing_payload():
    rec = TraceRecorder()
    with pytest.raises(EventSchemaError):
        rec.emit("not_a_kind")
    with pytest.raises(EventSchemaError):
        rec.emit("gauge", name="only_half")  # missing `value`
    # non-strict recorder defers validation to export/report time
    loose = TraceRecorder(strict=False)
    loose.emit("gauge", name="only_half")
    with pytest.raises(EventSchemaError):
        validate_events(loose.events)


def test_validate_events_rejects_seq_disorder():
    rec = TraceRecorder()
    rec.emit("canary_probe", divergence=0.0)
    rec.emit("canary_probe", divergence=0.1)
    evs = [rec.events[1], rec.events[0]]
    with pytest.raises(EventSchemaError):
        validate_events(evs)


def test_extra_payload_fields_allowed():
    rec = TraceRecorder()
    rec.emit("trie_evict", pages=3, reason="lru")  # extra field rides along
    assert rec.events[0]["reason"] == "lru"
    assert validate_events(rec.events) == 1


def test_every_kind_has_envelope_free_payload():
    # payload field names must never collide with the envelope
    from repro.obs import ENVELOPE_FIELDS

    for kind, fields in EVENT_KINDS.items():
        assert not set(fields) & set(ENVELOPE_FIELDS), kind


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------
def test_metrics_counter_gauge_histogram():
    m = MetricsRegistry()
    m.counter("hits").inc()
    m.counter("hits").inc(4)
    m.gauge("depth", shard=0).set(3)
    m.gauge("depth", shard=0).set(1)
    h = m.histogram("lat", buckets=(1, 2, 4))
    for v in (1, 3, 9):
        h.observe(v)
    snap = m.to_dict()
    assert snap["hits"]["value"] == 5
    assert snap["depth{shard=0}"]["value"] == 1
    assert snap["depth{shard=0}"]["max"] == 3
    assert snap["lat"]["count"] == 3
    assert snap["lat"]["counts"][-1] == 1  # 9 overflows the last bucket


def test_metrics_label_identity_and_type_guard():
    m = MetricsRegistry()
    assert m.counter("x", a=1, b=2) is m.counter("x", b=2, a=1)
    assert m.counter("x", a=1, b=2) is not m.counter("x", a=1)
    with pytest.raises(AssertionError):
        m.gauge("x", a=1, b=2)  # same name+labels, different type


def test_observe_fault_stats_folds_containers():
    m = MetricsRegistry()
    st = FaultStats(words=10, corrected=3, detected=1, shard=2)
    dom = DomainFaultStats({"mlp": st, "kv": FaultStats(words=5, silent=2)})
    m.observe_fault_stats("scrub", dom)
    assert m.get("scrub.corrected", domain="mlp", shard=2).value == 3
    assert m.get("scrub.silent", domain="kv").value == 2
    sh = ShardFaultStats([DomainFaultStats({"kv": st}, shard=2)])
    m2 = MetricsRegistry()
    m2.observe_fault_stats("scrub", sh)
    assert m2.get("scrub.words", domain="kv", shard=2).value == 10


# ---------------------------------------------------------------------------
# ShardFaultStats reduction symmetry (satellite: growth-path shard tags)
# ---------------------------------------------------------------------------
def _shard_stats(shard_ids):
    return ShardFaultStats(
        [
            DomainFaultStats(
                {"kv": FaultStats(words=10 * (s + 1), corrected=s, shard=s)},
                shard=s,
            )
            for s in shard_ids
        ]
    )


def test_shardfaultstats_growth_preserves_tags():
    """Accumulating a sub-fleet slice (shards 4..7) into an empty container
    must keep the rows' own shard ids, not collapse them to -1."""
    acc = ShardFaultStats()
    acc.accumulate(_shard_stats([4, 5, 6, 7]))
    assert [d.shard for d in acc.by_shard] == [4, 5, 6, 7]
    assert [d["kv"].shard for d in acc.by_shard] == [4, 5, 6, 7]
    # and the adopted rows are copies, not aliases
    acc.by_shard[0]["kv"].corrected += 100
    fresh = _shard_stats([4, 5, 6, 7])
    assert fresh.by_shard[0]["kv"].corrected == 4


def test_shardfaultstats_summed_matches_accumulate():
    """summed() is the pure partner of accumulate(): same totals, same
    per-row tags, no input mutated."""
    a, b = _shard_stats([0, 1]), _shard_stats([0, 1])
    pure = ShardFaultStats.summed([a, b])
    inplace = _shard_stats([0, 1])
    inplace.accumulate(_shard_stats([0, 1]))
    assert pure.n_shards == inplace.n_shards == 2
    for s in range(2):
        assert pure[s]["kv"].counters().tolist() == inplace[s]["kv"].counters().tolist()
        assert pure[s].shard == inplace[s].shard == s
    # inputs untouched by the pure reduction
    assert a[0]["kv"].corrected == 0 and b[1]["kv"].corrected == 1


def test_faultstats_to_dict_and_coverage_row():
    st = FaultStats(
        words=100, corrected=3, detected=2, silent=1,
        words_1bit=3, words_2bit=2, words_multi=1, faulty_bits=10,
    )
    d = st.to_dict()
    assert d["words"] == 100 and d["faulty_words"] == 6
    assert "shard" not in d  # untagged stats serialize untagged
    assert FaultStats(words=1, shard=3).to_dict()["shard"] == 3
    row = st.coverage_row()
    assert row["coverage_correctable"] == 3 / 6
    assert row["coverage_silent"] == 1 / 6


# ---------------------------------------------------------------------------
# serve traces: determinism, bit-identity, export round-trips
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def serve_setup():
    cfg = tiny_cfg()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    reqs = [
        (rng.integers(1, 100, size=s).astype(np.int32), n)
        for s, n in [(5, 6), (3, 4), (7, 5), (4, 8)]
    ]
    return cfg, params, reqs


def _engine(cfg, params, recorder=None):
    return ServingEngine(
        cfg, params,
        rel=ReliabilityConfig(
            mode="inline", voltage=0.58, seed=1,
            fault_model=FaultModelConfig(mask_source="device"),
            rails=RailsConfig(multi_rail=True),
        ),
        max_len=64, recorder=recorder,
    )


def _serve(cfg, params, reqs, recorder=None, eng=None):
    eng = eng or _engine(cfg, params, recorder)
    return eng.serve(reqs, n_lanes=2, scrub_interval=2, walk_kv=True,
                     kv_voltage=0.57)


def test_trace_jsonl_byte_identical_across_runs(serve_setup, tmp_path):
    """The tentpole acceptance property: two identical runs produce
    byte-identical JSONL traces (deterministic step-clock, no wall-clock)."""
    cfg, params, reqs = serve_setup
    texts = []
    for i in range(2):
        rec = TraceRecorder()
        _serve(cfg, params, reqs, recorder=rec)
        p = tmp_path / f"run{i}.jsonl"
        rec.to_jsonl(p)
        texts.append(p.read_bytes())
    assert texts[0] == texts[1]
    evs = read_jsonl(tmp_path / "run0.jsonl")
    assert validate_events(evs) == len(evs) > 0


def test_recorder_off_bit_identical(serve_setup):
    """Recorder absent vs attached: same tokens, same fault counters —
    tracing only reads host values the serve loop already computed."""
    cfg, params, reqs = serve_setup
    r_off = _serve(cfg, params, reqs)
    rec = TraceRecorder()
    r_on = _serve(cfg, params, reqs, recorder=rec)
    assert set(r_off.outputs) == set(r_on.outputs)
    for rid in r_off.outputs:
        assert np.array_equal(r_off.outputs[rid], r_on.outputs[rid]), rid
    assert r_off.kv_stats.counters().tolist() == r_on.kv_stats.counters().tolist()
    assert r_off.kv_voltages == r_on.kv_voltages
    assert r_off.steps == r_on.steps
    assert len(rec.events) > 0


def test_trace_covers_serve_lifecycle(serve_setup):
    cfg, params, reqs = serve_setup
    rec = TraceRecorder()
    rep = _serve(cfg, params, reqs, recorder=rec)
    kinds = {e["kind"] for e in rec.events}
    assert {"serve_begin", "admit", "retire", "kv_scrub", "gauge",
            "rail_step", "serve_end"} <= kinds
    # every request admits exactly once and retires exactly once
    admits = rec.of_kind("admit")
    retires = rec.of_kind("retire")
    assert len(admits) == len(retires) == len(reqs)
    for ev in retires:
        assert ev["latency_steps"] >= ev["tokens"] - 1 >= 0
    # serve_end joins the report
    end = rec.of_kind("serve_end")[-1]
    assert end["steps"] == rep.steps
    assert end["finished"] == len(rep.outputs)
    # kv rail_step events join their causing DED counters inline
    for ev in rec.of_kind("rail_step"):
        assert ev["domain"] == "kv"
        assert ev["words"] >= 0 and ev["corrected"] >= 0
    # metrics fed alongside events
    assert rec.metrics.get("serve.admissions").value == len(reqs)


def test_chrome_trace_layout(serve_setup, tmp_path):
    cfg, params, reqs = serve_setup
    rec = TraceRecorder()
    _serve(cfg, params, reqs, recorder=rec)
    path = tmp_path / "trace.json"
    ct = to_chrome_trace(rec, path)
    loaded = json.loads(path.read_text())
    assert loaded == ct
    evs = ct["traceEvents"]
    # one complete-span per request lifetime (admit -> retire)
    spans = [e for e in evs if e["ph"] == "X"]
    assert len(spans) == len(reqs)
    assert all(e["dur"] >= 1 for e in spans)
    # rail voltages exported as counter tracks, gauges too
    counters = {e["name"] for e in evs if e["ph"] == "C"}
    assert any(n.startswith("V[") for n in counters)
    assert "sched.queue_depth" in counters
    # process metadata names every shard track
    assert any(e["ph"] == "M" and e["name"] == "process_name" for e in evs)


def test_summary_markdown_renders(serve_setup, tmp_path):
    cfg, params, reqs = serve_setup
    rec = TraceRecorder()
    _serve(cfg, params, reqs, recorder=rec)
    md = rec.summary_markdown()
    assert "## Rail trajectories" in md
    assert "## Requests" in md
    assert "| kv " in md or "| kv |" in md
    # the report CLI renders the same thing from the written file
    from repro.obs import report as report_cli

    p = tmp_path / "t.jsonl"
    rec.to_jsonl(p)
    out = tmp_path / "t.md"
    assert report_cli.main([str(p), "--out", str(out), "--validate"]) == 0
    assert "## Event counts" in out.read_text()


# ---------------------------------------------------------------------------
# host spans (obs/profile.py): on the profiler's clock, never in the event log
# ---------------------------------------------------------------------------
class _NoAnnotation:
    """Stands in for TraceAnnotation outside a session: entering one fails."""

    @staticmethod
    def is_enabled():
        return False

    def __init__(self, *a, **kw):
        raise AssertionError("a span was entered outside a profiler session")


def test_span_outside_a_session_is_null_and_computes_no_stat(monkeypatch):
    monkeypatch.setattr(obs_profile, "TraceAnnotation", _NoAnnotation)
    calls = []
    fn = lambda x: (calls.append(x), x * 2)[1]
    assert obs_profile.call("noop", fn, 3) == 6  # passthrough, no block
    assert calls == [3]

    def costly():
        raise AssertionError("a stat was computed outside a profiler session")

    sp = obs_profile.span("kv.paged_gather_scrub", pages=4, live_pages=costly)
    assert sp is obs_profile.span("serve.decode_block")  # one shared null
    with sp as entered:
        entered.set_metadata(k=costly)
    assert not obs_profile.enabled()


@pytest.fixture(scope="module")
def traced_serve(serve_setup, tmp_path_factory):
    """Two engines alike, each with its own recorder, each serving the
    stream twice (the first serve compiles): one with no profiler session,
    where entering a span or reading the clock for the request stamps
    fails; one whose second serve runs inside a session. That session's
    XSpace is read back as program spans by bench/benchlib/spans.py, the
    reader the benchmark uses."""
    import os
    import sys

    from repro.serving import scheduler

    bench = os.path.join(os.path.dirname(os.path.dirname(__file__)), "bench")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from benchlib import spans, tracing

    def no_clock():
        raise AssertionError("the host clock was read outside a session")

    cfg, params, reqs = serve_setup
    rec_off, rec_on = TraceRecorder(), TraceRecorder()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(obs_profile, "TraceAnnotation", _NoAnnotation)
        mp.setattr(scheduler.time, "perf_counter_ns", no_clock)
        eng = _engine(cfg, params, rec_off)
        r_off = [_serve(cfg, params, reqs, eng=eng) for _ in range(2)]
    eng = _engine(cfg, params, rec_on)
    r_on = [_serve(cfg, params, reqs, eng=eng)]
    trace_dir = tmp_path_factory.mktemp("xspace")
    jax.profiler.start_trace(str(trace_dir))
    try:
        assert obs_profile.enabled()
        r_on.append(_serve(cfg, params, reqs, eng=eng))
    finally:
        jax.profiler.stop_trace()
    sp = spans.program_spans(tracing.load(str(trace_dir)))
    return {"off": (r_off, rec_off), "on": (r_on, rec_on), "spans": sp, "reqs": reqs}


def test_serve_outside_a_session_enters_no_span(traced_serve):
    """The whole serving path with no profiler session entered no span and
    never read the clock (the fixture's guards would have raised)."""
    r_off, _ = traced_serve["off"]
    assert [len(r.outputs) for r in r_off] == [len(traced_serve["reqs"])] * 2


def test_session_spans_nest_and_count(traced_serve):
    sp = traced_serve["spans"]
    named = lambda n: [s for s in sp if s[0] == n]
    blocks = named("serve.decode_block")
    assert blocks and named("decode.multistep")
    for _, s, e, _ in named("decode.multistep"):
        assert any(b[1] <= s and e <= b[2] for b in blocks)
    for _, s, e, _ in named("serve.block_sync"):
        assert any(b[1] <= s and e <= b[2] for b in blocks)
    assert all(st["k"] >= 1 and st["lanes_active"] >= 1 for *_, st in blocks)
    scrubs = named("kv.paged_gather_scrub")
    assert scrubs
    for *_, st in scrubs:
        assert 0 < st["live_pages"] <= st["pages"]
    retires = named("serve.retire")
    assert sorted(st["request_id"] for *_, st in retires) == list(
        range(len(traced_serve["reqs"]))
    )
    for *_, st in retires:
        assert 0 <= st["admit_ns"] <= st["first_token_ns"] <= st["done_ns"]
    (stream,) = named("serve.stream")
    assert stream[3] == {"requests": len(traced_serve["reqs"]), "lanes": 2}
    assert all(stream[1] <= s[1] and s[2] <= stream[2] for s in blocks + scrubs)


def test_session_leaves_outputs_and_recorder_byte_identical(traced_serve, tmp_path):
    (r_offs, rec_off), (r_ons, rec_on) = traced_serve["off"], traced_serve["on"]
    for r_off, r_on in zip(r_offs, r_ons):
        assert set(r_off.outputs) == set(r_on.outputs)
        for rid in r_off.outputs:
            assert r_off.outputs[rid].tobytes() == r_on.outputs[rid].tobytes(), rid
        assert r_off.kv_stats.counters().tolist() == r_on.kv_stats.counters().tolist()
        assert r_off.kv_voltages == r_on.kv_voltages
    rec_off.to_jsonl(tmp_path / "off.jsonl")
    rec_on.to_jsonl(tmp_path / "on.jsonl")
    assert (tmp_path / "off.jsonl").read_bytes() == (tmp_path / "on.jsonl").read_bytes()


def test_to_jsonl_accepts_events_or_recorder(tmp_path):
    rec = TraceRecorder()
    rec.emit("canary_probe", divergence=0.5)
    s1 = to_jsonl(rec)
    s2 = to_jsonl(rec.events)
    assert s1 == s2 and s1.endswith("\n")
    assert json.loads(s1.splitlines()[0])["kind"] == "canary_probe"


def test_mesh_serve_single_causal_trace():
    """The mesh acceptance property (ISSUE 9): serving on a forced 2-shard
    host mesh with one recorder yields a single causally-ordered trace in
    which every shard's serve lifecycle appears and every kv rail_step
    joins to its own shard's DED counters inline."""
    import os
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import json
        import numpy as np
        import jax
        from conftest import tiny_cfg
        from repro.launch.mesh import make_reliability_mesh
        from repro.models import lm
        from repro.obs import TraceRecorder, validate_events
        from repro.serving.engine import (
            FaultModelConfig, RailsConfig, ReliabilityConfig, ServingEngine,
        )

        cfg = tiny_cfg(d_model=64, n_layers=2, d_ff=128, vocab=128)
        params = lm.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        reqs = [
            (rng.integers(1, 100, size=int(s), dtype=np.int32), int(n))
            for s, n in zip(
                rng.integers(3, 10, size=8), rng.integers(6, 12, size=8)
            )
        ]
        rec = TraceRecorder()
        e = ServingEngine(
            cfg, params,
            ReliabilityConfig(
                mode="inline", voltage=0.58, seed=1,
                fault_model=FaultModelConfig(mask_source="device"),
                rails=RailsConfig(multi_rail=True, policy="per_shard", start_v=0.58),
            ),
            max_len=64, mesh=make_reliability_mesh(2), recorder=rec,
        )
        r = e.serve(reqs, n_lanes=2, scrub_interval=1, walk_kv=True)
        validate_events(rec.events)
        rails = [ev for ev in rec.events if ev["kind"] == "rail_step"]
        print(json.dumps({
            "served": sorted(r.outputs),
            "n_requests": len(reqs),
            "n_events": len(rec.events),
            "serve_shards": sorted({
                ev["shard"] for ev in rec.events
                if ev["kind"] in ("serve_begin", "serve_end", "kv_scrub")
            }),
            "rail_shards": sorted({ev["shard"] for ev in rails}),
            "rail_join": all(
                ev["words"] >= 0 and "detected" in ev for ev in rails
            ),
            "seqs_ordered": all(
                a["seq"] < b["seq"]
                for a, b in zip(rec.events, rec.events[1:])
            ),
        }))
        """
    )
    env = dict(os.environ)
    here = os.path.dirname(__file__)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(here, "..", "src"), here]
    )
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["served"] == list(range(res["n_requests"]))
    assert res["n_events"] > 0 and res["seqs_ordered"]
    # both shards' serve lifecycles and rail walks are in the ONE trace
    assert res["serve_shards"] == [0, 1]
    assert set(res["rail_shards"]) >= {0, 1}
    assert res["rail_join"]


def test_autotune_rail_steps_advance_clock(serve_setup):
    """Autotune rounds advance the step clock; rail_step events join the
    controller walk to its counters (one event per round per rail)."""
    cfg, params, reqs = serve_setup
    rec = TraceRecorder()
    eng = ServingEngine(
        cfg, params,
        rel=ReliabilityConfig(
            mode="inline", voltage=0.62, seed=1,
            fault_model=FaultModelConfig(mask_source="device"),
        ),
        max_len=64, recorder=rec,
    )
    eng.autotune_voltage(max_rounds=4)
    steps = rec.of_kind("rail_step")
    assert steps and len(steps) == len(eng.controller.history)
    assert [e["step"] for e in steps] == sorted(e["step"] for e in steps)
    assert rec.step >= len(steps)
    acts = {e["action"] for e in steps}
    assert acts <= {
        "hold", "lower", "drift+backoff", "escalate", "acc+backoff",
        "trip+backoff", "floor",
    }
