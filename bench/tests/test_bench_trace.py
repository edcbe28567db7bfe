"""The trace reduction, the peaks table and the operation/byte functions,
on a small recorded trace checked in beside this file
(data/trace_slice.pbtxt: one decode block and one interval scrub of the
qwen2-7b chat cell on a TPU v5 lite)."""

from __future__ import annotations

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchlib import costs, registry, tracing  # noqa: E402

FIXTURE = os.path.join(BENCH, "tests", "data", "trace_slice.pbtxt")
V5E = "TPU v5 lite"


@pytest.fixture(scope="module")
def profile():
    import jax

    with open(FIXTURE) as f:
        text = f.read()
    return jax.profiler.ProfileData.from_serialized_xspace(
        jax.profiler.ProfileData.text_proto_to_serialized_xspace(text)
    )


@pytest.fixture(scope="module")
def reduced(profile):
    return tracing.reduce(profile)


def _line(profile, plane, line):
    p = next(p for p in profile.planes if p.name == plane)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in next(
        ln for ln in p.lines if ln.name == line).events]


def test_idle_share_is_one_minus_union_of_device_ops(profile, reduced):
    (lo, hi), = [(s, e) for n, s, e in _line(profile, "/host:CPU", "python3") if n == "bench.window"]
    busy, end = 0.0, lo
    for _, s, e in sorted(_line(profile, "/device:TPU:0", "XLA Ops"), key=lambda t: t[1]):
        s, e = max(s, end), min(e, hi)
        if e > s:
            busy += e - s
            end = e
    assert reduced.window == (lo, hi)
    assert reduced.busy_ns == pytest.approx(busy, rel=1e-9)
    assert reduced.idle_share == pytest.approx(1 - busy / (hi - lo), rel=1e-9)
    assert 0 < reduced.idle_share < 0.01  # the slice is one scrub: the chip is busy


def test_kernel_and_program_time_by_name(profile, reduced):
    ops = _line(profile, "/device:TPU:0", "XLA Ops")
    want = sum(e - s for n, s, e in ops if n.startswith("%ecc_matmul_2d."))
    got = reduced.kernel_events("ecc_matmul_2d")
    assert len(got) == sum(n.startswith("%ecc_matmul_2d.") for n, _, _ in ops) > 0
    assert sum(ns for _, ns in got) == pytest.approx(want)
    mods = _line(profile, "/device:TPU:0", "XLA Modules")
    scrub = sum(e - s for n, s, e in mods if n.startswith("jit__scrub_rows("))
    assert reduced.module_ns["jit__scrub_rows"] == pytest.approx(scrub)
    assert reduced.op_ns["jit__unknown/ecc_matmul_2d"] == pytest.approx(want)
    assert not any(k.endswith("/while") for k in reduced.op_ns)


def test_kernel_shapes_come_from_the_hlo_text(reduced):
    name, _ = reduced.kernel_events("ecc_matmul_2d")[0]
    out, operands = tracing.shapes(name)
    (m, n), (x_dt, (m2, k)) = out[0][1], operands[0]
    assert m == m2 == 8 and x_dt == "bf16"
    assert operands[1] == ("u32", (k // 8, n)) and operands[3][0] == "u8"
    (_, (pages, words)), = tracing.shapes(reduced.kernel_events("gather_scrub_2d")[0][0])[1][:1]
    assert words % 128 == 0 and pages > 0


def test_roofline_names_its_bound():
    pk = costs.peaks(V5E)
    flops, nbytes = costs.ecc_matmul(8, 3584, 18944)
    t, bound = costs.roofline_seconds(flops, nbytes, pk)
    assert bound == "memory" and t == pytest.approx(nbytes / 819e9)
    assert nbytes == 9 * 448 * 18944 + 8 * 3584 * 2 + 8 * 18944 * 4
    flops, nbytes = costs.ecc_matmul(4096, 3584, 18944)
    t, bound = costs.roofline_seconds(flops, nbytes, pk)
    assert bound == "compute" and t == pytest.approx(2 * 4096 * 3584 * 18944 / 197e12)
    ops, nbytes = costs.gather_scrub(16, 1024)
    assert costs.roofline_seconds(ops, nbytes, pk)[1] == "memory"


def test_roofline_readers_stay_within_the_roofline(reduced):
    ctx = {"reduced": reduced, "peaks": costs.peaks(V5E)}
    for name in ("ecc_matmul_roofline", "gather_scrub_roofline", "kv_scrub_share", "idle_share"):
        value = registry.metric_reader(name, ROOT)(ctx)
        assert 0 < value <= 100, (name, value)
    empty = tracing.Reduced((0, 1), 1, 1.0, {}, {}, [], [])
    assert registry.metric_reader("ecc_matmul_roofline", ROOT)({"reduced": empty}) is None


def test_peaks_table_refuses_an_unknown_device():
    assert costs.peaks(V5E)["bf16_flops_per_s"] == 197e12
    with pytest.raises(costs.UnknownDevice):
        costs.peaks("cpu")


def test_breakdown_lists_top_ops_and_labelled_gaps(reduced):
    b = tracing.breakdown(reduced)
    ops = b["device_ops"]
    assert 0 < len(ops) <= 10 and len(b["idle_gaps"]) <= 10
    assert [v for _, v in ops] == sorted((v for _, v in ops), reverse=True)
    assert ops[0][0] == "jit__scrub_rows/fusion"
    assert all(isinstance(lab, str) and secs > 0 for lab, secs in b["idle_gaps"])


def test_model_flops_count_prompt_and_decode():
    cfg = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 2, "head_dim": 4,
           "num_attention_heads": 2, "num_key_value_heads": 1, "vocab_size": 32}
    dense = registry.load_family("dense")
    per_layer = 8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16
    assert dense.matmul_params(cfg) == 2 * per_layer + 8 * 32
    # prompt of 3 (contexts 1, 2, 3) and 2 outputs (one decode, context 4)
    want = sum(2 * dense.matmul_params(cfg) + 4 * 2 * 2 * 4 * c for c in (1, 2, 3, 4))
    assert costs.request_flops(cfg, 3, 2, dense) == want
