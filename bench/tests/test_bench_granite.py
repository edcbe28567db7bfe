"""The ``hybrid_mamba2`` family (granite-4.0-h-micro): its sizes at the
published widths, ``serve()`` on a 10-layer toy against the plain reference
``bench/reference/granite_hybrid.py``, and the two state-store readers.
"""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchlib import correctness, costs, program, registry, tracing, weights  # noqa: E402

NAME = "granite-4.0-h-micro"
# 10 layers at toy widths; every structural key (layer_types, multipliers,
# NoPE, conv, one B/C group) stays the configuration's own
TOY = dict(name="granite-toy", hidden_size=64, shared_intermediate_size=128,
           intermediate_size=128, num_attention_heads=4, num_key_value_heads=2,
           vocab_size=256, mamba_d_head=8, mamba_n_heads=16, mamba_d_state=16,
           mamba_chunk_size=4, attention_multiplier=1 / 16, torch_dtype="float32")
# the served tokens of a float32 toy match the reference's best to rounding;
# the float8 control must read more than this (tested)
TOLERANCE = 1e-3


def _config(**over) -> dict:
    bench = registry.load_benchmark(ROOT)
    return dict(registry.load_config(bench, NAME, ROOT), **over)


def test_family_sizes_at_the_published_widths():
    cfg = _config()
    family = registry.config_family(cfg, ROOT)
    n = sum(int(np.prod(shape)) for shape, _ in family.shapes(cfg).values())
    assert n == 951_991_232  # 9 Mamba-2 layers, 1 attention layer, the tied 100,352 rows
    whole = _config(num_hidden_layers=40, layer_types=cfg["published"]["layer_types"])
    assert sum(int(np.prod(s)) for s, _ in family.shapes(whole).values()) == 3_191_396_096
    mc = family.model_config(cfg)
    assert [mc.layer_kind(j)["mixer"] for j in range(mc.period)] == [
        "attn" if t == "attention" else "mamba2" for t in cfg["layer_types"]
    ]
    assert (mc.rope, mc.attn_scale, mc.embed_mult, mc.residual_mult, mc.logits_div) == (
        False, 1 / 64, 12.0, 0.22, 8.0
    )
    # FLOPs of a token: 2 per matmul parameter, the SSD step and the conv
    ssm = 9 * (2 * 4 * 4352 + 5 * 64 * 64 * 128)
    assert family.token_flops(cfg, 1) == 2.0 * family.matmul_params(cfg) + 4 * 32 * 64 + ssm


def test_the_reference_does_not_echo_its_input_token():
    """At a width where the embedding (times 12) would outweigh layer writes
    of plain ``matrix`` scale, the ``write`` init keeps the next token a
    function of the context: the reference's best token is the input token
    at few positions (about 90% with ``matrix`` writes)."""
    cfg = _config(**dict(TOY, hidden_size=256, shared_intermediate_size=512,
                         intermediate_size=512, mamba_n_heads=64, vocab_size=1024))
    family = registry.config_family(cfg, ROOT)
    reference = registry.load_reference(family.REFERENCE, ROOT)
    rw = reference.prepare(weights.make(cfg, 2**31 + 1, family), cfg)
    seq = np.random.default_rng(1).integers(1, cfg["vocab_size"], 64).astype(np.int32)
    best = np.asarray(reference.logits(rw, cfg, seq)).argmax(-1)
    assert np.mean(best == seq) < 0.1


@pytest.fixture(scope="module")
def served():
    """5 requests over 2 lanes, so both slots are written again mid-wave."""
    cfg = _config(**TOY)
    cfg["serve"] = dict(cfg["serve"], scrub_interval=2)
    family = registry.config_family(cfg, ROOT)
    w = weights.make(cfg, 2**31 + 5, family)
    eng = program.build_engine(cfg, family.program_params(w, cfg), 32, family)
    mix = {"lanes": 2, "max_len": 32, "requests": [[8, 6, 5]]}
    gen = np.random.default_rng(7)
    reqs = [(gen.integers(1, cfg["vocab_size"], size=8).astype(np.int32), 6) for _ in range(5)]
    eng.ssm_words_before = eng.rail_stats.by_domain["ssm"].words  # the weight planes' scrub
    rep = program.serve(eng, reqs, mix, cfg)
    items = [(p, rep.outputs[i]) for i, (p, _) in enumerate(reqs)]
    reference = registry.load_reference(family.REFERENCE, ROOT)
    return cfg, reference, reference.prepare(w, cfg), items, rep, eng


def test_serve_matches_the_reference_with_slots_reused(served):
    cfg, reference, rw, items, rep, eng = served
    assert [len(s) for _, s in items] == [6] * 5 and rep.preemptions == 0
    gap = correctness.widest_gap(reference, rw, cfg, items, 32)
    assert gap["widest"] <= TOLERANCE, gap
    # every decode step read each live lane's whole slot through the kernel
    words = rep.state_stats.words
    assert words > 0 and rep.state_stats.clean == words
    assert rep.state_stats.corrected == rep.state_stats.detected == 0
    assert eng.rail_stats.by_domain["ssm"].words - eng.ssm_words_before == words


def test_float8_reference_fails_the_tolerance(served):
    cfg, reference, rw, items, _, _ = served
    control = correctness.widest_gap(reference, rw, cfg, items, 32, "float8_e4m3fn")
    assert control["widest"] > 10 * TOLERANCE, control


class _Reduced:
    def __init__(self, kernels, module_ns, window=(0, 10**9)):
        self.kernels, self.module_ns, self.window, self.n_devices = kernels, module_ns, window, 1

    kernel_events = tracing.Reduced.kernel_events


# the HLO text of a traced ecc_ssd_step_2d event at the cell's shapes
_EVENT = (
    "%ecc_ssd_step_2d.7 = (u32[16384,128]{1,0:T(8,128)}, u32[16384,128]{1,0:T(8,128)}, "
    "u8[16384,128]{1,0:T(32,128)(4,1)}, f32[16384,1]{1,0:T(8,128)}, f32[16384,1]{1,0:T(8,128)}, "
    "s32[256,128]{1,0:T(8,128)}) custom-call(u32[16384,128]{1,0:T(8,128)} %a, "
    "u32[16384,128]{1,0:T(8,128)} %b, u8[16384,128]{1,0:T(32,128)(4,1)} %c, "
    "f32[16384,1]{1,0:T(8,128)} %d, f32[16384,1]{1,0:T(8,128)} %e, f32[16384,1]{1,0:T(8,128)} %f, "
    "s32[16384,1]{1,0:T(8,128)} %g, f32[8,1,128]{2,1,0:T(1,128)} %h, f32[8,1,128]{2,1,0:T(1,128)} %i), "
    'custom_call_target="tpu_custom_call"'
)


def test_state_store_readers():
    share = registry.metric_reader("ssm_state_share", ROOT)
    roof = registry.metric_reader("ecc_ssd_roofline", ROOT)
    pk = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    red = _Reduced([(_EVENT, 100_000), (_EVENT, 300_000)], {"jit_commit_state": 600_000})
    assert share({"reduced": red}) == pytest.approx(0.1)
    # 2 * 16384 * 128 words of 9 bytes each way, plus the small inputs and outputs
    words = 16384 * 128
    nbytes = 2 * words * 9 + 4 * 16384 * 4 + 2 * 8 * 128 * 4 + 2 * 16384 * 4 + 256 * 128 * 4
    want = 100.0 * 2 * nbytes / 819e9 / 400e-6
    assert roof({"reduced": red, "peaks": pk}) == pytest.approx(want)
    assert costs.roofline_seconds(10.0 * words, nbytes, pk)[1] == "memory"
    # a program without the state store has neither event: both read nothing
    empty = _Reduced([], {})
    assert share({"reduced": empty}) is None and roof({"reduced": empty, "peaks": pk}) is None
