"""The comparison that decides ``correct``, driven end to end on the CPU at a
small size: the harness's look for a chip is skipped and ``run.main`` runs
the rest of a run (weights, engine, warm-up, window, reference). A sound
program reads ``correct: true``; the same run with the timed path broken
underneath reads false, once for each fault a serving cell can have; the
lower-precision control, put in the program's place by ``--control 1``,
reads false through the same checks.
"""

from __future__ import annotations

import json
import os
import shutil
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import run  # noqa: E402
from benchlib import correctness, costs, registry, weights  # noqa: E402
from reference import dense_lm  # noqa: E402

CELL = "tiny.burst"
LIMIT = 0.01
SMALL = dict(name="tiny", hidden_size=128, intermediate_size=256, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, head_dim=32, vocab_size=512,
             torch_dtype="float32")


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(BENCH, "configs", "qwen3-0.6b.json")) as f:
        cfg = json.load(f)
    cfg.update(SMALL)
    (root / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/burst.json").write_text(
        json.dumps({"lanes": 2, "max_len": 48, "requests": [[8, 40, 3]]})
    )
    (root / "bench/limits/tiny.burst.json").write_text(
        json.dumps({"logit_gap": LIMIT, "sample_tokens": 120})
    )
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "tiny", "source": "https://example.org/tiny",
                         "file": "bench/configs/tiny.json", "reduced": [], "why": "test"}]
    bench["workloads"] = [{"name": CELL, "config": "tiny", "traffic": "burst", "chips": 1,
                           "why": "test"}]
    bench["end_to_end"].append({"name": f"tokens_per_s.{CELL}", "unit": "tokens/s",
                                "better": "higher", "bound": 0.01, "source": "host_clock",
                                "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def _cpu_devices(chips):
    import jax

    return jax.devices()


def _run(checkout, monkeypatch, capsys, seed=11, extra=()):
    monkeypatch.setattr(costs, "peaks", lambda kind: {"bf16_flops_per_s": 197e12,
                                                      "hbm_bytes_per_s": 819e9})
    rc = run.main(
        ["--workload", CELL, "--seed", str(seed), "--seconds", "0.01", *extra],
        root=checkout, device_check=_cpu_devices, compile_cache=lambda root: "off",
    )
    out = capsys.readouterr()
    assert rc == 0, out.err
    result = json.loads(out.out.strip().splitlines()[-1])
    assert list(result)[-1] == "checks"
    assert "check logit_gap" in out.err.strip().splitlines()[-2]
    return result


def _break(monkeypatch, fault):
    from repro.serving import steps

    make = steps.make_paged_helpers

    def broken(cfg, geom, codec="secded72", draft_cfg=None):
        h = make(cfg, geom, codec, draft_cfg=draft_cfg)
        ms, pf = h.multistep, h.prefill

        def multistep(params, tok, cache, lo, hi, par, *rest):
            toks, cache2, lo2, hi2, par2 = ms(params, tok, cache, lo, hi, par, *rest)
            if fault == "token":  # a token altered where it is produced
                return (toks + 1) % cfg.vocab, cache2, lo2, hi2, par2
            return toks, cache, lo, hi, par  # the step returns its state unchanged

        def prefill(params, tokens, cachem):
            tok, c = pf(params, tokens, cachem)
            half = (tokens.shape[0] + 1) // 2  # half of the batch left out
            return tok, {k: {n: a.at[:, half:].set(0) for n, a in v.items()} for k, v in c.items()}

        if fault == "half_batch":
            return type(h)(**{**h.__dict__, "prefill": prefill})
        return type(h)(**{**h.__dict__, "multistep": multistep})

    monkeypatch.setattr(steps, "make_paged_helpers", broken)


def test_sound_run_is_correct(checkout, monkeypatch, capsys):
    result = _run(checkout, monkeypatch, capsys)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] == 3
    assert result["checks"]["logit_gap"]["value"] <= LIMIT
    own = f"tokens_per_s.{CELL}"
    assert set(result["metrics"]) == {"setup_s", "tokens_per_s", "itl_ms", own}
    assert result["metrics"][own]["value"] == result["metrics"]["tokens_per_s"]["value"]


@pytest.mark.parametrize("fault", ["token", "state", "half_batch"])
def test_broken_timed_path_is_not_correct(checkout, monkeypatch, capsys, fault):
    _break(monkeypatch, fault)
    result = _run(checkout, monkeypatch, capsys)
    assert result["correct"] is False
    assert result["checks"]["logit_gap"]["value"] > LIMIT


def test_control_run_is_not_correct(checkout, monkeypatch, capsys):
    result = _run(checkout, monkeypatch, capsys, extra=("--control", "1"))
    assert result["correct"] is False
    assert result["failed"] == 0
    assert result["checks"]["logit_gap"]["value"] > LIMIT


def test_control_fails_the_limit_sound_runs_pass(checkout):
    with open(os.path.join(checkout, "bench/configs/tiny.json")) as f:
        cfg = json.load(f)
    rw = dense_lm.prepare(weights.make(cfg, 3, registry.load_family("dense")), cfg)
    gen = np.random.default_rng(0)
    items = []
    for _ in range(3):
        prompt = gen.integers(1, cfg["vocab_size"], 8)
        seq = list(prompt)
        for _ in range(12):  # greedy under the reference itself: gap 0
            seq.append(int(np.argmax(np.asarray(dense_lm.logits(rw, cfg, np.asarray(seq)))[-1])))
        items.append((prompt, np.asarray(seq[8:])))
    exact = correctness.widest_gap(dense_lm, rw, cfg, items, 24)
    control = correctness.widest_gap(dense_lm, rw, cfg, items, 24, cfg["precision"]["control"])
    assert exact["widest"] <= LIMIT < control["widest"]
