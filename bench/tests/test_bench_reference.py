"""The plain reference against the program's model, at a small size on the
CPU: the same canonical weights through ``repro.models.lm`` (plain and
with the ECC-protected int8 weights the served engine holds) and through
``bench/reference/dense_lm.py``."""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchlib import program, registry, weights  # noqa: E402
from reference import dense_lm  # noqa: E402

DENSE = registry.load_family("dense")
SMALL = dict(hidden_size=128, intermediate_size=256, num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, head_dim=32, vocab_size=512, torch_dtype="float32")


def small_config(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    cfg.update(SMALL)
    return cfg


def _program_logits(params, cfg, tokens):
    import jax.numpy as jnp

    from repro.models import lm

    return np.asarray(
        lm.sequence_logits(params, jnp.asarray(tokens)[None], DENSE.model_config(cfg))[0]
    )


@pytest.mark.parametrize("name", ["qwen3-0.6b", "qwen2-7b"])
def test_reference_matches_program_forward(name):
    cfg = small_config(name)
    w = weights.make(cfg, 5, DENSE)
    tokens = np.random.default_rng(0).integers(0, cfg["vocab_size"], 24)
    got = _program_logits(DENSE.program_params(w, cfg), cfg, tokens)
    plain = dict(cfg, precision=dict(cfg["precision"], protected=[]))
    want = np.asarray(dense_lm.logits(dense_lm.prepare(w, plain), plain, tokens))
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-4 * np.abs(want).max())


@pytest.mark.parametrize("name", ["qwen3-0.6b", "qwen2-7b"])
def test_reference_applies_the_engines_int8_dequantisation(name):
    cfg = small_config(name)
    w = weights.make(cfg, 6, DENSE)
    eng = program.build_engine(cfg, DENSE.program_params(w, cfg), 64, DENSE)
    tokens = np.random.default_rng(1).integers(0, cfg["vocab_size"], 24)
    got = _program_logits(eng.params, cfg, tokens)
    want = np.asarray(dense_lm.logits(dense_lm.prepare(w, cfg), cfg, tokens))
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-4 * np.abs(want).max())
    # without the dequantisation the two differ by far more than that
    plain = dict(cfg, precision=dict(cfg["precision"], protected=[]))
    raw = np.asarray(dense_lm.logits(dense_lm.prepare(w, plain), plain, tokens))
    assert np.abs(raw - got).max() > 10 * 2e-4 * np.abs(want).max()


def test_control_rounding_moves_the_logits():
    cfg = small_config("qwen3-0.6b")
    rw = dense_lm.prepare(weights.make(cfg, 7, DENSE), cfg)
    tokens = np.arange(1, 20)
    exact = np.asarray(dense_lm.logits(rw, cfg, tokens))
    ctl = np.asarray(dense_lm.logits(rw, cfg, tokens, cfg["precision"]["control"]))
    assert np.abs(exact - ctl).max() > 1e-2 * np.abs(exact).max()
