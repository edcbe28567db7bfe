"""Families: a configuration names its layer structure under ``family``, and
the harness finds the weight layout, the program adapter, the FLOP count
and the plain reference by that name (``bench/families/<family>.py``).

The digests below were recorded on the CPU before the dense code moved into
``bench/families/dense.py``: the move changes no weight, no parameter of the
program's tree and no request's FLOP count. The last test adds a family, its
reference and a configuration as new files in a checkout and runs a cell
through them on the CPU, untraced and traced, with no harness file edited.
"""

from __future__ import annotations

import hashlib
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
from benchlib import costs, registry, tracing, weights  # noqa: E402

CONFIGS = [c["name"] for c in registry.load_benchmark(ROOT)["configs"]]
SMALL = dict(name="tiny", hidden_size=128, intermediate_size=256, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, head_dim=32, vocab_size=512,
             torch_dtype="float32")

# sha256 of every leaf (path, shape, dtype, bytes) at SMALL widths, seed 11
DIGESTS = {
    "qwen3-0.6b": ("a7f5cf2e6a170fff4fa941c37e50a68ba3a35481a93f546b74a09465c97ac2e6",
                   "25ea27d943e310c0d56c56ccca61d50189457ec749a5c50f15064563ddeeb2d3"),
    "qwen2-7b": ("8a1e17d8658ff6902077aebbc12a2db1b550d9fbda7dee00a5a648bab4e42204",
                 "efadf3b498b062fcaf2c5eddf48f0a6c7fdb9a2a4ae7add2f640e2587b1da2e4"),
}
# costs.request_flops of the published-width configs at (prompt, output)
PAIRS = ((1, 1), (32, 96), (128, 32), (768, 8), (1536, 32))
FLOPS = {
    "qwen3-0.6b": (1192198144.0, 153244401664.0, 192440696832.0, 992749158400.0,
                   2149609897984.0),
    "qwen2-7b": (2469992448.0, 314262552576.0, 393629171712.0, 1935742771200.0,
                 3958426730496.0),
}


def _config(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def _digest(tree) -> str:
    import jax

    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in sorted(leaves, key=lambda kv: jax.tree_util.keystr(kv[0])):
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)}|{a.shape}|{a.dtype}|".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", CONFIGS)
def test_every_config_resolves_to_a_family_and_its_reference(name):
    bench = registry.load_benchmark(ROOT)
    family = registry.config_family(registry.load_config(bench, name, ROOT), ROOT)
    reference = registry.load_reference(family.REFERENCE, ROOT)
    for fn in ("shapes", "model_config", "program_params", "matmul_params", "token_flops"):
        assert callable(getattr(family, fn))
    assert isinstance(family.KINDS, dict)
    assert callable(reference.prepare) and callable(reference.logits)


def test_config_without_family_or_with_a_missing_one_is_refused():
    cfg = _config("qwen3-0.6b")
    del cfg["family"]
    with pytest.raises(registry.LookupFailed, match="'family'"):
        registry.config_family(cfg)
    with pytest.raises(registry.LookupFailed, match="families/no-such-family.py"):
        registry.config_family(dict(cfg, family="no-such-family"))
    with pytest.raises(registry.LookupFailed, match="reference/no-such-reference.py"):
        registry.load_reference("no-such-reference")


@pytest.mark.parametrize("name", sorted(DIGESTS))
def test_dense_weights_and_program_tree_are_unchanged_by_the_move(name):
    cfg = dict(_config(name), **SMALL)
    family = registry.config_family(cfg, ROOT)
    w = weights.make(cfg, 11, family)
    assert (_digest(w), _digest(family.program_params(w, cfg))) == DIGESTS[name]


@pytest.mark.parametrize("name", sorted(FLOPS))
def test_dense_request_flops_are_unchanged_by_the_move(name):
    cfg = _config(name)
    family = registry.config_family(cfg, ROOT)
    assert tuple(costs.request_flops(cfg, p, o, family) for p, o in PAIRS) == FLOPS[name]


# A family of files alone: the dense decoder, its norm gains drawn by a kind
# of its own (log-normal, so always positive), and its own reference module.
TOY_FAMILY = '''
import os

import jax
import jax.numpy as jnp

from benchlib import registry

_dense = registry.load_family(
    "dense", os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)
REFERENCE = "toy_lm"
KINDS = {"positive_gain": lambda key, shape: jnp.exp(0.1 * jax.random.normal(key, shape))}
model_config = _dense.model_config
program_params = _dense.program_params
matmul_params = _dense.matmul_params
token_flops = _dense.token_flops


def shapes(cfg):
    return {
        leaf: (shape, "positive_gain" if kind == "gain" else kind)
        for leaf, (shape, kind) in _dense.shapes(cfg).items()
    }
'''
TOY_CELL = "toy.burst"


@pytest.fixture(scope="module")
def toy_checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("toy_checkout")
    shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench/families/toy.py").write_text(TOY_FAMILY)
    shutil.copy(root / "bench/reference/dense_lm.py", root / "bench/reference/toy_lm.py")
    (root / "bench/configs/toy.json").write_text(
        json.dumps({**_config("qwen3-0.6b"), **SMALL, "name": "toy", "family": "toy"})
    )
    (root / "bench/traffic/burst.json").write_text(
        json.dumps({"lanes": 2, "max_len": 48, "requests": [[8, 40, 3]]})
    )
    (root / f"bench/limits/{TOY_CELL}.json").write_text(
        json.dumps({"logit_gap": 0.01, "sample_tokens": 120})
    )
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "toy", "source": "https://example.org/toy",
                         "file": "bench/configs/toy.json", "reduced": [], "why": "test"}]
    bench["workloads"] = [{"name": TOY_CELL, "config": "toy", "traffic": "burst", "chips": 1,
                           "why": "test"}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def test_a_family_of_files_alone_draws_its_own_kind(toy_checkout):
    import jax

    bench = registry.load_benchmark(toy_checkout)
    cfg = registry.load_config(bench, "toy", toy_checkout)
    toy = registry.config_family(cfg, toy_checkout)
    dense = registry.load_family("dense", toy_checkout)
    got = weights.make(cfg, 4, toy)
    plain = weights.make(cfg, 4, dense)
    layout = sorted(toy.shapes(cfg).items())
    keys = jax.random.split(jax.random.PRNGKey(weights.key_seed(4)), len(layout))
    for key, (leaf, (shape, kind)) in zip(keys, layout):
        if kind == "positive_gain":
            want = np.exp(0.1 * np.asarray(jax.random.normal(key, shape)))
            np.testing.assert_allclose(np.asarray(got[leaf]), want, rtol=1e-6)
            assert np.asarray(got[leaf]).min() > 0
        else:  # every other leaf keeps its key and its shared kind's draw
            np.testing.assert_array_equal(np.asarray(got[leaf]), np.asarray(plain[leaf]))
    assert {kind for _, (_, kind) in layout} == {"matrix", "table", "positive_gain"}


def _cpu_devices(chips):
    import jax

    return jax.devices()


def _toy_run(toy_checkout, monkeypatch, capsys, trace: int) -> dict:
    monkeypatch.setattr(costs, "peaks", lambda kind: {"bf16_flops_per_s": 197e12,
                                                      "hbm_bytes_per_s": 819e9})
    rc = run.main(
        ["--workload", TOY_CELL, "--seed", str(2**31 + 7), "--seconds", "0.01",
         "--trace", str(trace)],
        root=toy_checkout, device_check=_cpu_devices, compile_cache=lambda root: "off",
    )
    out = capsys.readouterr()
    assert rc == 0, out.err
    result = json.loads(out.out.strip().splitlines()[-1])
    assert result["correct"] is True, out.err
    assert result["failed"] == 0 and result["attempted"] == 3
    assert result["checks"]["logit_gap"]["value"] <= 0.01
    return result


def test_a_family_of_files_alone_runs_a_cell_correct(toy_checkout, monkeypatch, capsys):
    _toy_run(toy_checkout, monkeypatch, capsys, trace=0)


def _host_only_reduce(pd, max_gaps=10):
    """A CPU trace has no device plane: the window from the host plane's
    window span, the device taken as busy all through it."""
    (window,) = [
        (int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
        for plane in pd.planes if plane.name == tracing.HOST_PLANE
        for line in plane.lines for ev in line.events if ev.name == tracing.WINDOW_SPAN
    ]
    return tracing.Reduced(window, 1, float(window[1] - window[0]), {}, {}, [], [])


def test_a_family_of_files_alone_is_counted_in_a_traced_run(toy_checkout, monkeypatch, capsys):
    """``serve_mfu`` counts the toy family's FLOPs, handed on by the run: the
    family exists only in the toy checkout, so a lookup anywhere else fails."""
    monkeypatch.setattr(tracing, "reduce", _host_only_reduce)
    result = _toy_run(toy_checkout, monkeypatch, capsys, trace=1)
    bench = registry.load_benchmark(toy_checkout)
    cfg = registry.load_config(bench, "toy", toy_checkout)
    toy = registry.config_family(cfg, toy_checkout)
    with pytest.raises(registry.LookupFailed, match="families/toy.py"):
        registry.config_family(cfg, ROOT)
    (prompt, output, count), = registry.load_traffic("burst", toy_checkout)["requests"]
    flops = count * costs.request_flops(cfg, prompt, output, toy)
    want = 100.0 * flops / result["device"]["window_s"] / 197e12
    assert result["metrics"]["serve_mfu"]["value"] == pytest.approx(want, rel=1e-12)
    assert result["metrics"]["lane_occupancy"]["value"] > 0
