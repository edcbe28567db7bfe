"""CPU tests of the benchmark harness: traffic waves, lookup by name, the
warm-up plan, and the refusal to run without a TPU.

No test here loads libtpu: JAX is held to the CPU by the test run.
"""

from __future__ import annotations

import collections
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

from benchlib import program, registry, traffic  # noqa: E402

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic")) if f.endswith(".json"))


@pytest.mark.parametrize("name", MIXES)
def test_wave_holds_histogram_exactly(name):
    mix = registry.load_traffic(name, ROOT)
    want = collections.Counter(traffic.pairs(mix))
    for seed in (0, 7, 2**31 + 12345, 10**12):
        wave = traffic.wave(mix, 1000, traffic.rng(seed, traffic.WINDOW, 3))
        got = collections.Counter((len(p), o) for p, o in wave)
        assert got == want
        assert all(p.dtype == np.int32 and p.min() >= 1 and p.max() < 1000 for p, _ in wave)
        assert all(len(p) + o <= mix["max_len"] for p, o in wave)


def test_seed_changes_only_ids():
    mix = registry.load_traffic("chat", ROOT)
    a = traffic.wave(mix, 151936, traffic.rng(1, traffic.WINDOW, 0))
    b = traffic.wave(mix, 151936, traffic.rng(2, traffic.WINDOW, 0))
    again = traffic.wave(mix, 151936, traffic.rng(1, traffic.WINDOW, 0))
    assert [(len(p), o) for p, o in a] == [(len(p), o) for p, o in b] == traffic.pairs(mix)
    assert not all(np.array_equal(p, q) for (p, _), (q, _) in zip(a, b))
    assert all(np.array_equal(p, q) and o == n for (p, o), (q, n) in zip(a, again))
    assert sum(len(p) for p, _ in a) == sum(p for p, _ in traffic.pairs(mix))


def test_request_longer_than_max_len_is_refused():
    with pytest.raises(ValueError):
        traffic.pairs({"max_len": 16, "lanes": 1, "requests": [[10, 10, 1]]})


def test_every_cell_resolves():
    bench = registry.load_benchmark(ROOT)
    for w in bench["workloads"]:
        cfg = registry.load_config(bench, w["config"], ROOT)
        mix = registry.load_traffic(w["traffic"], ROOT)
        limits = registry.load_limits(w["name"], ROOT)
        assert cfg["name"] == w["config"] and mix["lanes"] >= 1
        assert limits["logit_gap"] > 0
        for m in registry.metrics_for(bench, w["name"], trace=True):
            assert callable(registry.metric_reader(m["name"], ROOT))


def _copy_bench(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    return root


def test_new_config_traffic_and_metric_are_found_from_files_alone(tmp_path):
    root = _copy_bench(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "bench/configs/qwen3-0.6b.json").read_text())
    cfg.update(name="tiny-lm", num_hidden_layers=2)
    (root / "bench/configs/tiny-lm.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/burst.json").write_text(
        json.dumps({"lanes": 2, "max_len": 64, "requests": [[16, 8, 3]]})
    )
    (root / "bench/limits/tiny-lm.burst.json").write_text(
        json.dumps({"logit_gap": 1.0, "sample_tokens": 8})
    )
    (root / "bench/metrics/wave_count.py").write_text(
        "def read(ctx):\n    return float(len(ctx['reports']))\n"
    )
    bench["configs"].append(
        {"name": "tiny-lm", "source": "https://example.org/tiny", "file": "bench/configs/tiny-lm.json",
         "reduced": ["num_hidden_layers"], "why": "a test"}
    )
    bench["workloads"].append(
        {"name": "tiny-lm.burst", "config": "tiny-lm", "traffic": "burst", "chips": 1, "why": "a test"}
    )
    bench["per_layer"].append(
        {"name": "wave_count", "unit": "waves", "better": "higher", "source": "program_counter",
         "layer": "scheduler", "moves": "tokens_per_s", "workloads": ["tiny-lm.burst"]}
    )
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    bench = registry.load_benchmark(str(root))
    work = registry.find_workload(bench, "tiny-lm.burst")
    assert registry.load_config(bench, work["config"], str(root))["num_hidden_layers"] == 2
    assert traffic.pairs(registry.load_traffic(work["traffic"], str(root))) == [(16, 8)] * 3
    names = [m["name"] for m in registry.metrics_for(bench, "tiny-lm.burst", trace=True)]
    assert "wave_count" in names
    assert registry.metric_reader("wave_count", str(root))({"reports": [1, 2]}) == 2.0
    with pytest.raises(registry.LookupFailed):
        registry.find_workload(bench, "no-such-cell")


def test_warmup_plan_covers_every_group_size_and_page_bucket():
    cfg = {"serve": {"page_tokens": 8, "scrub_interval": 8}}
    mix = {"lanes": 4, "max_len": 64, "requests": [[16, 8, 3], [40, 24, 1]]}
    calls = program.warmup_requests(mix, cfg)
    groups = {(c[0][0], len(c)) for c in calls if all(o == 1 for _, o in c)}
    assert groups == {(16, 1), (16, 2), (16, 3), (40, 1)}
    decode = [c[0] for c in calls if c[0][1] > 1]
    # live pages of the mix: 3..3 for the 16-token prompts, 6..8 for the
    # 40-token one -> buckets 4 and 8
    buckets = {program._bucket(-(-(p + 9) // 8)) for p, _ in decode}
    assert buckets == {4, 8}
    assert all(p + o <= mix["max_len"] and o >= 16 for p, o in decode)


def test_run_refuses_without_a_tpu(capsys):
    import run

    rc = run.main(["--workload", "qwen3-0.6b.chat", "--seed", "1", "--seconds", "1"])
    out = capsys.readouterr()
    assert rc != 0
    assert "needs a TPU" in out.err
    assert out.out.strip() == ""
