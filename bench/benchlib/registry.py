"""Find a cell, its configuration, its traffic mix and the per-layer metric
readers by the names ``BENCHMARK.json`` gives them.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own under ``bench/``:

  bench/configs/<config>.json   sizes, precision, protection, serve settings,
                                and ``family``: the layer structure
  bench/families/<family>.py    weight layout, program adapter, FLOP count
                                and the name of the plain reference
  bench/reference/<module>.py   the plain reference a family names
  bench/traffic/<traffic>.json  wave composition (lengths, lanes, max_len)
  bench/metrics/<metric>.py     a reader ``read(ctx) -> float | None``

so a new cell, configuration, layer structure or metric is added with files
and entries only.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class LookupFailed(KeyError):
    """A name in BENCHMARK.json has no entry or no file."""


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find_workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise LookupFailed(f"no workload {name!r} in BENCHMARK.json")


def find_config_entry(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return c
    raise LookupFailed(f"no config {name!r} in BENCHMARK.json")


def _load_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise LookupFailed(f"missing file {path}")
    with open(path) as f:
        return json.load(f)


def load_config(bench: dict, name: str, root: str = ROOT) -> dict:
    """The configuration file named by the config entry's ``file``."""
    entry = find_config_entry(bench, name)
    cfg = _load_json(os.path.join(root, entry["file"]))
    if cfg.get("name") != name:
        raise LookupFailed(f"{entry['file']} names {cfg.get('name')!r}, not {name!r}")
    return cfg


def load_traffic(name: str, root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "bench", "traffic", f"{name}.json"))


def load_limits(workload: str, root: str = ROOT) -> dict:
    """The correctness limits of a cell (``bench/limits/<workload>.json``),
    set from readings of the program and of the control."""
    return _load_json(os.path.join(root, "bench", "limits", f"{workload}.json"))


def metrics_for(bench: dict, workload: str, trace: bool) -> list[dict]:
    """The metric entries a run of ``workload`` reports: the end-to-end ones
    untraced, the per-layer ones traced (each limited to its ``workloads``
    list when it has one)."""
    entries = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in entries if workload in m.get("workloads", [workload])]


def _import(path: str, module_name: str, what: str):
    if not os.path.isfile(path):
        raise LookupFailed(f"missing {what} {path}")
    spec = importlib.util.spec_from_file_location(module_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: str = ROOT):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    path = os.path.join(root, "bench", "metrics", f"{name}.py")
    return _import(path, f"bench_metric_{name}", "metric reader").read


@functools.lru_cache(maxsize=None)
def load_family(name: str, root: str = ROOT):
    """The module ``bench/families/<name>.py`` (its interface is in
    ``bench/families/dense.py``); imported once per file."""
    path = os.path.join(root, "bench", "families", f"{name}.py")
    return _import(path, f"bench_family_{name}", "family")


def config_family(cfg: dict, root: str = ROOT):
    """The family module that a configuration names under ``family``."""
    if "family" not in cfg:
        raise LookupFailed(f"config {cfg.get('name')!r} has no key 'family'")
    return load_family(cfg["family"], root)


@functools.lru_cache(maxsize=None)
def load_reference(name: str, root: str = ROOT):
    """The plain reference ``bench/reference/<name>.py``: ``prepare(w, cfg)``
    and ``logits(rw, cfg, tokens, rounding=None)``; imported once per file."""
    path = os.path.join(root, "bench", "reference", f"{name}.py")
    return _import(path, f"bench_reference_{name}", "reference")
