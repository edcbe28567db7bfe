"""Benchmark driver — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines per benchmark, mirroring the
paper's result set plus the kernel, mesh, and roofline sections, and writes
one ``BENCH_<name>.json`` trajectory file at the repo root per suite — the
perf trajectory consumed between PRs (each file carries the parsed rows, so
a regression is a one-line diff against the previous commit's file).

  fig1    fault rate vs voltage, 3 platforms, ECC on/off      (paper Fig. 1)
  fig2    fault-type histogram + FIP                          (paper Fig. 2b/2c)
  table1  ECC area/power overhead + derived savings           (paper Table I)
  fig3    NN accelerator error vs voltage, ECC on/off         (paper Fig. 3)
  kernels Pallas kernel micro + fused-vs-naive roofline model
  codecs  ECC scheme comparison: coverage vs overhead vs scrub throughput
  mesh    sharded-scrub throughput vs host-device count (DESIGN.md §13)
  accuracy LM output divergence vs voltage per codec (DESIGN.md §15)
  roofline dry-run roofline table (reads benchmarks/out/dryrun.json)
"""

from __future__ import annotations

import contextlib
import importlib
import io
import json
import os
import sys
import time

from benchmarks.common import use_compile_cache

# (section, module under benchmarks/). Modules are imported only when their
# section runs, so this process touches JAX no earlier than it must.
SECTIONS = [
    ("fig1", "fig1_fault_rate"),
    ("fig2", "fig2_fault_types"),
    ("table1", "table1_overhead"),
    ("fig3", "fig3_nn_accuracy"),
    ("kernels", "kernel_micro"),
    ("codecs", "codec_compare"),
    ("mesh", "sharded_scrub"),
    ("accuracy", "accuracy_campaign"),
    ("roofline", "roofline"),
]

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse_rows(text: str) -> list[dict]:
    """CSV lines (``name,us_per_call,derived``) -> row dicts; comment lines
    (``# ...``) and the header are dropped."""
    rows = []
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#") or line.startswith("name,"):
            continue
        parts = line.split(",", 2)
        if len(parts) != 3:
            continue
        try:
            us = float(parts[1])
        except ValueError:
            continue
        rows.append({"name": parts[0], "us_per_call": us, "derived": parts[2]})
    return rows


def write_trajectory(name: str, rows: list[dict], seconds: float,
                     root: str = REPO_ROOT) -> str:
    """Write one suite's ``BENCH_<name>.json`` at the repo root."""
    path = os.path.join(root, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(
            {"suite": name, "rows": rows, "seconds": round(seconds, 1)},
            f, indent=1,
        )
        f.write("\n")
    return path


def run_section(name: str, module: str) -> list[dict]:
    """Run one section, tee its CSV output, write its trajectory file."""
    mod = importlib.import_module(f"benchmarks.{module}")
    t0 = time.time()
    print(f"# === {name} ===")
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            mod.main()
    finally:
        # echo even when the section dies: its CSV rows and diagnostics are
        # the only record of what happened before the crash
        sys.stdout.write(buf.getvalue())
    rows = parse_rows(buf.getvalue())
    seconds = time.time() - t0
    path = write_trajectory(name, rows, seconds)
    print(f"# {name}: {len(rows)} rows -> {os.path.relpath(path, REPO_ROOT)} "
          f"({seconds:.1f}s)")
    return rows


def main() -> None:
    only = sys.argv[1] if len(sys.argv) > 1 else None
    use_compile_cache()
    print("name,us_per_call,derived")
    for name, module in SECTIONS:
        if only and name != only:
            continue
        run_section(name, module)


if __name__ == "__main__":
    main()
