"""Readings that the correctness limits are set from (not run by the
benchmark's own runs).

For each seed, in one process on the chip: one window of the cell's timed
path (the same warm-up-free ``serve()`` waves the benchmark drives), the
widest logit gap of the served tokens against the float32 reference, and
the widest gap of the control: the reference computed in the precision
below the one the configuration states (``precision.control``), read at the
same positions of the same sample.

    python3 bench/control.py --workload <cell> --seeds 101 102 103

One run of a cell with the control in the program's place, decided by the
harness's own checks, is ``bench/run.py ... --control 1``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

import run  # noqa: E402
from benchlib import correctness, program, registry, weights  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    bench = registry.load_benchmark(ROOT)
    work = registry.find_workload(bench, args.workload)
    cfg = registry.load_config(bench, work["config"], ROOT)
    family = registry.config_family(cfg, ROOT)
    reference = registry.load_reference(family.REFERENCE, ROOT)
    mix = registry.load_traffic(work["traffic"], ROOT)
    limits = registry.load_limits(args.workload, ROOT)
    run.check_devices(work["chips"])
    run.use_compile_cache(ROOT)

    for seed in args.seeds:
        params = family.program_params(weights.make(cfg, seed, family), cfg)
        eng = program.build_engine(cfg, params, mix["max_len"], family)
        del params
        cell = {"cfg": cfg, "mix": mix, "seed": seed}
        win = run.run_window(eng, cell, 0.0, trace=False)
        finished = [(p, s) for p, s, n in win["requests"] if s is not None and len(s) == n]
        del eng, win
        gc.collect()
        rw = reference.prepare(weights.make(cfg, seed, family), cfg)
        items = [finished[i] for i in correctness.sample(finished, seed, limits["sample_tokens"])]
        row = {
            "seed": seed,
            "program": correctness.widest_gap(reference, rw, cfg, items, mix["max_len"]),
            "control": correctness.widest_gap(
                reference, rw, cfg, items, mix["max_len"], cfg["precision"]["control"]
            ),
        }
        del rw
        gc.collect()
        print("READING " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
