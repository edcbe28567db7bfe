"""Run one benchmark cell on the chip and print one JSON result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 bench/run.py ... --control 1   # the control: must read correct false

Order of work, all in this one process:

  1. the cell, its configuration, the configuration's family with its plain
     reference, and its traffic mix are looked up by name (BENCHMARK.json,
     bench/configs/, bench/families/, bench/reference/, bench/traffic/);
  2. JAX must find a TPU with as many chips as the cell asks for, or the run
     fails with no result;
  3. the persistent compilation cache is turned on at a fixed path in the
     checkout (or JAX_COMPILATION_CACHE_DIR when that is set);
  4. weights are made on the device from the seed, the protected
     ``ServingEngine`` is built, and every shape the cell's waves can reach
     is warmed up: that is ``setup_s``;
  5. the window: back-to-back waves, each one ``serve()`` call, started
     until ``--seconds`` have passed; the wave in flight then finishes and
     counts. With ``--trace 1`` the window is traced by the profiler and the
     per-layer metrics are read from it;
  6. the device's peak memory is read, the program's state is freed, and a
     seeded sample of the finished requests is compared with the family's
     plain float32 reference (bench/benchlib/correctness.py). With ``--control 1``
     the reference computed one precision lower (``precision.control``) is
     compared in the program's place, at the same positions, through the
     same checks and limits, so that run has to read ``correct: false``;
  7. the compared numbers and their limits are printed on standard error,
     and the result is the last line of standard output.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
# the TPU runtime otherwise writes its logs to a fixed directory under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from benchlib import registry  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def check_devices(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devices)}")
    return devices


def use_compile_cache(root: str) -> str:
    """JAX_COMPILATION_CACHE_DIR when set, else a fixed directory in the
    checkout: the path is part of the cache key, so it never moves."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts backend compilations (a persistent-cache load counts too:
    either means a program was not ready in this process)."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.n += 1


def run_window(eng, cell, seconds: float, trace: bool):
    """Waves until ``seconds`` have elapsed; the last one finishes. Keeps
    each wave's outputs and decode steps, not its report: a report holds
    the wave's KV arena."""
    import types

    import jax

    from benchlib import program, traffic

    cfg, mix = cell["cfg"], cell["mix"]
    done, reports, waves, ends = [], [], [], []
    ctx = jax.profiler.TraceAnnotation if trace else None
    t0 = time.perf_counter()
    i = 0
    while True:
        reqs = traffic.wave(mix, cfg["vocab_size"], traffic.rng(cell["seed"], traffic.WINDOW, i))
        if ctx:
            with ctx("bench.wave"):
                rep = program.serve(eng, reqs, mix, cfg)
        else:
            rep = program.serve(eng, reqs, mix, cfg)
        reports.append(types.SimpleNamespace(outputs=rep.outputs, steps=rep.steps))
        waves.append(reqs)
        del rep
        i += 1
        ends.append(time.perf_counter() - t0)
        if ends[-1] >= seconds:
            break
    elapsed = ends[-1]
    wave_s = [b - a for a, b in zip([0.0] + ends, ends)]
    for reqs, rep in zip(waves, reports):
        for rid, (prompt, n_out) in enumerate(reqs):
            served = rep.outputs.get(rid)
            done.append((prompt, served, n_out))
    return {"elapsed": elapsed, "wave_s": wave_s, "reports": reports, "waves": waves,
            "requests": done}


def main(argv=None, *, root: str = ROOT, device_check=check_devices,
         compile_cache=use_compile_cache) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")

    bench = registry.load_benchmark(root)
    work = registry.find_workload(bench, args.workload)
    cfg = registry.load_config(bench, work["config"], root)
    family = registry.config_family(cfg, root)
    reference = registry.load_reference(family.REFERENCE, root)
    mix = registry.load_traffic(work["traffic"], root)
    limits = registry.load_limits(args.workload, root)
    wanted = registry.metrics_for(bench, args.workload, bool(args.trace))

    try:
        devices = device_check(work["chips"])
    except NoChip as err:
        print(f"bench: {err}", file=sys.stderr)
        return 3
    import jax

    dev = devices[0]
    cache = compile_cache(root)
    counter = CompileCounter()
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; compile cache {cache}", flush=True)

    from benchlib import correctness, costs, program, tracing, weights

    pk = costs.peaks(dev.device_kind)
    cell = {"cfg": cfg, "mix": mix, "seed": args.seed}

    # -- set-up: weights, engine, warm-up ------------------------------------
    params = family.program_params(weights.make(cfg, args.seed, family), cfg)
    eng = program.build_engine(cfg, params, mix["max_len"], family)
    del params
    n_warm = program.warm_up(eng, mix, cfg, cfg["vocab_size"], args.seed)
    setup_s = time.perf_counter() - T_START
    print(f"setup: {setup_s:.3f} s, {n_warm} warm-up serve calls, {counter.n} compilations", flush=True)

    # -- the window ----------------------------------------------------------
    compiles_before = counter.n
    trace_dir = os.path.join(root, ".bench_trace", args.workload)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
            win = run_window(eng, cell, args.seconds, trace=True)
        jax.profiler.stop_trace()
    else:
        win = run_window(eng, cell, args.seconds, trace=False)
    in_window = counter.n - compiles_before
    print(f"compilations in window: {in_window}", flush=True)
    mem = dev.memory_stats() or {}
    peak_bytes = int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices))

    reqs = win["requests"]
    finished = [(p, s) for p, s, n in reqs if s is not None and len(s) == n]
    steps = sum(r.steps for r in win["reports"])
    gen_tokens = sum(len(s) for _, s in finished)
    metrics = {}
    red = None
    if args.trace:
        red = tracing.reduce(tracing.load(trace_dir))
        ctx = {
            "reduced": red, "reports": win["reports"], "waves": win["waves"],
            "cfg": cfg, "family": family, "mix": mix, "peaks": pk,
        }
        for m in wanted:
            value = registry.metric_reader(m["name"], root)(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        shutil.rmtree(trace_dir, ignore_errors=True)
        del ctx
    else:
        e2e = {
            "setup_s": setup_s,
            "tokens_per_s": gen_tokens / win["elapsed"],
            "itl_ms": 1e3 * win["elapsed"] / max(steps, 1),
        }
        for m in wanted:  # "<quantity>.<cell>" is <quantity> under a bound of its own
            metrics[m["name"]] = {"value": e2e[m["name"].split(".")[0]], "unit": m["unit"]}
    print(
        f"window: {win['elapsed']:.3f} s, {len(win['waves'])} waves, {len(reqs)} requests, "
        f"{gen_tokens} generated tokens, {steps} decode steps; peak memory {peak_bytes} "
        f"of {mem.get('bytes_limit')} bytes; wave seconds {[round(s, 3) for s in win['wave_s']]}",
        flush=True,
    )

    # -- correctness: the reference once the program's state is freed -------
    del eng, win
    gc.collect()
    rw = reference.prepare(weights.make(cfg, args.seed, family), cfg)
    picked = correctness.sample(finished, args.seed, limits["sample_tokens"])
    rounding = cfg["precision"]["control"] if args.control else None
    gap = correctness.widest_gap(reference, rw, cfg, [finished[i] for i in picked], mix["max_len"],
                                 rounding)
    failed = len(reqs) - len(finished)
    checks = {
        "logit_gap": {"value": gap["widest"], "limit": limits["logit_gap"]},
        "unfinished": {"value": failed, "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    print(f"sample: {len(picked)} requests, {gap['tokens']} served tokens"
          f"{'; CONTROL ' + rounding if rounding else ''}; "
          f"per-request widest gaps {gap['per_request']}", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)

    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak_bytes,
    }
    result = {"correct": correct, "attempted": len(reqs), "failed": failed,
              "metrics": metrics, "device": device}
    if red is not None:
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        result["breakdown"] = tracing.breakdown(red)
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
