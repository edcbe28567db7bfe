"""Sharded-scrub throughput: one fixed arena, sharded over 1 -> 8 devices.

Benchmarks the shard_map'd paged scrub-on-read step (distributed/meshrel.py):
every reliability shard gathers its own page rows from its slice of the
stacked KV planes, runs the Hsiao scrub kernel, and writes corrected planes
back — no plane word crosses a shard. The sweep is *strong scaling*: the
total arena (``--pages`` x ``--page-words`` words) is held fixed and split
evenly across the forced host devices, so every sweep point streams the
identical working set and the curve isolates what the gate exists to catch —
per-shard step overhead (an in-step collective, a materialized payload
output, per-shard dispatch bookkeeping) that grows with the shard count.
Weak scaling (fixed per-shard slice) is the wrong experiment on a
shared-cache host: total footprint then grows with the device count and the
curve measures which sweep points happen to fit the cache hierarchy, not the
scrub step. Each device count runs in its own subprocess
(``--xla_force_host_platform_device_count`` is locked at jax init). The
children are pinned to the CPU (``JAX_PLATFORMS=cpu``) and their rows say
``platform=cpu``: forced host devices are what this sweep measures, and a
child must never contend for an accelerator its parent may hold.

Timing is *steady state* (DESIGN.md §18): the step is built payload-free
(``with_payload=False`` — the scrub soak never reads the gathered page
payload, so the two largest outputs are dropped) and collective-free (no
in-step psum); after a compile warmup AND one dropped warm call, ``repeat``
calls are chain-dispatched — each feeds the previous call's corrected planes
forward — with a single ``block_until_ready`` at the end. That is exactly how
the serving scheduler drives the step (async dispatch, deferred harvest), and
it keeps per-call host dispatch overhead from polluting the high-device
points, where forced host devices multiply launch bookkeeping but not cores.

CSV rows: ``mesh_scrub_d<N>,us_per_call,words_per_s=...`` (tagged with the
kernel backend in force) plus the scaling summary row the nightly trajectory
tracks.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

from benchmarks.common import csv_line, emit, use_compile_cache

DEFAULT_DEVICES = (1, 2, 4, 8)


def _worker(
    n_devices: int, total_pages: int, page_words: int, repeat: int,
    groups: int = 5,
) -> None:
    """Runs inside a subprocess with ``n_devices`` forced host devices."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.distributed import meshrel
    from repro.launch.mesh import make_reliability_mesh

    assert len(jax.devices()) == n_devices, (len(jax.devices()), n_devices)
    assert total_pages % n_devices == 0, (total_pages, n_devices)
    # strong scaling: the arena is fixed, each shard owns total/n of it
    n_pages = total_pages // n_devices
    mesh = make_reliability_mesh(n_devices)
    sharding = meshrel.arena_sharding(mesh)
    local_words = n_pages * page_words
    total = n_devices * local_words
    rng = np.random.default_rng(0)
    lo = jax.device_put(
        jnp.asarray(rng.integers(0, 1 << 32, size=total, dtype=np.uint32)), sharding
    )
    hi = jax.device_put(
        jnp.asarray(rng.integers(0, 1 << 32, size=total, dtype=np.uint32)), sharding
    )
    from repro.kernels import ops as kops

    par = jax.device_put(kops.encode(lo, hi), sharding)
    # every shard scrubs all of its local pages each call
    table = jax.device_put(
        jnp.tile(jnp.arange(n_pages, dtype=jnp.int32)[None], (n_devices, 1)),
        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("data")),
    )
    from repro.kernels import backend as kbackend

    base = meshrel.make_kv_scrub_step(
        mesh, page_words, local_words, n_pages, with_payload=False
    )
    # donate the incoming planes: the chain feeds corrected planes forward
    # and never rereads old ones, so XLA reuses the buffers in place instead
    # of allocating (and page-faulting) fresh multi-MB outputs every call —
    # the same §18 donation contract the serving PlaneStore uses
    step = jax.jit(lambda l, h, p, t: base(l, h, p, t), donate_argnums=(0, 1, 2))
    olo, ohi, opar, cnt = step(lo, hi, par, table)
    jax.block_until_ready(cnt)  # warmup: compile
    # one more dropped call: the first post-compile dispatch still pays
    # executable/dispatch-cache population, which would otherwise dominate
    # repeat=1 smoke runs and the high-device points
    olo, ohi, opar, cnt = step(olo, ohi, opar, table)
    jax.block_until_ready(cnt)
    # steady state: chain-dispatch `repeat` calls (planes feed forward, as
    # the scheduler's async scrub does) and synchronize once per group.
    # min over groups: scheduler noise on a shared host is strictly
    # additive, so the fastest group estimates the true steady-state cost
    # (same rationale as kernel_micro's interleaved-min)
    best = float("inf")
    for _ in range(max(groups, 1)):
        t0 = time.perf_counter()
        for _ in range(repeat):
            olo, ohi, opar, cnt = step(olo, ohi, opar, table)
        jax.block_until_ready(cnt)
        best = min(best, time.perf_counter() - t0)
    us = best / repeat * 1e6
    print(json.dumps({
        "devices": n_devices,
        "us_per_call": us,
        "words_scrubbed": total,
        "words_per_s": total / (us / 1e6),
        "clean_words": int(np.asarray(cnt)[..., 0].sum()),
        "backend": kbackend.tag(),
        "platform": jax.devices()[0].platform,
    }))


def run_points(
    devices, n_pages: int, page_words: int, repeat: int, groups: int = 5,
    trials: int = 1,
) -> list[dict]:
    """One subprocess per (device count, trial); trials are interleaved
    round-robin across device counts and the per-point minimum taken, so a
    slow patch on a shared host hits every sweep point fairly instead of
    sinking whichever point it coincided with."""
    best: dict[int, dict] = {}
    for _ in range(max(trials, 1)):
        for n in devices:
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            # preserve unrelated XLA flags; only the forced count is ours
            kept = [
                f for f in env.get("XLA_FLAGS", "").split()
                if not f.startswith("--xla_force_host_platform_device_count")
            ]
            env["XLA_FLAGS"] = " ".join(
                kept + [f"--xla_force_host_platform_device_count={n}"]
            )
            env["PYTHONPATH"] = os.pathsep.join(
                p for p in (
                    os.path.join(os.path.dirname(__file__), "..", "src"),
                    os.path.join(os.path.dirname(__file__), ".."),
                    env.get("PYTHONPATH", ""),
                ) if p
            )
            out = subprocess.run(
                [
                    sys.executable, "-m", "benchmarks.sharded_scrub",
                    "--worker", "--devices", str(n), "--pages", str(n_pages),
                    "--page-words", str(page_words), "--repeat", str(repeat),
                    "--groups", str(groups),
                ],
                capture_output=True, text=True, env=env, timeout=900,
            )
            assert out.returncode == 0, out.stderr[-2000:]
            row = json.loads(out.stdout.strip().splitlines()[-1])
            if n not in best or row["us_per_call"] < best[n]["us_per_call"]:
                best[n] = row
    return [best[n] for n in devices]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--devices", type=int, default=0,
                    help="single device count (worker / one-point mode)")
    ap.add_argument("--max-devices", type=int, default=8)
    # TOTAL arena pages, split across shards (strong scaling; must divide by
    # every sweep device count). 256 x 4096 words ~ 9.4 MB of planes: past
    # L2 so the steady state is LLC-bound at every point, identical at every
    # point so the curve measures the scrub step, not the cache hierarchy
    ap.add_argument("--pages", type=int, default=256)
    ap.add_argument("--page-words", type=int, default=4096)
    ap.add_argument("--repeat", type=int, default=3)
    ap.add_argument("--groups", type=int, default=5,
                    help="timing groups per point (min taken)")
    ap.add_argument("--trials", type=int, default=3,
                    help="interleaved subprocess trials per point (min taken)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny geometry (CI: exercise the path, not the clock)")
    # parse_known_args: benchmarks.run passes its section name through argv
    args, _ = ap.parse_known_args(argv)
    if args.smoke:
        # chained dispatch makes extra repeats nearly free; 4 of them keep
        # the tiny-geometry points from being one-dispatch noise. The arena
        # must stay big enough that the d8 point (pages/8 per shard) is not
        # pure dispatch bookkeeping, or the smoke floor turns into a
        # dispatch-overhead lottery
        args.pages, args.page_words, args.repeat, args.groups = 128, 512, 4, 2
        args.trials = 1
    if args.worker:
        _worker(args.devices, args.pages, args.page_words, args.repeat,
                args.groups)
        return
    devices = [n for n in DEFAULT_DEVICES if n <= args.max_devices]
    if args.devices:
        devices = [args.devices]
    rows = run_points(devices, args.pages, args.page_words, args.repeat,
                      args.groups, args.trials)
    for r in rows:
        print(csv_line(
            f"mesh_scrub_d{r['devices']}", r["us_per_call"],
            f"words_per_s={r['words_per_s']:.3e};"
            f"backend={r['backend']};platform={r['platform']}",
        ))
    if len(rows) > 1:
        scale = rows[-1]["words_per_s"] / rows[0]["words_per_s"]
        print(csv_line(
            f"mesh_scrub_scaling_{rows[0]['devices']}to{rows[-1]['devices']}",
            0.0, f"throughput_ratio={scale:.2f}",
        ))
    emit(rows, "sharded_scrub")


if __name__ == "__main__":
    use_compile_cache()
    main()
