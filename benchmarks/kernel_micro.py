"""Pallas kernel micro-benchmarks + roofline model.

Every row is tagged with the kernel backend in force (``compiled`` on TPU,
``interpret`` on CPU; kernels/backend.py). On a CPU runner the wall-times are
interpret-lane numbers (NOT TPU performance); the derived
column reports the *kernel roofline model* for TPU v5e — the quantity used in
EXPERIMENTS.md §Perf to compare the fused ECC-matmul read path against the
naive decode-then-matmul baseline:

  naive  HBM bytes = planes(9B/8w) + int8 W write + int8 W read + x + out
  fused  HBM bytes = planes(9B/8w) + x + out          (decode lives in VMEM)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_line, emit, timed, use_compile_cache
from repro.kernels import backend as kbackend
from repro.kernels import ops, ref

HBM_BW = 819e9
PEAK = 197e12


def _roofline(m, k, n, fused: bool):
    planes = (k // 8) * n * 9  # lo+hi (8B) + parity (1B) per 8 int8 weights
    x_io = m * k * 4 + m * n * 4
    w_rt = 0 if fused else 2 * k * n  # int8 W write + read for naive
    t_mem = (planes + x_io + w_rt) / HBM_BW
    t_comp = 2 * m * k * n / PEAK
    return t_mem, t_comp


def voltage_sweep(n_steps: int = 10) -> dict:
    """Wall time + Pallas launch count for an N-step undervolt sweep on the
    paper NN config: historical per-leaf loop vs the batched arena path
    (one fused inject_scrub launch per step)."""
    import time

    from repro.configs import get_config
    from repro.core.nn_accel import EccMLP

    cfg = get_config("paper-nn")
    volts = np.linspace(0.60, 0.54, n_steps)
    out = {"kernel": "voltage_sweep", "steps": n_steps,
           "arch": cfg.name, "layer_sizes": list(cfg.layer_sizes)}
    # perleaf/batched share host (oracle) masks: pure kernel-count comparison;
    # "device" is the fully device-resident path (jax.random masks, no host
    # mask materialisation) — the production voltage-sweep configuration.
    for label, mask_source, batched in (
        ("perleaf", "host", False),
        ("batched", "host", True),
        ("device", "device", True),
    ):
        mlp = EccMLP(cfg.layer_sizes, platform=cfg.platform, seed=0,
                     mask_source=mask_source)
        mlp.store()  # untrained weights: we time the rail loop, not accuracy

        def sweep():
            for v in volts:
                mlp.set_voltage(float(v), batched=batched)

        sweep()  # warmup / compile
        ops.reset_launch_count()
        t0 = time.perf_counter()
        sweep()
        out[f"us_{label}"] = (time.perf_counter() - t0) * 1e6
        out[f"launches_{label}"] = ops.launch_count()
    out["launch_ratio"] = out["launches_perleaf"] / max(out["launches_batched"], 1)
    out["speedup"] = out["us_perleaf"] / out["us_batched"]
    out["speedup_device"] = out["us_perleaf"] / out["us_device"]
    return out


def run() -> list[dict]:
    rows = []
    rng = np.random.default_rng(0)
    # encode/decode planes
    for n_words in (1 << 14, 1 << 17):
        lo = jnp.asarray(rng.integers(0, 2**32, n_words, dtype=np.uint32))
        hi = jnp.asarray(rng.integers(0, 2**32, n_words, dtype=np.uint32))
        par, us_e = timed(lambda: jax.block_until_ready(ops.encode(lo, hi)))
        _, us_d = timed(lambda: jax.block_until_ready(ops.decode(lo, hi, par)))
        rows.append({"kernel": "secded_encode", "words": n_words, "us": us_e})
        rows.append({"kernel": "secded_decode", "words": n_words, "us": us_d})
    # fused inject+scrub vs the separate inject->decode pair it replaced.
    # `fused_over_pair` is the machine-independent metric the CI regression
    # gate tracks (benchmarks/check_regression.py): wall-clocks vary with the
    # runner, the fused/unfused ratio on the same process does not. Samples
    # are interleaved and the minimum taken — scheduler noise is strictly
    # additive, so min-of-n estimates the true cost where mean/median of a
    # few runs on a shared CI runner jitter by 2x.
    import time as _time

    def _interleaved_min(fa, fb, n=7, inner=3):
        fa(), fb()  # warmup / compile
        ta, tb = [], []
        for _ in range(n):
            t0 = _time.perf_counter()
            for _ in range(inner):
                fa()
            ta.append(_time.perf_counter() - t0)
            t0 = _time.perf_counter()
            for _ in range(inner):
                fb()
            tb.append(_time.perf_counter() - t0)
        return min(ta) / inner * 1e6, min(tb) / inner * 1e6

    for n_words in (1 << 14, 1 << 17):
        lo = jnp.asarray(rng.integers(0, 2**32, n_words, dtype=np.uint32))
        hi = jnp.asarray(rng.integers(0, 2**32, n_words, dtype=np.uint32))
        par = ops.encode(lo, hi)
        mlo = jnp.asarray(rng.integers(0, 2**32, n_words, dtype=np.uint32))
        mhi = jnp.asarray(rng.integers(0, 2**32, n_words, dtype=np.uint32))
        mpar = jnp.asarray(rng.integers(0, 256, n_words).astype(np.uint8))

        def fused():
            return jax.block_until_ready(
                ops.inject_scrub(lo, hi, par, mlo, mhi, mpar)[3]
            )

        def pair():
            flo, fhi, fpar = ops.inject(lo, hi, par, mlo, mhi, mpar)
            return jax.block_until_ready(ops.decode(flo, fhi, fpar)[2])

        us_f, us_p = _interleaved_min(fused, pair)
        rows.append(
            {
                "kernel": "inject_scrub", "words": n_words,
                "us": us_f, "us_pair": us_p,
                "fused_over_pair": us_f / us_p,
            }
        )
    # compiled-vs-interpret ratio on the flagship fused kernel (DESIGN.md
    # §18): `lane` is whatever backend.resolve() picks (compiled where a
    # Pallas lowering exists, interpret elsewhere), `interp` is forced
    # interpret. On an interpret-only host the two lanes are the same code
    # path and the ratio sits at ~1.0 — the trajectory row exists so a host
    # WITH a compiled lowering fails loudly if compiled ever regresses past
    # interpret (check_regression --only kernel).

    def lane():
        return jax.block_until_ready(
            ops.inject_scrub(lo, hi, par, mlo, mhi, mpar)[3]
        )

    def interp():
        return jax.block_until_ready(
            ops.inject_scrub(lo, hi, par, mlo, mhi, mpar, interpret=True)[3]
        )

    us_l, us_i = _interleaved_min(lane, interp)
    rows.append(
        {
            "kernel": "backend_ratio", "words": n_words,
            "us": us_l, "us_interpret": us_i,
            "compiled_over_interpret": us_l / us_i,
        }
    )
    # fused vs naive ecc_matmul
    for (m, k, n) in ((128, 1024, 512), (256, 2048, 1024)):
        x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((k, n)) * 0.05, jnp.float32)
        ew = ops.pack_ecc_weights(w)
        _, us_f = timed(lambda: jax.block_until_ready(ops.ecc_matmul(x, ew, fuse=True)), repeat=2)
        _, us_n = timed(lambda: jax.block_until_ready(ops.ecc_matmul(x, ew, fuse=False)), repeat=2)
        tm_f, tc = _roofline(m, k, n, fused=True)
        tm_n, _ = _roofline(m, k, n, fused=False)
        rows.append(
            {
                "kernel": "ecc_matmul", "mkn": [m, k, n],
                "us_fused_interp": us_f, "us_naive_interp": us_n,
                "tpu_model_mem_fused_s": tm_f, "tpu_model_mem_naive_s": tm_n,
                "tpu_model_compute_s": tc,
                "fused_traffic_saving": 1 - tm_f / tm_n,
            }
        )
    rows.append(voltage_sweep())
    for r in rows:  # every row carries the lowering it was measured under
        r.setdefault("backend", kbackend.tag())
    emit(rows, "kernel_micro")
    return rows


def main():
    rows = run()
    for r in rows:
        if r["kernel"] == "voltage_sweep":
            print(
                csv_line(
                    f"kernel/voltage_sweep_{r['steps']}step", r["us_batched"],
                    f"speedup_vs_perleaf={r['speedup']:.2f}x;"
                    f"device_resident={r['speedup_device']:.2f}x;"
                    f"launches={r['launches_batched']}vs{r['launches_perleaf']}"
                    f" ({r['launch_ratio']:.0f}x fewer)",
                )
            )
        elif r["kernel"] == "inject_scrub":
            print(
                csv_line(
                    f"kernel/inject_scrub_{r['words']}w", r["us"],
                    f"fused_over_pair={r['fused_over_pair']:.2f};"
                    f"pair_us={r['us_pair']:.1f};backend={r['backend']}",
                )
            )
        elif r["kernel"] == "backend_ratio":
            print(
                csv_line(
                    f"kernel/backend_ratio_{r['words']}w", r["us"],
                    f"compiled_over_interpret={r['compiled_over_interpret']:.2f};"
                    f"backend={r['backend']}",
                )
            )
        elif r["kernel"] == "ecc_matmul":
            m, k, n = r["mkn"]
            print(
                csv_line(
                    f"kernel/ecc_matmul_{m}x{k}x{n}", r["us_fused_interp"],
                    f"fused_vs_naive_hbm_saving={100 * r['fused_traffic_saving']:.1f}%;"
                    f"model_mem_fused={r['tpu_model_mem_fused_s']:.2e}s",
                )
            )
        else:
            print(csv_line(
                f"kernel/{r['kernel']}_{r['words']}w", r["us"],
                f"backend={r['backend']}",
            ))


if __name__ == "__main__":
    use_compile_cache()
    main()
