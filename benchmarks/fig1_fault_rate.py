"""Paper Fig. 1: fault rate vs voltage for VC707 / KC705-A / KC705-B,
with and without built-in ECC.

The tested memory matches the paper's hardware design: 512 memories of
1024 x 64-bit words (full BRAM utilization on VC707). For each voltage in the
critical region we count raw faulty words and the residual (uncorrected)
faulty words after SECDED — the ECC bars of Fig. 1.

Two execution paths:
  * vmapped (default) — all (platform, voltage) grid points in one compiled
    `core.sweep` call per arena chunk (the fault field is generated once and
    thresholded V times, instead of V mask+decode dispatches);
  * loop — the historical per-voltage Python loop over the host FaultField
    oracle, kept as the reference the vmapped path is tolerance-checked
    against (tests/test_multirail.py).
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.common import csv_line, emit, timed, use_compile_cache
from repro.core import ecc, sweep, voltage
from repro.core.faultsim import FaultField
from repro.core.telemetry import FaultStats

N_WORDS = 512 * 1024  # 512 x (1024 x 64-bit) words


def _stats_at(field: FaultField, v: float) -> FaultStats:
    masks = field.masks(v)
    # ECC outcome: a 1-flip word corrects, >=2-flip words detect or alias.
    # Build statuses via the decoder on a zero memory (content-independent:
    # syndromes depend only on the flip pattern).
    import jax.numpy as jnp

    lo = jnp.asarray(masks.lo)
    hi = jnp.asarray(masks.hi)
    par = ecc.encode(jnp.zeros_like(lo), jnp.zeros_like(hi)) ^ jnp.asarray(masks.parity)
    _, _, status = ecc.decode(lo, hi, par)
    return FaultStats.from_decode(np.asarray(status), masks.flip_counts())


def _grid():
    """The paper's critical-region grid as flat (profile, voltage) pairs."""
    pairs = []
    for prof in voltage.PLATFORMS.values():
        vs = np.round(np.arange(prof.v_crash, prof.v_min + 1e-9, 0.01), 3)
        pairs.extend((prof, float(v)) for v in vs)
    return pairs


def _row(pname: str, v: float, st: FaultStats, prof, us: float) -> dict:
    mbits = N_WORDS * 72 / (1024 * 1024)
    # raw counters come from the shared serialization (telemetry.to_dict);
    # only the Fig. 1 derived metrics are computed here
    return {
        "platform": pname,
        "voltage": float(v),
        **st.to_dict(),
        "faults_per_mbit": st.faulty_bits / mbits,
        "residual_after_ecc": st.detected + st.silent,
        "ecc_reduction": 1.0 - (st.detected + st.silent) / max(st.faulty_words, 1),
        "model_rate_per_mbit": prof.faults_per_mbit(float(v)),
        "us": us,
    }


def run(vmapped: bool = True) -> list[dict]:
    if not vmapped:
        return run_loop()
    grid = _grid()
    sweep.sweep_platform_grid(grid, N_WORDS, 17)  # warmup / compile
    sweep.reset_dispatch_count()  # count exactly one sweep's dispatches
    t0 = time.perf_counter()
    points = sweep.sweep_platform_grid(grid, N_WORDS, 17)
    us = (time.perf_counter() - t0) * 1e6 / max(len(points), 1)
    rows = [
        _row(pt.platform, pt.voltage, pt.stats, prof, us)
        for (prof, _), pt in zip(grid, points)
    ]
    emit(rows, "fig1_fault_rate")
    return rows


def run_loop() -> list[dict]:
    """Reference path: per-voltage Python loop over the host oracle."""
    rows = []
    for pname, prof in voltage.PLATFORMS.items():
        field = FaultField(prof, N_WORDS, seed=17)
        vs = np.round(np.arange(prof.v_crash, prof.v_min + 1e-9, 0.01), 3)
        for v in vs:
            st, us = timed(_stats_at, field, float(v), repeat=1)
            rows.append(_row(pname, float(v), st, prof, us))
    emit(rows, "fig1_fault_rate")
    return rows


def main():
    rows = run()
    for r in rows:
        print(
            csv_line(
                f"fig1/{r['platform']}@{r['voltage']:.2f}V",
                r["us"],
                f"faults_per_mbit={r['faults_per_mbit']:.1f};"
                f"ecc_reduction={100 * r['ecc_reduction']:.1f}%",
            )
        )
    # headline anchors vs paper
    vc = [r for r in rows if r["platform"] == "vc707"]
    crash = vc[0]
    print(
        f"# VC707 @V_crash: {crash['faults_per_mbit']:.0f} faults/Mbit "
        f"(paper 652); ECC removes {100 * crash['ecc_reduction']:.1f}% "
        f"(paper >90% corrected)"
    )
    print(
        f"# vmapped sweep: {len(rows)} grid points in "
        f"{sweep.dispatch_count()} compiled dispatch(es) "
        f"(loop path: {len(rows)} mask+decode dispatches)"
    )


if __name__ == "__main__":
    use_compile_cache()
    main()
