"""Paper Fig. 3: NN classification error vs BRAM voltage, with/without ECC.

Trains the paper's MLP accelerator on the synthetic-MNIST task (DESIGN.md
§10: real MNIST unavailable offline; fault-free error calibrated near the
paper's 2.56%), stores int8 weights SECDED-encoded, then sweeps V_CCBRAM
through the critical region measuring classification error and modeled
power. The `fuse=True` read path exercises the Pallas decode-matmul kernel
in interpret mode.

Divergence rows: each point also carries ``divergence_vs_clean`` — the
shared campaign scorer (core/campaign.label_divergence, the classifier form
of the LM campaign's token divergence) against the fault-free predictions —
and the scorer version, so this figure and BENCH_accuracy.json measure
quality loss in the same units (DESIGN.md §15).
"""

from __future__ import annotations

import numpy as np

from benchmarks.common import csv_line, emit, timed, use_compile_cache
from repro.core import campaign, voltage
from repro.core.nn_accel import EccMLP
from repro.data import mnist

N_TRAIN, N_TEST, STEPS = 20000, 4000, 600


def run() -> list[dict]:
    xtr, ytr = mnist.make_dataset(N_TRAIN, split="train")
    xte, yte = mnist.make_dataset(N_TEST, split="test")
    mlp = EccMLP((784, 256, 128, 10), platform="vc707", seed=0)
    mlp.train(xtr, ytr, steps=STEPS)
    prof = voltage.PLATFORMS["vc707"]

    rows = []
    mlp.set_voltage(prof.v_nom, ecc=True)
    pred0, us0 = timed(mlp.predict, xte, repeat=1)
    err0 = float((pred0 != yte).mean())
    rows.append(
        {"voltage": prof.v_nom, "err_free": err0, "us": us0,
         "power_w": mlp.power_w(),
         "scorer_version": campaign.SCORER_VERSION}
    )
    vs = np.round(np.arange(prof.v_crash, prof.v_min + 1e-9, 0.01), 3)
    for v in vs[::-1]:
        mlp.set_voltage(float(v), ecc=True)
        pred_ecc, us = timed(mlp.predict, xte, repeat=1)
        err_ecc = float((pred_ecc != yte).mean())
        p_ecc = mlp.power_w()
        mlp.set_voltage(float(v), ecc=False)
        pred_raw = mlp.predict(xte)
        err_raw = float((pred_raw != yte).mean())
        rows.append(
            {
                "voltage": float(v),
                "err_ecc": err_ecc,
                "err_no_ecc": err_raw,
                "err_free": err0,
                # quality loss in the campaign's units: prediction churn vs
                # the clean run, not error vs labels (a faulty model can get
                # lucky on labels; it cannot get lucky on the clean output)
                "divergence_vs_clean": campaign.label_divergence(pred0, pred_ecc),
                "divergence_no_ecc": campaign.label_divergence(pred0, pred_raw),
                "scorer_version": campaign.SCORER_VERSION,
                **mlp.stats.coverage_row(),
                "power_w": p_ecc,
                "bram_saving_vs_vmin": voltage.power_saving(prof.v_min, float(v), ecc=True),
                "us": us,
            }
        )
    emit(rows, "fig3_nn_accuracy")
    return rows


def main():
    rows = run()
    for r in rows[1:]:
        print(
            csv_line(
                f"fig3/vc707@{r['voltage']:.2f}V", r["us"],
                f"err_ecc={100 * r['err_ecc']:.2f}%;err_no_ecc={100 * r['err_no_ecc']:.2f}%;"
                f"divergence={r['divergence_vs_clean']:.4f};power={r['power_w']:.2f}W",
            )
        )
    last = rows[-1]
    d_ecc = 100 * (last["err_ecc"] - last["err_free"])
    d_raw = 100 * (last["err_no_ecc"] - last["err_free"])
    print(
        f"# fault-free err {100 * last['err_free']:.2f}% (paper 2.56%); @V_crash "
        f"ECC overhead {d_ecc:+.2f}% vs no-ECC {d_raw:+.2f}% "
        f"(paper +0.56% vs +3.59%); ECC advantage {d_raw / max(d_ecc, 1e-9):.1f}x "
        f"(paper 6.1x); BRAM saving Vmin->Vcrash "
        f"{100 * last['bram_saving_vs_vmin']:.1f}% (paper ~40% incl. guardband ref)"
    )


if __name__ == "__main__":
    use_compile_cache()
    main()
