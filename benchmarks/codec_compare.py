"""Scheme-comparison benchmark: ECC codec coverage vs overhead vs throughput.

Runs every registered codec (repro.codes) across the three platform fault
curves (DESIGN.md §12):

  * **coverage** — the vmapped scheme sweep (core/sweep.sweep_codec_schemes)
    classifies one fault field per (platform, voltage) grid point under each
    codec; all codecs share the per-word weakness draw, so the comparison
    isolates the code design.
  * **overhead** — check bits per 64-bit word (the redundancy the power
    model charges via voltage.redundancy_factor).
  * **scrub throughput** — wall time of the generalized scrub-on-read kernel
    (kernels/paged_gather.py) over a fixed page stack, reported relative to
    SECDED in the same process (machine-normalized, like the fused/pair CI
    ratio). Interpret-mode numbers off-TPU.

The emitted JSON (benchmarks/out/codec_compare.json) is the nightly-lane
artifact; the `acceptance` rows record whether DEC-TED and interleaved
SECDED beat plain SECDED's correctable coverage at each platform's deepest
voltage step — the design-space result this subsystem exists to show.

A second table covers the **scenario matrix** (DESIGN.md §14): the same
codec sweep under every named environment (consumer / avionics / space),
each with its flux multiplier and correlated-burst shape, at a
rate-matched voltage per platform (scenario.scenario_voltage — comparable
fault density across environments despite 1x..50000x flux). Its
`scenario_acceptance` rows record whether the 4-way interleaved code beats
plain SECDED's correctable coverage under bursts — per environment, the
result the burst model exists to show.

``--smoke --codec NAME`` runs one codec through the generalized fused
inject+scrub and scrub-on-read kernels on a tiny arena and verifies both
against the codec's numpy oracle — the CI codec-matrix job.
``--scenario-smoke --env NAME`` does the same under one environment's
burst-shaped masks: DeviceFaultField burst masks at the scenario voltage
through the fused kernel, DED lane checked against the codec's numpy
decode oracle plus a mask-replay check — the CI scenario-matrix job.

Usage: python -m benchmarks.codec_compare [--words N] [--seed S]
       python -m benchmarks.codec_compare --smoke --codec dected79
       python -m benchmarks.codec_compare --scenario-smoke --env avionics
"""

from __future__ import annotations

import argparse
import dataclasses
import sys

import numpy as np

import jax.numpy as jnp

from benchmarks.common import csv_line, emit, timed, use_compile_cache
from repro import codes
from repro.core import scenario, sweep, voltage
from repro.kernels import ops, paged_gather


def scheme_grid():
    """Every platform's critical-region voltage steps (the paper grid)."""
    pairs = []
    for prof in voltage.PLATFORMS.values():
        vs = np.round(np.arange(prof.v_crash, prof.v_min + 1e-9, 0.01), 3)
        pairs.extend((prof, float(v)) for v in vs)
    return pairs


def scrub_throughput(codec_names, pages=16, words_per_page=4096, seed=0):
    """Interpret-mode scrub-on-read wall time per codec on one page stack."""
    rng = np.random.default_rng(seed)
    lo = jnp.asarray(rng.integers(0, 2**32, (pages, words_per_page), dtype=np.uint32))
    hi = jnp.asarray(rng.integers(0, 2**32, (pages, words_per_page), dtype=np.uint32))
    rows = []
    for name in codec_names:
        par = ops.encode(lo, hi, codec=name)

        def scrub():
            import jax

            return jax.block_until_ready(
                paged_gather.gather_scrub_pages(lo, hi, par, codec=name)[3]
            )

        _, us = timed(scrub, repeat=3)
        rows.append(
            {
                "kernel": "codec_scrub",
                "codec": name,
                "pages": pages,
                "words": pages * words_per_page,
                "us": us,
                "words_per_s": pages * words_per_page / (us * 1e-6),
            }
        )
    base = next(r["us"] for r in rows if r["codec"] == "secded72")
    for r in rows:
        r["us_over_secded"] = r["us"] / base
    return rows


SCENARIO_CODECS = ("secded72", "ileave88")


def scenario_grid(env):
    """One rate-matched (platform, voltage) point per platform.

    The environments span 1x..50000x flux; sweeping them at the *same*
    voltage steps saturates space at P_MAX while consumer barely faults.
    scenario_voltage bisects each platform's env-scaled curve to a common
    target fault density, so the codec comparison isolates the burst shape.
    """
    return [
        (prof, scenario.scenario_voltage(prof, env))
        for prof in voltage.PLATFORMS.values()
    ]


def scenario_rows(words: int, seed: int = 0) -> list[dict]:
    """Codec coverage under every environment's burst shape + acceptance."""
    out = []
    for name, env in scenario.ENVIRONMENTS.items():
        cov = sweep.sweep_codec_schemes(
            SCENARIO_CODECS, scenario_grid(env), words, seed=seed, env=env
        )
        for r in cov:
            r["kernel"] = "scenario_coverage"
        out.extend(cov)
        # Acceptance per environment: interleaving must win under bursts on
        # every platform — adjacent flips land one per subcode (codes/
        # interleaved.py), so ileave88 corrects the doubles SECDED only
        # detects. Aggregated across the env's rate-matched grid points.
        cover = {
            c: sum(r["corrected"] for r in cov if r["codec"] == c)
            / max(sum(r["faulty_words"] for r in cov if r["codec"] == c), 1)
            for c in SCENARIO_CODECS
        }
        out.append(
            {
                "kernel": "scenario_acceptance",
                "environment": name,
                "burst": dataclasses.asdict(env.burst),
                "rate_multiplier": env.rate_multiplier,
                "correctable": cover,
                "ileave_beats_secded": cover["ileave88"] > cover["secded72"],
            }
        )
    return out


def acceptance_rows(coverage_rows):
    """Per-platform: do the stronger codes beat SECDED at the deepest step?"""
    out = []
    platforms = sorted({r["platform"] for r in coverage_rows})
    for p in platforms:
        deepest = min(r["voltage"] for r in coverage_rows if r["platform"] == p)
        at = {
            r["codec"]: r["coverage_correctable"]
            for r in coverage_rows
            if r["platform"] == p and r["voltage"] == deepest
        }
        out.append(
            {
                "kernel": "codec_acceptance",
                "platform": p,
                "voltage": deepest,
                "coverage": at,
                "dected_beats_secded": at.get("dected79", 0) > at.get("secded72", 0),
                "ileave_beats_secded": at.get("ileave88", 0) > at.get("secded72", 0),
            }
        )
    return out


def run(words: int = 1 << 18, seed: int = 0) -> list[dict]:
    names = list(codes.names())
    cov = sweep.sweep_codec_schemes(names, scheme_grid(), words, seed=seed)
    for r in cov:
        r["kernel"] = "codec_coverage"
    rows = (
        cov
        + acceptance_rows(cov)
        + scenario_rows(words, seed=seed)
        + scrub_throughput(names, seed=seed)
    )
    emit(rows, "codec_compare")
    return rows


def smoke(codec: str, words: int = 1 << 12, seed: int = 0) -> int:
    """One codec through the generalized kernels vs its numpy oracle."""
    c = codes.get(codec)
    rng = np.random.default_rng(seed)
    lo = jnp.asarray(rng.integers(0, 2**32, words, dtype=np.uint32))
    hi = jnp.asarray(rng.integers(0, 2**32, words, dtype=np.uint32))
    par = ops.encode(lo, hi, codec=codec)
    sel = rng.random(words)
    mlo = jnp.asarray((sel < 0.02).astype(np.uint32) << rng.integers(0, 32, words).astype(np.uint32))
    mhi = jnp.asarray(((sel > 0.3) & (sel < 0.32)).astype(np.uint32) << rng.integers(0, 32, words).astype(np.uint32))
    mpar = jnp.asarray(
        ((sel > 0.6) & (sel < 0.61)).astype(np.uint64)
        << rng.integers(0, c.n_check, words).astype(np.uint64)
    ).astype(jnp.dtype(c.check_dtype))

    flo, fhi, fpar, cnt = ops.inject_scrub(lo, hi, par, mlo, mhi, mpar, codec=codec)
    nlo, nhi, nst = c.decode_np(np.asarray(flo), np.asarray(fhi), np.asarray(fpar))
    cnt = np.asarray(cnt)
    ok = cnt[2] == int((nst == 2).sum())

    pages, w = 8, words // 8
    olo, ohi, opar, pcnt = paged_gather.gather_scrub_pages(
        jnp.asarray(np.asarray(flo).reshape(pages, w)),
        jnp.asarray(np.asarray(fhi).reshape(pages, w)),
        jnp.asarray(np.asarray(fpar).reshape(pages, w)),
        codec=codec,
    )
    st = nst.reshape(pages, w)
    exp = np.stack([(st == 0).sum(1), (st == 1).sum(1), (st == 2).sum(1)], 1)
    ok &= np.array_equal(np.asarray(pcnt)[:, :3], exp)
    ok &= np.array_equal(np.asarray(olo), nlo.reshape(pages, w))
    ok &= np.array_equal(np.asarray(ohi), nhi.reshape(pages, w))
    print(
        f"codec-smoke {codec}: {words} words, "
        f"detected={int(cnt[2])} corrected={int(cnt[1])} "
        f"-> {'OK' if ok else 'MISMATCH'}"
    )
    return 0 if ok else 1


def scenario_smoke(env_name: str, words: int = 1 << 13, seed: int = 0) -> int:
    """One environment's burst masks through the fused kernel vs the oracle.

    For each scenario codec: draw the env-scaled DeviceFaultField burst
    masks at the platform's rate-matched scenario voltage, push a random
    clean memory through ops.inject_scrub, and check the kernel's DED lane
    against the codec's numpy decode oracle on the faulted planes — plus a
    replay check (same field, same voltage -> bit-identical masks), the
    determinism contract CI pins per environment.
    """
    from repro.core.faultsim import DeviceFaultField

    env = scenario.ENVIRONMENTS[env_name]
    prof = voltage.PLATFORMS["vc707"]
    v = scenario.scenario_voltage(prof, env)
    rng = np.random.default_rng(seed)
    lo = jnp.asarray(rng.integers(0, 2**32, words, dtype=np.uint32))
    hi = jnp.asarray(rng.integers(0, 2**32, words, dtype=np.uint32))
    ok = True
    for cname in SCENARIO_CODECS:
        c = codes.get(cname)
        field = DeviceFaultField(
            env.scale_profile(prof), words, seed=seed,
            n_check=c.n_check, burst=env.burst,
        )
        mlo, mhi, mpar = field.masks(v)
        rlo, rhi, rpar = field.masks(v)
        replay = (
            bool(jnp.all(mlo == rlo))
            and bool(jnp.all(mhi == rhi))
            and bool(jnp.all(mpar == rpar))
        )
        par = ops.encode(lo, hi, codec=cname)
        flo, fhi, fpar, cnt = ops.inject_scrub(
            lo, hi, par, mlo, mhi, mpar, codec=cname
        )
        _, _, nst = c.decode_np(
            np.asarray(lo ^ mlo), np.asarray(hi ^ mhi),
            np.asarray(par ^ mpar.astype(par.dtype)),
        )
        cnt = np.asarray(cnt)
        match = cnt[2] == int((nst == 2).sum())
        ok &= replay and match
        print(
            f"scenario-smoke {env_name}/{cname}: v={v} "
            f"faulty={int(jnp.count_nonzero(mlo | mhi))} "
            f"detected={int(cnt[2])} corrected={int(cnt[1])} "
            f"replay={'OK' if replay else 'MISMATCH'} "
            f"oracle={'OK' if match else 'MISMATCH'}"
        )
    return 0 if ok else 1


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--words", type=int, default=1 << 18)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--codec", default=None, help="smoke mode: codec to exercise")
    ap.add_argument("--scenario-smoke", action="store_true")
    ap.add_argument("--env", default=None, help="scenario smoke: environment name")
    # parse_known_args: benchmarks.run passes its section name through argv
    args, _ = ap.parse_known_args(argv)
    if args.scenario_smoke:
        targets = [args.env] if args.env else sorted(scenario.ENVIRONMENTS)
        sys.exit(max(scenario_smoke(t, seed=args.seed) for t in targets))
    if args.smoke:
        targets = [args.codec] if args.codec else list(codes.names())
        sys.exit(max(smoke(t) for t in targets))
    rows = run(words=args.words, seed=args.seed)
    for r in rows:
        if r["kernel"] == "codec_scrub":
            print(
                csv_line(
                    f"codec/scrub_{r['codec']}", r["us"],
                    f"words_per_s={r['words_per_s']:.3e};"
                    f"vs_secded={r['us_over_secded']:.2f}",
                )
            )
        elif r["kernel"] == "codec_acceptance":
            print(
                csv_line(
                    f"codec/acceptance_{r['platform']}", 0.0,
                    f"v={r['voltage']:.2f};"
                    f"dected_beats_secded={r['dected_beats_secded']};"
                    f"ileave_beats_secded={r['ileave_beats_secded']}",
                )
            )
        elif r["kernel"] == "scenario_acceptance":
            print(
                csv_line(
                    f"codec/scenario_{r['environment']}", 0.0,
                    f"flux={r['rate_multiplier']:.0f}x;"
                    f"ileave_beats_secded={r['ileave_beats_secded']}",
                )
            )


if __name__ == "__main__":
    use_compile_cache()
    main()
