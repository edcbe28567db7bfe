"""Bring-up smoke test: the ECC-protected serving path on one TPU chip.

Drives the system's main path once, through the entry points a user calls,
at the full published width of qwen3-0.6b (28 layers, d_model 1024, 16/8
heads, d_ff 3072, vocab 151936, bf16) with random weights made from
``--seed``:

  kernels  every main-path Pallas kernel compiled on the chip (encode/decode,
           fused inject+scrub with per-domain counters, paged gather-scrub,
           the fused decode-matmul) against the codec's NumPy oracle and
           ``kernels/ref.py``, on seeded planes carrying injected single- and
           double-bit flips: planes and counters bit for bit, the matmul
           against float32 ``x @ dequant(W)``;
  engine   ``ServingEngine`` (inline multi-rail protection, device masks) at
           nominal voltage: ``generate``, then ``serve()`` of an 8-request
           stream whose every output must equal ``generate`` on that
           request's own prompt and budget with no detected KV error; then
           ``autotune_voltage`` from near the critical region to its lock,
           where the engine must still serve.

``--chips 4`` runs only the mesh path and what it is compared with: a
4-replica ``ServingEngine(mesh=...)`` under per-shard rails, one rail step,
and the same stream served with every replica on its own chip, token for
token against the one-chip unsharded serve.

Timings are printed for orientation only. The last stdout line is the
result, printed only when every phase passed:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Exits non-zero without a result when JAX finds no TPU.

Usage: python chip_smoke.py [--chips 4] [--seed N]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

MAX_LEN = 320  # longest request: 256 prompt + 57 new tokens
N_LANES = 4
SCRUB_INTERVAL = 8
AUTOTUNE_START_V = 0.62  # just above vc707's V_min: locks in a few rounds
MATMUL_RTOL = 1e-4  # |out - ref| <= MATMUL_RTOL * max|ref| (f32 accumulation)


class SmokeFailure(AssertionError):
    """A check of the smoke run failed."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# seeded inputs
# ---------------------------------------------------------------------------
def request_stream(vocab: int, seed: int):
    """8 requests, prompts 32-256 tokens, 17-57 new tokens. Lengths repeat
    with period 4 so each of 4 round-robin replicas sees one prompt length;
    budgets are 1 + 8n so every decode block is one scrub interval long."""
    import numpy as np

    rng = np.random.default_rng(seed)
    lengths = [32, 256, 96, 160] * 2
    budgets = [17, 57, 33, 17, 57, 33, 17, 57]
    return [
        (rng.integers(1, vocab, size=s).astype(np.int32), n)
        for s, n in zip(lengths, budgets)
    ]


def flip_masks(rng, n: int, n_check: int, p1=0.004, p2=0.002):
    """XOR masks with single-bit flips in ~p1 and double-bit flips in ~p2 of
    ``n`` codewords (bits drawn over the 64 data + ``n_check`` check bits)."""
    import numpy as np

    nbits = 64 + n_check
    mlo = np.zeros(n, np.uint32)
    mhi = np.zeros(n, np.uint32)
    mpar = np.zeros(n, np.uint32)
    u = rng.random(n)
    for n_flips, sel in ((1, u < p1), (2, (u >= p1) & (u < p1 + p2))):
        words = np.flatnonzero(sel)
        if not len(words):
            continue
        bits = np.stack(
            [rng.choice(nbits, n_flips, replace=False) for _ in words]
        ).reshape(len(words), n_flips)
        for j in range(n_flips):
            b = bits[:, j].astype(np.uint32)
            for plane, lo_bit, width in ((mlo, 0, 32), (mhi, 32, 32), (mpar, 64, n_check)):
                hit = (b >= lo_bit) & (b < lo_bit + width)
                plane[words[hit]] ^= np.uint32(1) << (b[hit] - lo_bit)
    return mlo, mhi, mpar


# ---------------------------------------------------------------------------
# phase: kernels on the chip vs the oracles
# ---------------------------------------------------------------------------
def kernel_phase(cfg, seed: int) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro import codes
    from repro.core.faultsim import FlipMasks
    from repro.core.kvpages import KVGeometry
    from repro.core.telemetry import FaultStats
    from repro.kernels import ops, paged_gather, ref

    rng = np.random.default_rng(seed)
    codec = codes.get("secded72")
    u32 = lambda a: jnp.asarray(a, jnp.uint32)

    def same(name, got, want):
        got = np.asarray(got)
        check(
            got.shape == want.shape and np.array_equal(got, want),
            f"{name}: kernel differs from the oracle",
        )

    # encode / decode over a 1M-word arena block
    n = 1 << 20
    lo = rng.integers(0, 1 << 32, n, dtype=np.uint32)
    hi = rng.integers(0, 1 << 32, n, dtype=np.uint32)
    par = np.asarray(ops.encode(u32(lo), u32(hi)))
    same("encode", par, codec.encode_np(lo, hi))
    mlo, mhi, mpar = flip_masks(rng, n, codec.n_check)
    mpar = mpar.astype(np.uint8)
    flo, fhi, fpar = lo ^ mlo, hi ^ mhi, par ^ mpar
    got = ops.decode(u32(flo), u32(fhi), jnp.asarray(fpar))
    want = codec.decode_np(flo, fhi, fpar)
    for name, g, w in zip(("decode.lo", "decode.hi", "decode.status"), got, want):
        same(name, g, w)
    status = want[2]
    flips = FlipMasks(mlo, mhi, mpar).flip_counts()
    check(
        np.all(status[flips == 1] == 1) and np.all(status[flips == 2] == 2),
        "decode: single flips must correct, double flips must detect",
    )
    check(
        np.array_equal(want[0][flips == 1], lo[flips == 1]),
        "decode: corrected words differ from the clean data",
    )

    # fused inject + scrub with one counter row per memory domain
    n_dom = 3
    dom = rng.integers(0, n_dom, n).astype(np.int32)
    out = ops.inject_scrub_domains(
        u32(lo), u32(hi), jnp.asarray(par), u32(mlo), u32(mhi),
        jnp.asarray(mpar), jnp.asarray(dom), n_dom,
    )
    for name, g, w in zip(("inject.lo", "inject.hi", "inject.parity"), out[:3], (flo, fhi, fpar)):
        same(name, g, w)
    rows = np.stack(
        [
            FaultStats.from_decode(status[dom == d], flips[dom == d]).counters()
            for d in range(n_dom)
        ]
    )
    same("inject_scrub_domains.counters", np.asarray(out[3]).astype(np.int64), rows)

    # scrub-on-read of gathered KV pages at the model's page geometry
    words = KVGeometry.from_config(cfg).words_per_page
    p = 8
    plo = rng.integers(0, 1 << 32, (p, words), dtype=np.uint32)
    phi = rng.integers(0, 1 << 32, (p, words), dtype=np.uint32)
    ppar = codec.encode_np(plo, phi)
    qlo, qhi, qpar = (m.reshape(p, words) for m in flip_masks(rng, p * words, 8))
    plo, phi, ppar = plo ^ qlo, phi ^ qhi, ppar ^ qpar.astype(np.uint8)
    glo, ghi, gpar, gcnt = paged_gather.gather_scrub_pages(u32(plo), u32(phi), jnp.asarray(ppar))
    clo, chi, cst = codec.decode_np(plo, phi, ppar)
    same("gather_scrub.lo", glo, clo)
    same("gather_scrub.hi", ghi, chi)
    same("gather_scrub.parity", gpar, np.where(cst == 2, ppar, codec.encode_np(clo, chi)))
    want_cnt = np.zeros((p, 8), np.int32)
    for s in range(3):
        want_cnt[:, s] = (cst == s).sum(axis=1)
    same("gather_scrub.counters", gcnt, want_cnt)

    # fused decode-matmul at the model's projection widths, single-bit
    # faults injected into the weight planes (all corrected on the read path)
    d, hd = cfg.d_model, cfg.hd
    for m, k, nn in ((8, d, cfg.n_heads * hd), (512, d, cfg.d_ff), (8, cfg.d_ff, d)):
        w = rng.standard_normal((k, nn)).astype(np.float32) * 0.02
        ew = ops.pack_ecc_weights(jnp.asarray(w))
        x = jnp.asarray(rng.standard_normal((m, k)), cfg.compute_dtype)
        w_q = np.asarray(ref.unpack_ecc_weights(ew.lo, ew.hi), np.float64)
        dequant = w_q * np.asarray(ew.scale, np.float64)
        want = np.asarray(x, np.float64) @ dequant
        wlo, whi, _ = flip_masks(rng, ew.lo.size, 8, p1=0.01, p2=0.0)
        faulty = dataclasses.replace(
            ew,
            lo=ew.lo ^ u32(wlo.reshape(ew.lo.shape)),
            hi=ew.hi ^ u32(whi.reshape(ew.hi.shape)),
        )
        got = np.asarray(ops.ecc_matmul(x, faulty), np.float64)
        err = np.max(np.abs(got - want)) / np.max(np.abs(want))
        check(
            err <= MATMUL_RTOL,
            f"ecc_matmul {m}x{k}x{nn}: max error {err:.3g} x max|ref| "
            f"exceeds {MATMUL_RTOL}",
        )
        print(f"kernels: ecc_matmul {m}x{k}x{nn} max|err|/max|ref| = {err:.3g}")


# ---------------------------------------------------------------------------
# phase: the serving engine at full width
# ---------------------------------------------------------------------------
def _rel(**rails):
    from repro.serving.engine import (
        FaultModelConfig,
        RailsConfig,
        ReliabilityConfig,
    )

    return ReliabilityConfig(
        mode="inline",
        platform="vc707",
        rails=RailsConfig(multi_rail=True, **rails),
        fault_model=FaultModelConfig(mask_source="device"),
    )


def _serve(eng, reqs):
    return eng.serve(
        reqs, n_lanes=N_LANES, scrub_interval=SCRUB_INTERVAL, walk_kv=True
    )


def _new_tokens(reqs) -> int:
    return sum(n for _, n in reqs)


def _same_tokens(got, want, what: str) -> None:
    import numpy as np

    got, want = np.asarray(got), np.asarray(want)
    if got.shape == want.shape and np.array_equal(got, want):
        return
    diff = [i for i, (a, b) in enumerate(zip(got, want)) if a != b]
    first = diff[0] if diff else min(len(got), len(want))
    raise SmokeFailure(
        f"{what}: {len(got)} vs {len(want)} tokens, first difference at "
        f"position {first} ({len(diff)} positions differ)"
    )


def engine_phase(cfg, params, kind: str, seed: int) -> None:
    import gc

    import numpy as np

    from repro.serving.engine import ServingEngine

    reqs = request_stream(cfg.vocab, seed)
    t0 = time.perf_counter()
    eng = ServingEngine(cfg, params, _rel(), max_len=MAX_LEN)
    print(f"timing[{kind}]: engine build {time.perf_counter() - t0:.2f} s")

    prompts = np.random.default_rng(seed + 1).integers(1, cfg.vocab, (4, 64)).astype(np.int32)
    t0 = time.perf_counter()
    toks = eng.generate(prompts, 32)
    print(f"timing[{kind}]: generate 4x64 -> 32 (compile included) {time.perf_counter() - t0:.2f} s")
    check(toks.shape == (4, 32), f"generate shape {toks.shape}")
    check(((toks >= 0) & (toks < cfg.vocab)).all(), "generate: token out of vocab")

    # serve twice: the first pass compiles, the second is the warm pass
    want = [eng.generate(prompt[None], budget)[0] for prompt, budget in reqs]
    walls = []
    for attempt in range(2):
        t0 = time.perf_counter()
        report = _serve(eng, reqs)
        walls.append(time.perf_counter() - t0)
        for rid, ref in enumerate(want):
            _same_tokens(
                report.outputs[rid], ref,
                f"serve pass {attempt}, request {rid} vs generate",
            )
        check(
            report.kv_stats.detected == 0,
            f"nominal serve: KV detected {report.kv_stats.detected}",
        )
    tps = _new_tokens(reqs) / walls[1]
    print(
        f"timing[{kind}]: serve 8 requests first pass (compile included) "
        f"{walls[0]:.2f} s, warm pass {walls[1]:.2f} s = {tps:.1f} new tokens/s"
    )
    print(f"engine: nominal serve == generate for all {len(reqs)} requests; "
          f"KV {report.kv_stats.counters().tolist()}; kv rail {eng.rails['kv']:.3f} V")
    del eng, report
    gc.collect()

    # autotune from just above V_min, then serve at the lock
    t0 = time.perf_counter()
    eng = ServingEngine(cfg, params, _rel(start_v=AUTOTUNE_START_V), max_len=MAX_LEN)
    rails, history = eng.autotune_voltage(max_rounds=12)
    rounds = max(len(h) for h in history.values())
    print(f"timing[{kind}]: engine build + autotune ({rounds} rounds) {time.perf_counter() - t0:.2f} s")
    check(eng.controller.locked, f"autotune did not lock in 12 rounds: {rails}")
    check(eng.stats.corrected > 0, "autotune: no corrected fault on the walk")
    walk = {d: st.counters().tolist() for d, st in eng.rail_stats.by_domain.items()}
    served = eng.serve(reqs, n_lanes=N_LANES, scrub_interval=SCRUB_INTERVAL)
    for rid, (_, budget) in enumerate(reqs):
        check(len(served.outputs[rid]) == budget, f"serve at lock: request {rid} incomplete")
    print(f"engine: locked rails {json.dumps({d: round(v, 3) for d, v in eng.rails.items()})}")
    print(f"engine: autotune counters by domain {json.dumps(walk)} (fields "
          "clean, corrected, detected, silent, 1bit, 2bit, multi, faulty bits)")
    print(f"engine: KV counters at the lock {served.kv_stats.counters().tolist()}")
    print(f"engine: power_report {json.dumps(eng.power_report(), default=float)}")


# ---------------------------------------------------------------------------
# phase: four replicas, one per chip (--chips 4)
# ---------------------------------------------------------------------------
def mesh_phase(cfg, params, kind: str, seed: int, n_chips: int) -> None:
    import gc

    from repro.distributed import meshrel
    from repro.launch.mesh import make_reliability_mesh
    from repro.serving.engine import ServingEngine

    reqs = request_stream(cfg.vocab, seed)
    t0 = time.perf_counter()
    eng = ServingEngine(cfg, params, _rel(), max_len=MAX_LEN)
    ref = _serve(eng, reqs).outputs
    print(f"timing[{kind}]: one-chip build + serve {time.perf_counter() - t0:.2f} s")
    del eng
    gc.collect()

    t0 = time.perf_counter()
    mesh = make_reliability_mesh(n_chips)
    eng = ServingEngine(cfg, params, _rel(policy="per_shard"), max_len=MAX_LEN, mesh=mesh)
    eng.set_rails({d: eng.platform.v_nom for d in eng._store.domains})  # one rail step
    report = _serve(eng, reqs)
    print(f"timing[{kind}]: {n_chips}-replica build + rail step + serve {time.perf_counter() - t0:.2f} s")
    chips = meshrel.shard_devices(mesh)
    check(len(set(chips)) == n_chips, f"replicas share chips: {chips}")
    for s, arena in enumerate(eng.kv_arenas):
        # the arena planes are outputs of the replica's decode dispatches, so
        # their placement is where both the KV and the decode ran
        devs = arena.lo.devices()
        print(f"mesh: replica {s} KV arena + decode on {sorted(devs, key=str)}")
        check(devs == {chips[s]}, f"replica {s} ran on {devs}, not on {chips[s]}")
    for rid in ref:
        _same_tokens(
            report.outputs[rid], ref[rid],
            f"mesh request {rid} (replica {report.shard_of[rid]}) vs the "
            "one-chip serve",
        )
    print(f"mesh: all {len(ref)} requests token-identical to the one-chip serve; "
          f"KV per replica {[st.counters().tolist() for st in report.kv_stats_by_shard]}")


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the mesh path and its one-chip comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from benchmarks.common import use_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    kind = dev.device_kind
    print(f"device: {dev.platform} {kind} x{len(devices)}; "
          f"compile cache {use_compile_cache()}")

    from repro.configs.qwen3_0_6b import config
    from repro.kernels import backend
    from repro.models import lm

    lane = backend.resolve()
    print(f"kernel lane: {lane}")
    if lane != "compiled":
        print("chip_smoke: kernels are not on the compiled lane", file=sys.stderr)
        return 1

    cfg = config()
    t0 = time.perf_counter()
    params = lm.init_params(cfg, jax.random.PRNGKey(args.seed))
    jax.block_until_ready(params)
    print(f"timing[{kind}]: init_params {time.perf_counter() - t0:.2f} s")

    if args.chips == 4:
        phases = [("mesh", lambda: mesh_phase(cfg, params, kind, args.seed, 4))]
    else:
        phases = [
            ("kernels", lambda: kernel_phase(cfg, args.seed)),
            ("engine", lambda: engine_phase(cfg, params, kind, args.seed)),
        ]
    failed = []
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            run()
            ok = backend.resolve() == "compiled"
        except Exception:  # report every phase, then fail the run
            traceback.print_exc()
            ok = False
        print(f"phase {name}: {'PASS' if ok else 'FAIL'} "
              f"({time.perf_counter() - t0:.2f} s on {kind})", flush=True)
        if not ok:
            failed.append(name)
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": kind, "count": len(devices)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
