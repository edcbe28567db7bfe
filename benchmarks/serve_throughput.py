"""Serving throughput: continuous batching (paged SECDED KV cache) vs the
fixed-batch decode loop, on a mixed-length request stream.

The fixed-batch baseline is what `ServingEngine.generate` does: pad every
prompt to the longest, decode the *longest* token budget for everyone, and
run the stream in rectangular waves of ``n_lanes`` requests — short requests
burn lane-steps padding out each wave's longest budget. Continuous batching
(`ServingEngine.serve`) admits a request the moment a lane frees up and
retires it the moment its budget is done, so lane-steps track useful tokens;
multi-step blocks keep its dispatch count in the same league as the
baseline's `lax.scan` rollout. The stream below is the adversarial-but-
typical serving mix: one long generation per wave of four, so the fixed
path wastes ~2/3 of its lane-steps.

The continuous path pays its full reliability freight in the measurement:
every token's KV is SECDED-encoded into pages and the scrub-on-read pass
runs on cadence. The fixed baseline does neither (dense unprotected cache).

The gated metric is ``cont_over_fixed`` — continuous tokens/s over fixed
tokens/s in the same process — which cancels machine speed and interpret
overhead exactly like the fused/pair kernel ratio; both are gated by
benchmarks/check_regression.py against the checked-in baseline. Samples are
interleaved and the minimum taken (scheduler noise is strictly additive).

The second experiment measures prefix sharing (DESIGN.md §16): the same
serve loop over a stream whose prompts share a long common prefix, with the
copy-on-write trie on vs off. ``shared_over_private`` is the tokens/s ratio
(> 1.0 gated absolutely: sharing must never cost throughput on a
shared-heavy stream). The win is structural — shared pages prefill through
the model once and are scrubbed once per interval instead of once per
reader — and the outputs stay bit-identical (tested, not benchmarked).

Usage: PYTHONPATH=src python -m benchmarks.serve_throughput
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from benchmarks.common import csv_line, emit, use_compile_cache

N_LANES = 4
MAX_LEN = 72
SCRUB_INTERVAL = 16
# the overlap experiment scrubs on a tight cadence so the scrub launch is a
# real fraction of the loop (at 16 it is amortized into the noise): the
# serialized path blocks on counters inside every interval, the overlapped
# path (DESIGN.md #18) defers the harvest one interval and lets decode run
OVERLAP_SCRUB_INTERVAL = 4
# one long generation per wave of four: budgets 48 / 5, prompts 8 tokens
STREAM = [(8, 48 if i % 4 == 0 else 5) for i in range(16)]
# prefix-sharing stream: a 48-token common prompt prefix (6 full pages at
# page_tokens=8) + 4 private suffix tokens, 12 new tokens each. The first
# wave of N_LANES seeds the trie (registration happens after commit, so
# same-wave requests cannot share); every later wave hits all 6 pages.
SHARED_PREFIX = 48
SHARED_SUFFIX = 4
SHARED_NEW = 12
N_SHARED = 16


def _setup():
    import jax

    from repro.configs import get_smoke_config
    from repro.models import lm
    from repro.serving import ServingEngine

    # serving-shaped config: big enough that per-step compute, not Python
    # dispatch, is the cost being scheduled (the smoke config is dispatch-
    # bound and would benchmark the interpreter, not the scheduler)
    cfg = dataclasses.replace(
        get_smoke_config("qwen3-0.6b"),
        n_layers=4, d_model=256, n_heads=8, n_kv_heads=4, head_dim=32, d_ff=512,
    )
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    reqs = [
        (rng.integers(0, cfg.vocab, size=(s0,)).astype(np.int32), n)
        for s0, n in STREAM
    ]
    prefix = rng.integers(0, cfg.vocab, size=(SHARED_PREFIX,)).astype(np.int32)
    shared_reqs = [
        (
            np.concatenate(
                [prefix, rng.integers(0, cfg.vocab, size=(SHARED_SUFFIX,)).astype(np.int32)]
            ),
            SHARED_NEW,
        )
        for _ in range(N_SHARED)
    ]
    return ServingEngine(cfg, params, rel=None, max_len=MAX_LEN), reqs, shared_reqs


def _run_fixed(eng, reqs) -> None:
    """Rectangular waves of N_LANES: pad prompts to the wave max, decode the
    wave-max token budget for every lane."""
    for w in range(0, len(reqs), N_LANES):
        wave = reqs[w : w + N_LANES]
        s_max = max(len(p) for p, _ in wave)
        n_max = max(n for _, n in wave)
        prompts = np.zeros((len(wave), s_max), np.int32)
        for i, (p, _) in enumerate(wave):
            prompts[i, : len(p)] = p  # right-pad; timing-only baseline
        eng.generate(prompts, n_tokens=n_max)


def run(samples: int = 3) -> list[dict]:
    eng, reqs, shared_reqs = _setup()
    useful_tokens = sum(n for _, n in reqs)
    run_cont = lambda: eng.serve(
        reqs, n_lanes=N_LANES, scrub_interval=SCRUB_INTERVAL
    )
    run_shared = lambda on: eng.serve(
        shared_reqs,
        n_lanes=N_LANES,
        scrub_interval=SCRUB_INTERVAL,
        share_prefix=on,
    )
    run_overlap = lambda on: eng.serve(
        reqs,
        n_lanes=N_LANES,
        scrub_interval=OVERLAP_SCRUB_INTERVAL,
        scrub_overlap=on,
    )

    from repro.obs import TraceRecorder

    _run_fixed(eng, reqs)  # warmup / compile
    rep = run_cont()
    run_shared(False), run_shared(True)  # warm both trie states' shapes
    run_overlap(False), run_overlap(True)  # warm the tight-cadence shapes
    tf, tc = [], []
    tp, ts = [], []
    tt, n_events = [], 0
    tser, tovl = [], []
    for _ in range(samples):
        t0 = time.perf_counter()
        _run_fixed(eng, reqs)
        tf.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        rep = run_cont()
        tc.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        run_shared(False)
        tp.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        srep = run_shared(True)
        ts.append(time.perf_counter() - t0)
        # traced sample: same serve loop with the flight recorder attached
        # (fresh per sample so the event list never amortizes across runs)
        eng.recorder = TraceRecorder()
        t0 = time.perf_counter()
        run_cont()
        tt.append(time.perf_counter() - t0)
        n_events = len(eng.recorder.events)
        eng.recorder = None
        t0 = time.perf_counter()
        run_overlap(False)
        tser.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        run_overlap(True)
        tovl.append(time.perf_counter() - t0)

    tps_fixed = useful_tokens / min(tf)
    tps_cont = useful_tokens / min(tc)
    tps_traced = useful_tokens / min(tt)
    shared_tokens = sum(n for _, n in shared_reqs)
    tps_private = shared_tokens / min(tp)
    tps_shared = shared_tokens / min(ts)
    tps_serialized = useful_tokens / min(tser)
    tps_overlapped = useful_tokens / min(tovl)
    rows = [
        {
            "kernel": "serve_throughput",
            "n_requests": len(reqs),
            "n_lanes": N_LANES,
            "useful_tokens": useful_tokens,
            "scrub_interval": SCRUB_INTERVAL,
            "steps_cont": rep.steps,
            "preemptions": rep.preemptions,
            "tokens_s_fixed": tps_fixed,
            "tokens_s_cont": tps_cont,
            "cont_over_fixed": tps_cont / tps_fixed,
        },
        {
            "kernel": "serve_shared_prefix",
            "n_requests": len(shared_reqs),
            "n_lanes": N_LANES,
            "useful_tokens": shared_tokens,
            "scrub_interval": SCRUB_INTERVAL,
            "prefix_tokens": SHARED_PREFIX,
            "prefix_hit_tokens": srep.prefix_hit_tokens,
            "tokens_s_private": tps_private,
            "tokens_s_shared": tps_shared,
            "shared_over_private": tps_shared / tps_private,
        },
        {
            # observability overhead: the same continuous-batching serve with
            # the flight recorder on. Gated absolutely (>= 0.95): tracing must
            # stay in the noise, never a tax on serving throughput.
            "kernel": "serve_traced",
            "n_requests": len(reqs),
            "n_lanes": N_LANES,
            "useful_tokens": useful_tokens,
            "scrub_interval": SCRUB_INTERVAL,
            "trace_events": n_events,
            "tokens_s_untraced": tps_cont,
            "tokens_s_traced": tps_traced,
            "traced_over_untraced": tps_traced / tps_cont,
        },
        {
            # async scrub off the decode critical path (DESIGN.md #18):
            # identical stream and cadence, scrub_overlap forced off vs on.
            # Gated absolutely in check_regression: overlapping a launch the
            # serialized path blocks on must never cost throughput.
            "kernel": "serve_scrub_overlap",
            "n_requests": len(reqs),
            "n_lanes": N_LANES,
            "useful_tokens": useful_tokens,
            "scrub_interval": OVERLAP_SCRUB_INTERVAL,
            "tokens_s_serialized": tps_serialized,
            "tokens_s_overlapped": tps_overlapped,
            "overlapped_over_serialized": tps_overlapped / tps_serialized,
        },
    ]
    emit(rows, "serve_throughput")
    return rows


def main():
    rows = run()
    r = rows[0]
    print(
        csv_line(
            f"serve/throughput_{r['n_requests']}req_{r['n_lanes']}lane",
            1e6 / r["tokens_s_cont"],
            f"cont_over_fixed={r['cont_over_fixed']:.2f};"
            f"tokens_s_cont={r['tokens_s_cont']:.1f};"
            f"tokens_s_fixed={r['tokens_s_fixed']:.1f};"
            f"preemptions={r['preemptions']}",
        )
    )
    s = rows[1]
    print(
        csv_line(
            f"serve/shared_prefix_{s['n_requests']}req_{s['prefix_tokens']}tok",
            1e6 / s["tokens_s_shared"],
            f"shared_over_private={s['shared_over_private']:.2f};"
            f"tokens_s_shared={s['tokens_s_shared']:.1f};"
            f"tokens_s_private={s['tokens_s_private']:.1f};"
            f"prefix_hit_tokens={s['prefix_hit_tokens']}",
        )
    )
    t = rows[2]
    print(
        csv_line(
            f"serve/traced_{t['n_requests']}req_{t['n_lanes']}lane",
            1e6 / t["tokens_s_traced"],
            f"traced_over_untraced={t['traced_over_untraced']:.2f};"
            f"tokens_s_traced={t['tokens_s_traced']:.1f};"
            f"trace_events={t['trace_events']}",
        )
    )
    o = rows[3]
    print(
        csv_line(
            f"serve/scrub_overlap_{o['n_requests']}req_si{o['scrub_interval']}",
            1e6 / o["tokens_s_overlapped"],
            f"overlapped_over_serialized={o['overlapped_over_serialized']:.2f};"
            f"tokens_s_overlapped={o['tokens_s_overlapped']:.1f};"
            f"tokens_s_serialized={o['tokens_s_serialized']:.1f}",
        )
    )


if __name__ == "__main__":
    use_compile_cache()
    main()
