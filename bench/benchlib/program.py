"""The benchmark's only contact with the system under test.

Builds ``ServingEngine`` with the configuration's protection, from the
program's parameter tree and model configuration that the configuration's
family (``bench/families/<family>.py``) makes, and drives ``serve()``: the
entry the window measures. Also warms up every shape a cell's waves can
reach.
"""

from __future__ import annotations

from benchlib import traffic as traffic_mod


def build_engine(cfg: dict, params, max_len: int, family):
    from repro.serving.engine import (
        FaultModelConfig,
        RailsConfig,
        ReliabilityConfig,
        ServingEngine,
    )

    prot = cfg["protection"]
    if prot["rails"] != "nominal":
        raise ValueError(f"unsupported rail setting {prot['rails']!r}")
    rel = ReliabilityConfig(
        mode=prot["mode"],
        platform=prot["platform"],
        rails=RailsConfig(multi_rail=prot["multi_rail"]),
        fault_model=FaultModelConfig(mask_source=prot["mask_source"]),
    )
    return ServingEngine(family.model_config(cfg), params, rel, max_len=max_len)


def serve(eng, requests, mix: dict, cfg: dict):
    """One ``serve()`` call over ``requests`` with the cell's settings."""
    s = cfg["serve"]
    return eng.serve(
        requests,
        n_lanes=mix["lanes"],
        page_tokens=s["page_tokens"],
        scrub_interval=s["scrub_interval"],
        share_prefix=s["share_prefix"],
        speculative=s["speculative"],
        scrub_overlap=s["scrub_overlap"],
        walk_kv=s["walk_kv"],
    )


def _bucket(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def warmup_requests(mix: dict, cfg: dict) -> list[list[tuple[int, int]]]:
    """Serve calls, as (prompt_len, output_len) lists, that compile every
    shape a wave of ``mix`` can reach:

      * one call per prompt length p and group size m (1 .. min(count of p,
        lanes)) with m requests of length p and one output token: the
        prefill program, prompt-KV extract, page commit and lane load at
        (m, p);
      * one call per live-page bucket the scrub can see, with one request of
        the mix's prompt lengths decoding 2 * scrub_interval tokens: decode
        blocks of every power-of-two size up to the scrub interval, and the
        scrub + refresh programs at that page-table width.
    """
    pt = cfg["serve"]["page_tokens"]
    si = cfg["serve"]["scrub_interval"]
    pages = lambda t: -(-t // pt)
    reqs = traffic_mod.pairs(mix)
    calls = []
    counts: dict[int, int] = {}
    for p, _ in reqs:
        counts[p] = counts.get(p, 0) + 1
    for p, c in sorted(counts.items()):
        for m in range(1, min(c, mix["lanes"]) + 1):
            calls.append([(p, 1)] * m)
    buckets = {
        _bucket(pages(t)) for p, o in reqs for t in range(p + 1, p + o + 1)
    }
    for b in sorted(buckets):
        # the shortest request of the mix whose live pages reach bucket b
        for p, o in sorted(set(reqs)):
            n = next((n for n in range(1, o + 1) if _bucket(pages(p + n)) == b), None)
            if n is not None:
                out = max(2 * si, -(-n // si) * si + si)
                calls.append([(p, min(out, mix["max_len"] - p))])
                break
    return calls


def warm_up(eng, mix: dict, cfg: dict, vocab: int, seed: int) -> int:
    """Run the warm-up serve calls; returns how many were made."""
    calls = warmup_requests(mix, cfg)
    gen = traffic_mod.rng(seed, traffic_mod.WARMUP)
    for call in calls:
        reqs = [(gen.integers(1, vocab, size=p).astype("int32"), o) for p, o in call]
        serve(eng, reqs, mix, cfg)
    return len(calls)
