"""Accuracy-under-undervolt campaign: the paper's headline curve, measured.

Drives core/campaign.run_campaign — for each codec (and optional environment
scenario) an inline ServingEngine walks the campaign voltage grid and every
point's output is scored against the clean nominal rollout (greedy-match
prefix, teacher-forced logit KL, perplexity delta; see DESIGN.md §15). The
emitted rows are the accuracy-vs-voltage trajectory `benchmarks/run.py`
publishes as BENCH_accuracy.json and `check_regression.py --only accuracy`
gates on shape: zero divergence at nominal, and ileave88's zero-divergence
region reaching strictly deeper than parity65's.

CLI:
  python -m benchmarks.accuracy_campaign                  # full default grid
  python -m benchmarks.accuracy_campaign --smoke          # 1 voltage, 1 codec
  python -m benchmarks.accuracy_campaign \
      --codecs secded72,ileave88 --voltages 1.0,0.59,0.55 # nightly lane
"""

from __future__ import annotations

import argparse

from benchmarks.common import csv_line, emit, use_compile_cache
from repro.core import campaign


def run(spec: campaign.CampaignSpec | None = None) -> list[dict]:
    rows = campaign.run_campaign(spec or campaign.CampaignSpec())
    emit(rows, "accuracy_campaign")
    return rows


def _parse_spec(args) -> campaign.CampaignSpec:
    kw = {}
    if args.smoke:
        # cheapest harness exercise that still scores a faulty point:
        # one codec, nominal + one deep-undervolt voltage
        kw.update(
            codecs=("secded72",), voltages=(1.0, 0.55), n_prompts=2,
            n_tokens=12, proxy_words=1 << 12,
        )
    if args.model:
        kw["model"] = args.model
    if args.codecs:
        kw["codecs"] = tuple(args.codecs.split(","))
    if args.voltages:
        kw["voltages"] = tuple(float(v) for v in args.voltages.split(","))
    if args.env:
        kw["environments"] = tuple(
            None if e in ("", "none") else e for e in args.env.split(",")
        )
    if args.prompts:
        kw["n_prompts"] = args.prompts
    if args.tokens:
        kw["n_tokens"] = args.tokens
    if args.seed is not None:
        kw["seed"] = args.seed
    return campaign.CampaignSpec(**kw)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", default=None, help="tiny | <arch>-smoke | <arch>")
    ap.add_argument("--codecs", default=None, help="comma-separated codec names")
    ap.add_argument("--voltages", default=None, help="comma-separated volts")
    ap.add_argument("--env", default=None,
                    help="comma-separated scenario names ('none' = baseline)")
    ap.add_argument("--prompts", type=int, default=None)
    ap.add_argument("--tokens", type=int, default=None)
    ap.add_argument("--seed", type=int, default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="1 codec x {nominal, 0.55V} harness smoke (CI)")
    # parse_known_args: benchmarks.run passes its section name through argv
    args, _ = ap.parse_known_args(argv)

    rows = run(_parse_spec(args))
    for r in rows:
        env = f"/{r['environment']}" if r["environment"] else ""
        print(
            csv_line(
                f"accuracy/{r['model']}{env}/{r['codec']}@{r['voltage']:.2f}V",
                r["us"],
                f"divergence={r['divergence']:.4f};match_len={r['match_len']:.1f}"
                f"/{r['n_tokens']};kl={r['kl']:.4f};ppl_delta={r['ppl_delta']:.3f};"
                f"faulty_words={r['faulty_words']};detected={r['detected']}",
            )
        )
    # per-codec deepest voltage still bit-identical to the clean run — the
    # number the paper's "negligible accuracy loss down to V_min-ish" claim
    # becomes at LM scale
    for codec in dict.fromkeys(r["codec"] for r in rows):
        zero = [
            r["voltage"] for r in rows
            if r["codec"] == codec and r["divergence"] == 0.0
        ]
        floor = min(zero) if zero else None
        print(f"# {codec}: zero-divergence floor {floor} V over {len(zero)} points")

    smoke_ok = all(r["divergence"] == 0.0 for r in rows if r["nominal"])
    print(f"# nominal rows bit-identical to clean reference: {smoke_ok}")
    if not smoke_ok:
        raise SystemExit("nominal campaign rows diverged from the clean run")


if __name__ == "__main__":
    use_compile_cache()
    main()
