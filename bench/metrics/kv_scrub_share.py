"""KV store: share of the traced window the chip spent in the interval
scrub program (core/kvpages.py ``_scrub_rows``: the page gather, the
scrub-on-read kernel and the scatter write-back), from the trace's
``XLA Modules`` line, averaged over the chips used."""


def read(ctx):
    red = ctx["reduced"]
    ns = red.module_ns.get("jit__scrub_rows", 0)
    if not ns:
        return None
    return 100.0 * ns / red.n_devices / (red.window[1] - red.window[0])
