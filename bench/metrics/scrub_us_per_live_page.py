"""KV store: device time of the interval scrub per live page it scrubbed.

The device time of the ``jit__scrub_rows`` program in the traced window
(the trace's ``XLA Modules`` line, averaged over the chips used) over the
sum of ``live_pages`` of the window's ``kv.paged_gather_scrub`` spans, in
microseconds. Padding entries cost device time but add no live page, so
the number falls both with a faster scrub and with a tighter table.
"""

from benchlib import spans


def read(ctx):
    red = ctx["reduced"]
    scrubs = spans.named(spans.window_spans(ctx, __file__), "kv.paged_gather_scrub")
    live = sum(st.get("live_pages", 0) for _, _, _, st in scrubs)
    ns = red.module_ns.get("jit__scrub_rows", 0)
    if not live or not ns:
        return None
    return ns / red.n_devices / live / 1e3
