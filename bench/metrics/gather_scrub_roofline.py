"""Kernel, KV store: the scrub-on-read kernel's share of its roofline
(kernels/paged_gather.py).

For every ``gather_scrub_2d`` event of the traced window the gathered page
planes (P, W) come from its HLO text; the kernel reads every codeword (two
uint32 words and a check byte) once, writes the corrected planes once and
one counter row per page. Integer syndrome work is not matrix FLOPs, so the
bound is memory: bytes over HBM bandwidth, summed over the events, over
their summed device time.
"""

from benchlib import costs, tracing


def read(ctx):
    least = spent = 0.0
    for name, ns in ctx["reduced"].kernel_events("gather_scrub_2d"):
        _, operands = tracing.shapes(name)
        (_, (pages, words)), (par_dt, _) = operands[0], operands[2]
        ops, nbytes = costs.gather_scrub(pages, words, tracing.DTYPE_BYTES[par_dt])
        least += costs.roofline_seconds(ops, nbytes, ctx["peaks"])[0]
        spent += ns / 1e9
    if not spent:
        return None
    return 100.0 * least / spent
