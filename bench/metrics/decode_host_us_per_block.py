"""Scheduler: host time per decode block, the wait for its tokens left out.

Each ``serve.decode_block`` span of the traced window covers the block's
size choice, page growth, page-table build, dispatch, token bookkeeping and
retirements; its ``serve.block_sync`` child is the host's wait for the
block's tokens (``np.asarray``). The metric is the mean over the window's
blocks of the block's duration minus the time its sync children cover, in
microseconds: the host work the device waits for between blocks once the
device is no longer the limit.
"""

from benchlib import spans


def read(ctx):
    sp = spans.window_spans(ctx, __file__)
    blocks = spans.named(sp, "serve.decode_block")
    if not blocks:
        return None
    syncs = spans.named(sp, "serve.block_sync")
    host_ns = sum(
        (b[2] - b[1]) - sum(s[2] - s[1] for s in spans.inside(syncs, b)) for b in blocks
    )
    return host_ns / len(blocks) / 1e3
