"""Scheduler: share of decode-lane slots that produced a token.

Generated tokens of the window's requests over decode steps times lanes,
from the ``ServeReport`` of each wave. The first token of a request comes
out of its prefill, not a decode step, so it is left out of the numerator.
"""


def read(ctx):
    steps = sum(r.steps for r in ctx["reports"])
    if not steps:
        return None
    decoded = sum(
        max(len(toks) - 1, 0) for r in ctx["reports"] for toks in r.outputs.values()
    )
    return 100.0 * decoded / (steps * ctx["mix"]["lanes"])
