"""Model step: the window's model FLOPs over the chip's bf16 peak.

Model FLOPs of every request the traced window served (its prompt's forward
pass and one forward per generated token after the first: 2 per matmul
parameter including the head, plus attention over the real context;
int8 dequantisation and padded or idle rows are not counted), over the
window's seconds, over the peak of the chips used. The FLOPs of a token are
the configuration's family's (``ctx["family"]``, resolved by ``run.py``).
"""

from benchlib import costs


def read(ctx):
    red = ctx["reduced"]
    flops = sum(
        costs.request_flops(ctx["cfg"], len(prompt), len(rep.outputs[rid]), ctx["family"])
        for reqs, rep in zip(ctx["waves"], ctx["reports"])
        for rid, (prompt, _) in enumerate(reqs)
        if rid in rep.outputs
    )
    if not flops:
        return None
    return 100.0 * flops / red.window_s / (red.n_devices * ctx["peaks"]["bf16_flops_per_s"])
