"""State store: share of the traced window the chip spent reading and
rewriting the protected Mamba-2 state: the decode kernel ``ecc_ssd_step_2d``
(kernels/ecc_ssd.py: decode, correct, update, re-encode, in place) and the
admission's state write (``jit_commit_state``: the prefill's final state
encoded into the admitted lanes' slots, core/statestore.py), from the
trace's ``XLA Ops`` and ``XLA Modules`` lines, averaged over the chips
used. A program without the state store has neither and reads nothing."""


def read(ctx):
    red = ctx["reduced"]
    ns = sum(t for _, t in red.kernel_events("ecc_ssd_step_2d"))
    ns += red.module_ns.get("jit_commit_state", 0)
    if not ns:
        return None
    return 100.0 * ns / red.n_devices / (red.window[1] - red.window[0])
