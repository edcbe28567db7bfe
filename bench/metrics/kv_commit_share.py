"""KV store: share of the traced window the chip spent in the prompt-KV
commit program (core/kvpages.py ``_commit_tokens``: the encode and the
token-window scatter into the arena planes), from the trace's
``XLA Modules`` line, averaged over the chips used. It covers the prompt
commit alone: the decode commit runs inside ``jit_multistep``."""


def read(ctx):
    red = ctx["reduced"]
    ns = red.module_ns.get("jit__commit_tokens", 0)
    if not ns:
        return None
    return 100.0 * ns / red.n_devices / (red.window[1] - red.window[0])
