"""The comparison that decides ``correct``.

After the window closes, a sample of the requests the window finished,
drawn from the seed and always holding the longest, is run once through
the plain float32 reference that the configuration's family names
(``bench/reference/<REFERENCE>.py``), teacher-forced on each prompt and
its served tokens. At every served position the gap is the reference's best logit
minus the reference's logit of the token the program served; the number
compared is the widest gap of the sample. Greedy serving with exact
arithmetic gives 0; rounding in the program's bfloat16 path shows as small
gaps at near-ties; a wrong token, a lost cache entry or a skipped layer
shows as a large one.

The control (``rounding``) is the reference itself computed in a lower
precision: at the same positions it reads the gap of the token that the
lower precision puts first.
"""

from __future__ import annotations

import numpy as np

from benchlib import traffic


def sample(finished: list, seed: int, target_tokens: int) -> list[int]:
    """Indices into ``finished`` [(prompt, served)]: the request with the
    most served tokens (longest prompt on a tie), then others in a seeded
    order until the sample holds ``target_tokens`` served tokens."""
    if not finished:
        return []
    longest = max(range(len(finished)), key=lambda i: (len(finished[i][1]), len(finished[i][0])))
    order = traffic.rng(seed, traffic.WINDOW, 10**6).permutation(len(finished))
    picked, n = [longest], len(finished[longest][1])
    for i in order:
        if n >= target_tokens:
            break
        if i != longest:
            picked.append(int(i))
            n += len(finished[i][1])
    return picked


def widest_gap(reference, rw: dict, cfg: dict, items: list, max_len: int,
               rounding: str | None = None) -> dict:
    """Widest gap over ``items`` [(prompt, served)] under ``reference``,
    the module ``cfg``'s family names.

    ``rounding=None``: gap of each served token. Otherwise: gap of the
    token the reference computed at ``rounding`` puts first."""
    import jax.numpy as jnp

    per_request = []
    for prompt, served in items:
        prompt = np.asarray(prompt, np.int32)
        served = np.asarray(served, np.int32)
        seq = np.zeros(max_len, np.int32)
        body = np.concatenate([prompt, served[:-1]])
        seq[: len(body)] = body
        pos = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
        ref = reference.logits(rw, cfg, seq)[pos]
        if rounding is None:
            choice = jnp.asarray(served)
        else:
            choice = jnp.argmax(reference.logits(rw, cfg, seq, rounding)[pos], axis=-1)
        picked = jnp.take_along_axis(ref, choice[:, None], axis=-1)[:, 0]
        gap = jnp.max(ref, axis=-1) - picked
        per_request.append(float(jnp.max(gap)))
    return {
        "widest": max(per_request) if per_request else float("inf"),
        "per_request": per_request,
        "tokens": int(sum(len(s) for _, s in items)),
    }
