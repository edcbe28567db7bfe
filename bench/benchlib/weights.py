"""Seeded random weights, made on the device in one jitted call.

The benchmark owns the weights: the program under test and the plain
reference both start from the canonical tree this module returns for a
seed, so the reference takes nothing that the program made. The tree's
leaves, shapes and kinds are the configuration's family's
(``bench/families/<family>.py``, ``shapes(cfg)``).

Shared kinds: a ``matrix`` (a projection) is N(0, 1/sqrt(fan_in)), which
keeps every layer's output at unit scale, so the residual stream, and with
it each next token, depends on the context and not on the current token
alone: a fault in the KV cache changes what is served. A ``table`` (the
embedding and the head) is N(0, 0.02); a ``bias`` N(0, 0.5) and a ``gain``
1 + N(0, 0.1), so that a path that drops a bias or a gain changes the
result too. A family adds kinds of its own under ``KINDS``.
"""

from __future__ import annotations

import functools

from benchlib import traffic


def _normal(key, shape):
    import jax
    import jax.numpy as jnp

    return jax.random.normal(key, shape, jnp.float32)


# kind -> init(key, shape) -> float32 array; fan_in is the matrix's axis -2
KINDS = {
    "matrix": lambda key, shape: shape[-2] ** -0.5 * _normal(key, shape),
    "table": lambda key, shape: 0.02 * _normal(key, shape),
    "bias": lambda key, shape: 0.5 * _normal(key, shape),
    "gain": lambda key, shape: 1.0 + 0.1 * _normal(key, shape),
}


def key_seed(seed: int) -> int:
    """A 31-bit JAX key seed derived from any whole-number seed."""
    return int(traffic.rng(seed, traffic.WEIGHTS).integers(0, 2**31 - 1))


@functools.lru_cache(maxsize=None)
def _maker(layout: tuple, dtype_name: str, kinds: tuple):
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype_name)
    init = dict(kinds)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(layout))
        return {
            name: init[kind](k, shape).astype(dtype)
            for k, (name, shape, kind) in zip(keys, layout)
        }

    return make


def make(cfg: dict, seed: int, family):
    """The canonical weights of ``cfg`` for ``seed``, in the served dtype,
    laid out by ``cfg``'s family."""
    import jax

    shared = set(KINDS) & set(family.KINDS)
    if shared:
        raise ValueError(f"a family may not redefine the shared kinds {sorted(shared)}")
    layout = tuple(
        (name, shape, kind) for name, (shape, kind) in sorted(family.shapes(cfg).items())
    )
    kinds = tuple(sorted({**KINDS, **family.KINDS}.items()))
    out = _maker(layout, cfg["torch_dtype"], kinds)(jax.random.PRNGKey(key_seed(seed)))
    return jax.block_until_ready(out)
