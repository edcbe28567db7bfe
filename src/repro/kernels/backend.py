"""Pallas lowering selection: the compiled lane vs the interpret lane.

Every kernel wrapper in ``kernels/ops.py`` (and the mesh/paged callers that
bake ``interpret`` into a jit cache key) routes its lowering decision through
this module (DESIGN.md §18). The lane is a property of the platform, never a
fallback:

  * ``resolve()`` returns ``"compiled"`` on TPU (Mosaic) and ``"interpret"``
    on CPU. Any other platform has no lane for these kernels and raises.
    On TPU a Mosaic lowering or compile failure propagates from the kernel
    call itself; nothing retries it in the interpreter.
  * ``REPRO_KERNEL_BACKEND`` (``auto`` | ``compiled`` | ``interpret``) or
    ``set_backend()`` states which lane the caller expects. ``auto`` takes
    the platform's lane; any other value must match it, or
    :class:`BackendUnavailable` is raised — so a compiled request on a CPU
    host fails loudly instead of running the interpreter.
  * A per-call ``interpret=True`` is always honored (the reference lane the
    tests compare against); ``interpret=False`` raises where no lowering
    exists.
"""

from __future__ import annotations

import os

import jax

VALID = ("auto", "compiled", "interpret")

# The kernels use Mosaic TPU primitives (pltpu scratch, TPU block tiling), so
# TPU is the only platform with a compiled lane; CPU runs the interpreter.
_LANES = {"tpu": "compiled", "cpu": "interpret"}

_override: list[str | None] = [None]  # set_backend() beats the env var


class BackendUnavailable(RuntimeError):
    """A kernel lane was requested that this platform cannot run."""


def set_backend(mode: str | None) -> None:
    """State the expected lane programmatically (tests); ``None`` restores
    auto."""
    if mode is not None and mode not in VALID:
        raise ValueError(f"backend must be one of {VALID}, got {mode!r}")
    _override[0] = mode


def requested() -> str:
    """The requested mode: set_backend() > REPRO_KERNEL_BACKEND > auto."""
    if _override[0] is not None:
        return _override[0]
    mode = os.environ.get("REPRO_KERNEL_BACKEND", "auto").strip().lower()
    return mode if mode in VALID else "auto"


def platform_lane() -> str:
    """The one lane the runtime platform supports."""
    platform = jax.default_backend()
    try:
        return _LANES[platform]
    except KeyError:
        raise BackendUnavailable(
            f"no Pallas lane for platform {platform!r} (kernels lower with "
            "Mosaic on TPU and run interpreted on CPU)"
        ) from None


def compiled_available() -> bool:
    """Whether the runtime platform lowers these kernels (TPU)."""
    return jax.default_backend() == "tpu"


def resolve() -> str:
    """The lane in force: ``"compiled"`` or ``"interpret"``.

    Raises :class:`BackendUnavailable` when the requested mode names a lane
    the platform does not have (a compiled request on CPU, an interpret
    request on TPU)."""
    lane = platform_lane()
    mode = requested()
    if mode not in ("auto", lane):
        raise BackendUnavailable(
            f"kernel lane {mode!r} requested on platform "
            f"{jax.default_backend()!r}, whose only lane is {lane!r}"
        )
    return lane


def use_interpret() -> bool:
    """Boolean view of ``resolve()``."""
    return resolve() == "interpret"


def resolve_interpret(interpret: bool | None) -> bool:
    """Resolve a per-call ``interpret`` request to a concrete lowering.

    ``None``  -> the lane in force (``resolve()``).
    ``False`` -> compiled; raises :class:`BackendUnavailable` where the
                 platform has no lowering.
    ``True``  -> interpret, always honored (the reference lane).
    """
    if interpret is None:
        return use_interpret()
    if not interpret and not compiled_available():
        raise BackendUnavailable(
            f"compiled kernel requested on platform {jax.default_backend()!r}, "
            "which has no Pallas lowering"
        )
    return bool(interpret)


def tag() -> str:
    """Row tag for benchmarks/profiler: the lane in force."""
    return resolve()
