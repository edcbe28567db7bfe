"""chip_smoke.py on the CPU: it refuses to run without a TPU, and its
kernel oracles agree with the kernels at the smoke config (interpret
lane), so a failure on the chip points at the chip, not at the checks."""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke  # noqa: E402
from repro.configs.qwen3_0_6b import smoke_config  # noqa: E402


def test_refuses_cpu_without_result(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs a TPU" in out.err


def test_kernel_phase_oracles_hold_on_interpret_lane():
    chip_smoke.kernel_phase(smoke_config(), seed=3)


def test_flip_masks_inject_requested_flip_counts():
    import numpy as np

    from repro.core.faultsim import FlipMasks

    rng = np.random.default_rng(0)
    mlo, mhi, mpar = chip_smoke.flip_masks(rng, 20000, 8, p1=0.05, p2=0.05)
    flips = FlipMasks(mlo, mhi, mpar.astype(np.uint8)).flip_counts()
    assert set(np.unique(flips)) == {0, 1, 2}
    assert 0.04 < (flips == 1).mean() < 0.06
    assert 0.04 < (flips == 2).mean() < 0.06
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check(False, "boom")
