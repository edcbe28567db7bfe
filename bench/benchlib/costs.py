"""Device peaks, and the operations and bytes each measured piece of work
needs, computed from its shapes.

Peaks come from one table (``bench/peaks.json``) keyed by JAX's
``device_kind``; a device that is not in the table is an error.
"""

from __future__ import annotations

import json
import os

from benchlib.registry import BENCH_DIR


class UnknownDevice(KeyError):
    """The device kind has no row in bench/peaks.json."""


def peaks(device_kind: str) -> dict:
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r} in bench/peaks.json")
    return table[device_kind]


def roofline_seconds(flops: float, nbytes: float, pk: dict) -> tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_c = flops / pk["bf16_flops_per_s"]
    t_m = nbytes / pk["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def ecc_matmul(m: int, k: int, n: int, x_bytes: int = 2, out_bytes: int = 4):
    """(flops, bytes) of one fused decode-matmul x (m, k) @ W (k, n).

    The weight travels as SECDED planes: 8 int8 weights in one 64-bit
    codeword plus one check byte, 9 bytes per 8 weights, read once. ``x`` is
    read at the model's compute dtype and the product written as float32,
    the kernel's output dtype."""
    return 2.0 * m * k * n, 9.0 * (k // 8) * n + m * k * x_bytes + m * n * out_bytes


def gather_scrub(pages: int, words_per_page: int, check_bytes: int = 1):
    """(ops, bytes) of one scrub-on-read of ``pages`` gathered pages: every
    codeword (two uint32 data words and its check plane) read once and the
    corrected planes written back once, plus one int32 counter row of 128
    lanes per page. Syndrome arithmetic is integer work on the vector unit,
    not matrix FLOPs, so the bound is the memory one."""
    words = pages * words_per_page
    return 0.0, 2.0 * words * (8 + check_bytes) + pages * 128 * 4


def request_flops(cfg: dict, prompt: int, output: int, family) -> float:
    """Model FLOPs of one served request: the prompt's forward pass, then
    one forward per generated token after the first (the first comes out
    of the prefill), each token's FLOPs from ``cfg``'s family."""
    flops = family.token_flops
    total = sum(flops(cfg, i + 1) for i in range(prompt))
    total += sum(flops(cfg, prompt + j + 1) for j in range(output - 1))
    return total
