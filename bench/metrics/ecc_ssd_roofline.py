"""Kernel, state store: the protected Mamba-2 step's share of its roofline
(kernels/ecc_ssd.py ``ecc_ssd_step_2d``).

Each event's operands come from its HLO text: the state planes lo, hi
(R, N) uint32 and the check plane (R, N); dA, u_lo, u_hi (R, 1) float32 and
live (R, 1); B and C (lanes, 1, N) float32. The kernel reads every state
codeword once and writes it back once, reads the per-row inputs and the
lanes' B and C once, and writes y (two (R, 1) float32) and one (8, 128)
int32 counter tile per grid step. Its floating-point work is 5 operations
per state element (two float32 per codeword): the decay, the input outer
product, their sum, and the read-out's multiply-add. The least time is the
larger of the operations over the bf16 peak and the bytes over HBM
bandwidth; the share is the sum of least times over the sum of the events'
device time.
"""

from benchlib import costs, tracing


def ecc_ssd(rows: int, n: int, lanes: int, check_bytes: int, grid_steps: int):
    """(flops, bytes) of one ``ecc_ssd_step_2d`` over (rows, n) planes."""
    words = rows * n
    planes = 2 * words * (8 + check_bytes)
    inputs = 4 * rows * 4 + 2 * lanes * n * 4
    outputs = 2 * rows * 4 + grid_steps * 8 * 128 * 4
    return 5.0 * 2 * words, float(planes + inputs + outputs)


def read(ctx):
    least = spent = 0.0
    for name, ns in ctx["reduced"].kernel_events("ecc_ssd_step_2d"):
        outs, operands = tracing.shapes(name)
        (_, (rows, n)), (par_dt, _) = operands[0], operands[2]
        lanes = operands[7][1][0]
        steps = outs[-1][1][0] // 8
        flops, nbytes = ecc_ssd(rows, n, lanes, tracing.DTYPE_BYTES[par_dt], steps)
        least += costs.roofline_seconds(flops, nbytes, ctx["peaks"])[0]
        spent += ns / 1e9
    if not spent:
        return None
    return 100.0 * least / spent
