"""Kernel, weight store: the fused SECDED-decode matmul's share of its
roofline (kernels/ecc_matmul.py).

For every ``ecc_matmul_2d`` event of the traced window the kernel's shapes
come from its HLO text: x (M, K) at its dtype, the planes (K/8, N), the
float32 product (M, N). The least time of a call is the larger of
2*M*K*N over the bf16 peak and its bytes over HBM bandwidth (planes at 9
bytes per 8 weights, read once; x and the product at their dtypes). The
share is the sum of those least times over the sum of the events' device
time.
"""

from benchlib import costs, tracing


def read(ctx):
    least = spent = 0.0
    for name, ns in ctx["reduced"].kernel_events("ecc_matmul_2d"):
        (out_dt, (m, n)), (x_dt, (_, k)) = tracing.shapes(name)[0][0], tracing.shapes(name)[1][0]
        flops, nbytes = costs.ecc_matmul(
            m, k, n, tracing.DTYPE_BYTES[x_dt], tracing.DTYPE_BYTES[out_dt]
        )
        least += costs.roofline_seconds(flops, nbytes, ctx["peaks"])[0]
        spent += ns / 1e9
    if not spent:
        return None
    return 100.0 * least / spent
