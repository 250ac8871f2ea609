"""Weight GEMM, decode: the least time ``dequant_matmul`` could take for
the calls in the traced decode programs (each call's shapes read from its
trace event; the larger of operations over the bf16 peak and packed-weight
plus activation bytes over HBM bandwidth, bench/cost) over the device time
of those events."""
from bench.cost import dequant_matmul


def read(ctx, peaks):
    tr = getattr(ctx, "trace", None)
    if tr is None:
        return None
    return dequant_matmul.roofline_share(tr, ("decode_fn",), peaks)
