"""Weight GEMM, decode: the share of the decode programs' device op time
spent fetching packed weights outside the ``dequant_matmul`` kernel: the
layer loop's slices of the stacked weights (``scan.weights``) and the ops
of the dequant-matmul wrapper that are not its Pallas kernel (scale and
zero gathers); bench/program_trace.py."""
from bench import program_trace


def read(ctx, peaks):
    pt = program_trace.for_context(ctx)
    if pt is None:
        return None
    return pt.share("decode_fn", ("scan.weights",),
                    pt.weight_gather_ns.get("decode_fn", 0.0))
