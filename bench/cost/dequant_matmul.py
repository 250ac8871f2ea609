"""``dequant_matmul``: y (M, N) = x (M, K) @ dequant(W)^T, W packed INT4
(N, K/2) uint8 with float32 scale and zero per row and group of K. The
kernel gets x split into its even and odd columns, (M, K/2) each. Vmapped
over stacked experts (``repro.models.layers.expert_apply``) it is one call
whose result, weights and planes lead with the experts, x too when each
expert has its own."""
import math


def cost(m: int, k: int, n: int, group: int = 128,
         act_bytes: int = 4) -> tuple:
    flop = 2 * m * k * n
    weight = n * k // 2 + 2 * n * (k // group) * 4
    return flop, weight + m * k * act_bytes + m * n * act_bytes


def call_shape(shapes: tuple) -> tuple:
    """(calls, (M, K, N, group)) of one call from its trace event's shapes:
    result (..., M, N), then x even (..., M, K/2), x odd, packed
    (..., N, K/2), scale (..., N, K/g). The result's leading axes, if any,
    make that many calls of the unbatched shape."""
    *batch, m, n = shapes[0]
    k = 2 * shapes[1][-1]
    return math.prod(batch), (m, k, n, k // shapes[4][-1])


def roofline_share(trace, programs, peaks):
    """Percent of the roofline over every call of the kernel in
    ``programs``; None when the trace holds none."""
    ideal, spent = 0.0, 0.0
    for prog in programs:
        spent += trace.kernel_s("dequant_matmul", prog)
        for shp, count in trace.calls.get((prog, "dequant_matmul"),
                                          {}).items():
            calls, shape = call_shape(shp)
            flop, moved = cost(*shape)
            ideal += count * calls * max(flop / peaks["bf16_flop_per_s"],
                                         moved / peaks["hbm_byte_per_s"])
    if spent <= 0:
        return None
    return 100.0 * ideal / spent
