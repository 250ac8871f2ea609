"""``dequant_matmul``: y (M, N) = x (M, K) @ dequant(W)^T, W packed INT4
(N, K/2) uint8 with float32 scale and zero per row and group of K. The
kernel gets x split into its even and odd columns, (M, K/2) each."""


def cost(m: int, k: int, n: int, group: int = 128,
         act_bytes: int = 4) -> tuple:
    flop = 2 * m * k * n
    weight = n * k // 2 + 2 * n * (k // group) * 4
    return flop, weight + m * k * act_bytes + m * n * act_bytes


def call_shape(shapes: tuple) -> tuple:
    """(M, K, N, group) of one call from its trace event's shapes: result
    (M, N), then x even (M, K/2), x odd, packed (N, K/2), scale (N, K/g)."""
    (m, n), (_, half) = shapes[0], shapes[1]
    k = 2 * half
    return m, k, n, k // shapes[4][1]


def roofline_share(trace, programs, peaks):
    """Percent of the roofline over every call of the kernel in
    ``programs``; None when the trace holds none."""
    ideal, spent = 0.0, 0.0
    for prog in programs:
        spent += trace.kernel_s("dequant_matmul", prog)
        for shp, count in trace.calls.get((prog, "dequant_matmul"),
                                          {}).items():
            flop, moved = cost(*call_shape(shp))
            ideal += count * max(flop / peaks["bf16_flop_per_s"],
                                 moved / peaks["hbm_byte_per_s"])
    if spent <= 0:
        return None
    return 100.0 * ideal / spent
