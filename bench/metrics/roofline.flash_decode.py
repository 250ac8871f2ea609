"""Decode attention: the least time ``flash_decode`` could take over the
traced decode steps (per layer and step, the larger of its operations over
the bf16 peak and the live K/V bytes over HBM bandwidth, bench/cost; the
live context of each step is the harness's count, the layers and heads the
cell's block module's) over the device time of its events in the decode
programs."""
from bench.cost import flash_decode


def read(ctx, peaks):
    tr = getattr(ctx, "trace", None)
    steps = getattr(ctx, "steps", None)
    if tr is None or not steps:
        return None
    t = tr.kernel_s("flash_decode", "decode_fn")
    if t <= 0:
        return None
    layers, heads, kv_heads, head_dim = ctx.block.decode_attention(ctx.dims)
    rows = ctx.engine_cfg["num_slots"]
    ideal = 0.0
    for s in steps:
        if s.decoded:
            flop, moved = flash_decode.cost(s.decode_ctx, rows, heads,
                                            kv_heads, head_dim)
            ideal += layers * max(
                flop / peaks["bf16_flop_per_s"],
                moved / peaks["hbm_byte_per_s"])
    return 100.0 * ideal / t
