"""Decode attention: the least time ``flash_decode`` could take over the
traced decode steps (per layer and step, the larger of its operations over
the bf16 peak and the live K/V bytes over HBM bandwidth, bench/cost; a
row's live K/V is its context, the last ``window`` tokens of it in a
windowed layer; the contexts are the harness's count, the layers, heads
and windows the cell's block module's) over the device time of its events
in the decode programs."""
import collections

from bench.cost import flash_decode


def read(ctx, peaks):
    tr = getattr(ctx, "trace", None)
    steps = getattr(ctx, "steps", None)
    if tr is None or not steps:
        return None
    t = tr.kernel_s("flash_decode", "decode_fn")
    if t <= 0:
        return None
    # layers alike are counted once, times their number
    layers = collections.Counter(ctx.block.decode_attention(ctx.dims))
    rows = ctx.engine_cfg["num_slots"]
    ideal = 0.0
    for s in steps:
        if not s.contexts:
            continue
        for (heads, kv_heads, head_dim, window), n in layers.items():
            live = sum(s.contexts if window is None
                       else (min(c, window) for c in s.contexts))
            flop, moved = flash_decode.cost(live, rows, heads, kv_heads,
                                            head_dim)
            ideal += n * max(flop / peaks["bf16_flop_per_s"],
                             moved / peaks["hbm_byte_per_s"])
    return 100.0 * ideal / t
