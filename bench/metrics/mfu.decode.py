"""Step programs, decode: model operations of the traced decode tokens
(every block linear, the head, attention over each live context; the cell's
block module counts them) over the device time of the decode programs
times the bf16 peak."""


def read(ctx, peaks):
    tr = getattr(ctx, "trace", None)
    if tr is None or not hasattr(ctx, "steps"):
        return None
    t = tr.program_s("decode_fn")
    tokens = sum(s.decoded for s in ctx.steps)
    if t <= 0 or tokens == 0:
        return None
    flop = ctx.block.decode_flop(ctx.dims, tokens,
                                 sum(s.decode_ctx for s in ctx.steps))
    return 100.0 * flop / (t * peaks["bf16_flop_per_s"])
