"""Step programs, decode: model operations of the traced decode tokens
(every block linear, the head, attention over each row's live context; the
cell's block module counts them) over the device time of the decode
programs times the bf16 peak."""


def read(ctx, peaks):
    tr = getattr(ctx, "trace", None)
    if tr is None or not hasattr(ctx, "steps"):
        return None
    t = tr.program_s("decode_fn")
    contexts = [c for s in ctx.steps for c in s.contexts]
    if t <= 0 or not contexts:
        return None
    flop = ctx.block.decode_flop(ctx.dims, contexts)
    return 100.0 * flop / (t * peaks["bf16_flop_per_s"])
