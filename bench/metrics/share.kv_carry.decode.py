"""KV pool, decode: the share of the decode programs' device op time that
moves the KV pool as data rather than attending or writing it: the layer
loop's pool-shaped slices, updates and copies (``scan.kv``) and XLA's
pool-shaped copies outside it (``unscoped.kv``); bench/program_trace.py."""
from bench import program_trace


def read(ctx, peaks):
    pt = program_trace.for_context(ctx)
    if pt is None:
        return None
    return pt.share("decode_fn", program_trace.KV_BUCKETS)
