"""Device, serving: the share of the traced window in which no operation
ran on the device (1 - busy / window), averaged over the chips used."""


def read(ctx, peaks):
    tr = getattr(ctx, "trace", None)
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
