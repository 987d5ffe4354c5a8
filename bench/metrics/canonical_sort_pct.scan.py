"""Share of the window the host spent in ``accel.canonical_order``, the
record sort every accelerator backend runs before its kernel (the
benchmark's own span around it), in percent.  Nothing when no sort ran."""


def read(ctx):
    a, b = ctx.window
    busy = ctx.probes.spans_in("canonical_order", a, b)
    if busy <= 0 or ctx.window_s <= 0:
        return None
    return 100.0 * busy / ctx.window_s
