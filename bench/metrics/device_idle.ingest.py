"""Share of the traced segment in which no operation ran on the device:
1 - (union of the device's program runs) / segment, averaged over chips."""


def read(ctx):
    t = ctx["trace"]
    if t is None or t.cut or t.window_s <= 0:
        return None
    return 100.0 * t.idle_share
