"""Time the host spent blocked on the device (every ``*.device_wait``
span) per decode in the traced segment."""
import spans


def read(ctx):
    return spans.per_miss(ctx, spans.DEVICE_WAIT)
