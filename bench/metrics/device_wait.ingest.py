"""Time the host spent blocked on the device (every ``*.device_wait``
span) per MiB committed in the traced segment."""
import spans


def read(ctx):
    return spans.per_mib(ctx, spans.DEVICE_WAIT)
