"""Host time of one miss: self time of the spans ``server.decode_span``,
``container.read_record``, ``rans.decode`` and ``decode.inverse`` (their
work less their device waits) per decode in the traced segment."""
import spans

NAMES = ("server.decode_span", "container.read_record", "rans.decode",
         "decode.inverse")


def read(ctx):
    return spans.per_miss(ctx, NAMES, field=2)
