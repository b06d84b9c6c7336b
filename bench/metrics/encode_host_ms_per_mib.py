"""Host time of selection and encode (self time of the spans
``select.probe``, ``encode.prepare``, ``encode.fused``, ``encode.frame``,
``encode.classic``, ``container.serialize`` and ``rans.encode``: their work
less their device waits and children) per MiB committed in the traced
segment."""
import spans

NAMES = ("select.probe", "encode.prepare", "encode.fused", "encode.frame",
         "encode.classic", "container.serialize", "rans.encode")


def read(ctx):
    return spans.per_mib(ctx, NAMES, field=2)
