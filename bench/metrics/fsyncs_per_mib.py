"""File and directory fsyncs (counts of the spans ``durable.fsync`` and
``durable.fsync_dir``) per MiB committed in the traced segment."""
import spans


def read(ctx):
    return spans.per_mib(ctx, ("durable.fsync", "durable.fsync_dir"),
                         field=0, scale=1.0)
