"""Wall time of the durable commits' fsyncs and renames (spans
``durable.fsync``, ``durable.fsync_dir``, ``durable.replace``) per MiB
committed in the traced segment."""
import spans


def read(ctx):
    return spans.per_mib(ctx, ("durable.fsync", "durable.fsync_dir",
                               "durable.replace"))
