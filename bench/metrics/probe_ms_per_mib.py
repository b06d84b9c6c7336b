"""Wall time of phase 1's ranking (span ``select.probe``) per MiB
committed in the traced segment."""
import spans


def read(ctx):
    return spans.per_mib(ctx, ("select.probe",))
