"""Device time of the fused encode programs (``core/pipeline._fused_program``:
apply, verify, pack, histogram and the rANS lane scan in one dispatch) and of
the standalone rANS encode scan (``kernels/rans.encode_scan``, the classic
path's entropy coder), per MiB committed in the traced segment."""
PROGRAMS = r"^jit_run_(sse|cb|id)$|^jit_encode_scan$"


def read(ctx):
    t, seg = ctx["trace"], ctx["segment"]
    if t is None or t.cut or not seg or not seg.get("work_mib"):
        return None
    s = t.seconds_in(PROGRAMS)
    return s * 1e3 / seg["work_mib"] if s else None
