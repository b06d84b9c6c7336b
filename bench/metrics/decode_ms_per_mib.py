"""Device time of every program the traced reads ran (the rANS
``decode_scan`` and the inverse transforms of ``pipeline.decode``; a read
runs nothing else on the device) per MiB decoded in the traced segment
(``TensorServer.stats()['decoded_bytes']``)."""


def read(ctx):
    t, seg = ctx["trace"], ctx["segment"]
    if t is None or t.cut or not seg or not seg.get("decoded_mib"):
        return None
    s = sum(t.program_s.values())
    return s * 1e3 / seg["decoded_mib"] if s else None
