"""Host seconds per job spent blocked on device results inside the two
codec stages: ``flate.inflate.wait`` plus ``flate.deflate.wait``."""


def read(ctx):
    waits = [ctx.span_per_job(n) for n in ("flate.inflate.wait", "flate.deflate.wait")]
    if None in waits:
        return None
    return sum(waits)
