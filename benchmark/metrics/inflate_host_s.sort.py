"""Host seconds per job of the inflate stage less its waits on device
results: ``flate.stage.inflate_device`` minus ``flate.inflate.wait``."""


def read(ctx):
    stage = ctx.span_per_job("flate.stage.inflate_device")
    wait = ctx.span_per_job("flate.inflate.wait")
    if stage is None or wait is None:
        return None
    return stage - wait
