"""Host seconds per job of the deflate stage less its waits on device
results: ``flate.stage.deflate_device`` minus ``flate.deflate.wait``."""


def read(ctx):
    stage = ctx.span_per_job("flate.stage.deflate_device")
    wait = ctx.span_per_job("flate.deflate.wait")
    if stage is None or wait is None:
        return None
    return stage - wait
