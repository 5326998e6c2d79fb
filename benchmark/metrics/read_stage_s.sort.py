"""Host seconds of the ``sort_bam.read`` span, per job."""


def read(ctx):
    return ctx.span_per_job("sort_bam.read")
