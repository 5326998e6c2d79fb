"""Bytes the program counted as copied host to device, per record."""


def read(ctx):
    if not ctx.jobs:
        return None
    return ctx.counter("transfers.h2d_bytes") / (ctx.records * len(ctx.jobs))
