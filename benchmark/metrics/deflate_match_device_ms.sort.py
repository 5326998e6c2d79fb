"""Device milliseconds of the deflate lanes' match search per job: the
programs launched inside ``flate.deflate.match`` (each group's word
build and the match kernel)."""


def read(ctx):
    t = ctx.device_s(["flate.deflate.match"])
    if not t or max(t.values()) <= 0:
        return None
    return 1000.0 * max(t.values()) / len(ctx.jobs)
