"""Device milliseconds of the part write's sorted gather per job: the
programs launched inside ``bam.write.gather``."""


def read(ctx):
    t = ctx.device_s(["bam.write.gather"])
    if not t or max(t.values()) <= 0:
        return None
    return 1000.0 * max(t.values()) / len(ctx.jobs)
