"""Device milliseconds of the part write's CRC32 per job: the programs
launched inside ``flate.deflate.crc``."""


def read(ctx):
    t = ctx.device_s(["flate.deflate.crc"])
    if not t or max(t.values()) <= 0:
        return None
    return 1000.0 * max(t.values()) / len(ctx.jobs)
