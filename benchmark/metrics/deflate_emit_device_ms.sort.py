"""Device milliseconds of token compaction and fixed-Huffman emit per
job: the programs launched inside ``flate.deflate.emit``."""


def read(ctx):
    t = ctx.device_s(["flate.deflate.emit"])
    if not t or max(t.values()) <= 0:
        return None
    return 1000.0 * max(t.values()) / len(ctx.jobs)
