"""Device milliseconds of the record walk, the keys and the sort, per job:
programs launched inside ``pipeline.stage.device_parse`` or
``sort_bam.device_parse_sort``."""


def read(ctx):
    t = ctx.device_s(["pipeline.stage.device_parse", "sort_bam.device_parse_sort"])
    if not t or max(t.values()) <= 0:
        return None
    return 1000.0 * max(t.values()) / len(ctx.jobs)
