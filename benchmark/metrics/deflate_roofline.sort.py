"""Share of the HBM roofline that the deflate stage reaches on the device.

The work is what the job needs: the record bytes read plus the deflated
bytes of those records as zlib writes them at the output level (the
reference's size, not the program's, so that writing more bytes is no
gain), at the chip's HBM bandwidth.  The time is that of every device
program launched inside ``flate.stage.deflate_device`` (match search, token
emit, CRC32).
"""


def read(ctx):
    t = sum(ctx.device_s(["flate.stage.deflate_device"]).values())
    if t <= 0:
        return None
    work = ctx.work["output_compressed"] + ctx.work["output_inflated"]
    return 100.0 * work / ctx.peaks["hbm_bytes_per_s"] / t
