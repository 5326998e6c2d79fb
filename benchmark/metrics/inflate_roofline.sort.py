"""Share of the HBM roofline that the inflate stage reaches on the device.

The work is what the job needs: the deflated bytes read plus the inflated
bytes written, of every input member that holds records, at the chip's
HBM bandwidth.  The time is that of every device program launched inside
``flate.stage.inflate_device``, whichever kernel does it.
"""


def read(ctx):
    t = sum(ctx.device_s(["flate.stage.inflate_device"]).values())
    if t <= 0:
        return None
    work = ctx.work["input_compressed"] + ctx.work["input_inflated"]
    return 100.0 * work / ctx.peaks["hbm_bytes_per_s"] / t
