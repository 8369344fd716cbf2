"""Least time for the chunked scan's required work (bench/counts: forward
and backward of every gradient evaluation, forward of every loss
evaluation; the recompute not counted) over the device time under scope
fl.lm_ssd, per computed round."""


def read(ctx):
    r = ctx.roofline("fl.lm_ssd", "lm_ssd")
    return None if r is None else r[0]
