"""Least time for the local trajectory's required work (bench/counts) over
the device time under scope fl.local_trajectory, per computed round."""


def read(ctx):
    r = ctx.roofline("fl.local_trajectory", "local_trajectory")
    return None if r is None else r[0]
