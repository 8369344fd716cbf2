"""Least time for the AA step's required work (bench/counts) over the
device time under scope fl.aa_step, per computed round."""


def read(ctx):
    r = ctx.roofline("fl.aa_step", "aa_step")
    return None if r is None else r[0]
