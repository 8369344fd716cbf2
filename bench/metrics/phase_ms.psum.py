"""Device milliseconds per computed round under scope fl.psum (the sharded
round's cross-client sums: the all-reduces and the sums around them), on
the slowest chip."""
import numpy as np


def read(ctx):
    t = ctx.phase_s_per_slot("fl.psum", reduce=np.max)
    return None if t is None else 1e3 * t
