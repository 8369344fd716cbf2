"""Device milliseconds per computed round under scope fl.lm_ssd (the Mamba-2
layers' chunked scan: forward, its recompute and backward), averaged over
the chips."""


def read(ctx):
    t = ctx.phase_s_per_slot("fl.lm_ssd")
    return None if t is None else 1e3 * t
