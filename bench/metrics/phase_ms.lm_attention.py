"""Device milliseconds per computed round under scope fl.lm_attention (the
attention layers' scores, softmax and weighted values: forward, its
recompute and backward), averaged over the chips."""


def read(ctx):
    t = ctx.phase_s_per_slot("fl.lm_attention")
    return None if t is None else 1e3 * t
