"""Device milliseconds per computed round: the union of the intervals in
which an operation ran on the chip, over the traced window, divided by the
rounds computed there (averaged over the chips). The device's work alone,
without the host's turnaround that spreads round_ms from run to run."""


def read(ctx):
    if ctx.trace is None or ctx.slots == 0 or ctx.trace.busy_s <= 0:
        return None
    return 1e3 * ctx.trace.busy_s / ctx.slots
