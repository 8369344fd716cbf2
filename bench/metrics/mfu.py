"""Required FLOPs of a round (bench/counts) times live rounds per second of
the traced window, over the cell's chips times the bf16 peak."""


def read(ctx):
    work = ctx.work().get("round")
    if work is None or ctx.window_s <= 0:
        return None
    rate = work[0] * ctx.live_rounds / ctx.window_s
    return 100.0 * rate / (ctx.cell.chips * ctx.peaks["bf16_flops_per_s"])
