"""Device milliseconds per computed round of the fused local-trajectory
kernel's own instructions: the Pallas kernel the program names
``fl_local_trajectory_kernel``, found by that name in the trace's
operations (averaged over the chips). The padding copy before the kernel,
which ``roofline.local_trajectory`` counts under the same scope, is left
out. Silent for a program whose kernel carries no such name."""

KERNEL = "fl_local_trajectory_kernel"


def read(ctx):
    if ctx.trace is None or ctx.slots == 0:
        return None
    seconds = sum(s for key, s in ctx.trace.top_ops
                  if KERNEL in key.split(":", 1)[-1])
    if seconds <= 0:
        return None
    return 1e3 * seconds / ctx.cell.chips / ctx.slots
