"""Window seconds (first job's start to last job's end) over the jobs that
reached the target."""


def read(ctx):
    reached = sum(j.reached for j in ctx.jobs)
    return ctx.window_s / reached if reached else None
