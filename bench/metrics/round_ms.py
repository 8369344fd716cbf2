"""Window milliseconds over the live rounds the jobs executed; rounds
computed past a stop inside a job's last chunk count as time, not rounds."""


def read(ctx):
    return 1e3 * ctx.window_s / ctx.live_rounds if ctx.live_rounds else None
