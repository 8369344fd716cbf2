"""Process start to window start: imports, data, reference, compile and the
warm-up job."""


def read(ctx):
    return ctx.setup_s
