"""Process start to the first timed query: data generation, load through
the program, warm-up of every query the window sends."""


def read(ctx):
    return ctx.setup_s
