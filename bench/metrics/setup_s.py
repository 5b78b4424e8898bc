"""Set-up time: process start, device start-up, input generation and
warm-up (compilation, or loading from the persistent cache), up to the
start of the window."""


def read(ctx):
    return ctx.setup_s
