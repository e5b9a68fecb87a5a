"""Process start to the first instant of the window: build, sampling,
compile or cache load, warm requests."""


def read(ctx):
    return ctx.setup_s
