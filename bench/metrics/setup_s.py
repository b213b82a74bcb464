"""Set-up: seconds from process start to the window's start (loading,
weights, engine, compiling or reading compiles back, warm-up, and the
load queued or offered before the window)."""


def read(ctx):
    return ctx.setup_s
