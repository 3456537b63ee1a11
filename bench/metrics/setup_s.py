"""Process start to the window's opening: imports, device discovery,
weights or artifact, engine, compiles and warm-up (host clock)."""


def read(ctx):
    return ctx.setup_s
