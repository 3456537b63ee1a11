"""Output tokens emitted by all requests in the window / the window's
length (host clock)."""
from harness.stats import decode_tokens


def read(ctx):
    return decode_tokens(ctx.window) / ctx.window.seconds
