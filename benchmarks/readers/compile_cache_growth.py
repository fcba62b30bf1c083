"""New entries in the persistent compilation cache between the window's two
ends (the server caches every compile on a device, threshold 0): a count
that should read 0."""


def read(ctx):
    return ctx.cache_growth if ctx.before is not None else None
