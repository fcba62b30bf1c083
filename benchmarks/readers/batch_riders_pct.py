"""Of the requests a batching histogram saw in the window (``sum`` of the
batch sizes), the share that rode another's dispatch (``sum - count``), in
per cent."""

import prom


def read(ctx, name: str):
    if ctx.before is None:
        return None
    members = prom.delta(ctx.after, ctx.before, name + "_sum")
    batches = prom.delta(ctx.after, ctx.before, name + "_count")
    if members <= 0:
        return None
    return 100.0 * (members - batches) / members
