"""What the gRPC front and the wire add, in ms: the generators' mean latency
of a method (send to reply) minus the server's mean handler latency for it
(the ``rpc.server`` timer), over the window."""

import prom

FAMILY = {"/etcdserverpb.KV/Range": 0, "/etcdserverpb.KV/Txn": 1}


def read(ctx, method: str):
    if ctx.before is None:
        return None
    lat = [r[4] - r[3] for r in ctx.recs(FAMILY[method], judged_only=False)
           if r[5]]
    server = prom.mean_delta(ctx.after, ctx.before, "rpc_server_latency_seconds",
                             method=method)
    if not lat or server is None:
        return None
    return (sum(lat) / len(lat) - server) * 1e3
