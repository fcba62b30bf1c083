"""A paged list of one namespace: pages of ``page`` rows, each next page due
the moment the last one lands and pinned to the first page's revision, as
client-go pages. Pages up to 1,024 rows go to the server's host iterator."""

import etcd

WRITES = False
DEVICE_READ = False


def issue(gen, op: dict, pool: dict, due: float) -> bool:
    t = pool["table"]
    prefix = t.ns_prefix(gen.rng.randrange(t.namespaces))
    walk = {"end": etcd.prefix_end(prefix), "page": int(op["page"]),
            "revision": 0}
    gen.send_range(due, op["op"], prefix, limit=walk["page"], walk=walk,
                   end=walk["end"])
    return True
