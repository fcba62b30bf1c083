"""An unpaged Range over one namespace of the table: an informer's relist.
Each generator process starts at a namespace drawn from the seed and goes on
to the next, so every seed sends the same requests over the same sizes, from
another starting point. ``TpuScanner``'s device path answers it."""

WRITES = False
DEVICE_READ = True


def issue(gen, op: dict, pool: dict, due: float) -> bool:
    t = pool["table"]
    if "ns_cursor" not in pool:
        pool["ns_cursor"] = gen.rng.randrange(t.namespaces)
    pool["ns_cursor"] = (pool["ns_cursor"] + 1) % t.namespaces
    gen.send_range(due, op["op"], t.ns_prefix(pool["ns_cursor"]))
    return True
