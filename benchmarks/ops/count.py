"""kube-apiserver's per-resource object-count poll: a count_only Range over
the whole table, answered by the device scan."""

WRITES = False
DEVICE_READ = True


def issue(gen, op: dict, pool: dict, due: float) -> bool:
    gen.send_range(due, op["op"], pool["table"].prefix, count_only=True)
    return True
