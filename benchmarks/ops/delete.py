"""CAS-delete a live key of this writer's, picked in the op's ``order``."""

WRITES = True
DEVICE_READ = False


def issue(gen, op: dict, pool: dict, due: float) -> bool:
    i = gen.pick(pool, op.get("order", "shuffle"), remove=True)
    if i is None:
        return False
    gen.send_write(due, op["op"], pool, i, ver=pool["ver"][i],
                   guard=pool["rev"][i], delete=True)
    return True
