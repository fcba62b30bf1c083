"""CAS-update a live key of this writer's, picked in the op's ``order``
(``orders/<order>.py``; ``shuffle`` where none is given), with the next
version of its value."""

WRITES = True
DEVICE_READ = False


def issue(gen, op: dict, pool: dict, due: float) -> bool:
    i = gen.pick(pool, op.get("order", "shuffle"), remove=False)
    if i is None:
        return False
    gen.send_write(due, op["op"], pool, i, ver=pool["ver"][i] + 1,
                   guard=pool["rev"][i])
    return True
