"""Create a key the table has never held: the next free index of this
writer's residue class (a Txn guarded on mod_revision 0)."""

WRITES = True
DEVICE_READ = False


def issue(gen, op: dict, pool: dict, due: float) -> bool:
    i = pool["next_new"]
    pool["next_new"] += gen.writers
    gen.send_write(due, op["op"], pool, i, ver=0, guard=0)
    return True
