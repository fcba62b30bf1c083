"""The writer's keys in index order, round and round: every node renews its
Lease once a period."""


def pick(gen, pool: dict, remove: bool):
    return gen.next_free(pool, pool["cycle"], remove)
