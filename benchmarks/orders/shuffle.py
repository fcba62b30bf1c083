"""The writer's live keys in an order shuffled from the seed, round and
round."""


def pick(gen, pool: dict, remove: bool):
    return gen.next_free(pool, pool["live"], remove)
