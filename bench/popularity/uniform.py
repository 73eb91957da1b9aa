"""Every query equally popular: passes over the pool, each asking every
query once, in an order the seed draws."""

from bench import gen


def stream(traffic: dict, seed: int):
    pool = int(traffic["pool"])
    r = gen.rng(seed, 3)
    while True:
        yield from r.permutation(pool).tolist()
