"""YCSB's ``requestdistribution=uniform``: every record equally likely."""


def draw(rng, n, items, spec):
    return rng.integers(0, items, n)
