"""YCSB's ``requestdistribution=zipfian``: the scrambled zipfian over the
records, with the mix's ``theta`` (YCSB's 0.99)."""
from bench.lib import keys as K


def draw(rng, n, items, spec):
    return K.scrambled_zipfian(rng, n, items, float(spec["theta"]))
