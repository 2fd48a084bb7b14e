"""Device time of XLA operations other than Pallas kernels and
collectives (phase 1: hashing, fingerprints, the HBM add's row sort),
summed over the chips used, per key the window carried (ns/key)."""
from bench.lib.layer_readers import ns_per_key


def read(ctx):
    return ns_per_key(ctx, "lookup", "xla")
