"""Device time of Pallas kernels (phase 2: the row kernels), summed over
the chips used, per key the window carried (ns/key)."""
from bench.lib.layer_readers import ns_per_key


def read(ctx):
    return ns_per_key(ctx, "build", "pallas")
