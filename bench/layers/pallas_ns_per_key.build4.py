"""Device time of Pallas kernels in the sharded build (each owner's
segment update, the HBM row kernel), summed over the chips, per key the
window carried (ns/key)."""
from bench.lib.layer_readers import ns_per_key


def read(ctx):
    return ns_per_key(ctx, "build", "pallas")
