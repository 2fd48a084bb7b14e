"""Device time of collectives in the sharded build (the all_to_all that
carries each key to its segment's owner), summed over the chips, per key
the window carried (ns/key)."""
from bench.lib.layer_readers import ns_per_key


def read(ctx):
    return ns_per_key(ctx, "build", "collective")
