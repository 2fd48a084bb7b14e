"""Device time of XLA operations other than Pallas kernels and
collectives in the sharded build (routing keys by owner, phase 1's hash
and the segment's row sort, the copy of each segment the update writes),
summed over the chips, per key the window carried (ns/key)."""
from bench.lib.layer_readers import ns_per_key


def read(ctx):
    return ns_per_key(ctx, "build", "xla")
