"""Share of the traced window in which no operation ran on a chip (%),
averaged over the four chips of the sharded build: 1 minus the union of
each chip's operation intervals over the window."""
from bench.lib.layer_readers import idle_share


def read(ctx):
    return idle_share(ctx, "build")
