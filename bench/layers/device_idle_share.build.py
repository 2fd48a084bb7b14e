"""Share of the traced window in which no operation ran on the device
(%), averaged over the chips used: 1 minus the union of the device's
operation intervals over the window. Read in the cells whose window is
of kind "build"."""
from bench.lib.layer_readers import idle_share


def read(ctx):
    return idle_share(ctx, "build")
